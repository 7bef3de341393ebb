"""Builder for ``nemotron3-super-120b-a12b`` (``model_type`` ``nemotron_h``):
published keys -> the model arguments of ``models/llama.py``.  The letters
of ``hybrid_override_pattern`` become the pattern's entries, ONE sublayer
a layer: ``M`` a state-space duality (Mamba-2) mixer with ``n_groups``
groups of B and C and no FFN, ``*`` grouped-query attention without rotary
embedding and no FFN, ``E`` no mixer and the LatentMoE layer (a sigmoid
router with a selection bias over the full row, this chip's share of the
non-gated ReLU^2 experts in the latent width, the shared expert at full
width); then the untied head over the vocabulary slice, and a paged
``GenerationEngine`` sized by the mix's ``engine`` group.  The driver
calls ``require_program``, ``engine``, ``seed_expert_bias`` and
``seed_delta_gates`` and knows nothing else of the family; the two seeders
are the siblings', by import."""
from __future__ import annotations


def require_program():
    """The program must know a layer of one sublayer, more than one group
    of B and C, experts of two matrices and the latent pair, or nothing is
    built: asked of what the program BUILDS for a two-layer toy of them
    (its caches and its parameters), before a device is claimed or a
    weight drawn."""
    import importlib

    import paddle_tpu as pt

    # (``paddle_tpu.models`` exports a function of the module's name)
    llama = importlib.import_module("paddle_tpu.models.llama")
    ssd = {"kind": "ssd", "heads": 4, "head_dim": 64, "state": 128,
           "groups": 2, "conv": 4}
    experts = {"experts": 4, "top_k": 2, "width": 128, "latent": 64,
               "gated": False, "activation": "relu2",
               "route_from": "normed", "shared_width": 192}
    pattern = [{"mixer": None, "ffn": experts}, {"mixer": ssd, "ffn": None}]
    channels = 4 * 64 + 2 * 2 * 128
    lacks = []
    try:
        spec = llama.cache_spec(
            "probe", 2, pattern, num_slots=1, num_pages=2, page_tokens=16,
            num_kv_heads=1, head_dim=128, hidden=256)
        got = [(e["layer"], e["shape"][1:]) for e in spec]
    except Exception as e:  # noqa: BLE001 — any failure: not built
        got = f"{type(e).__name__}: {e}"
    if got != [(1, [3, channels]), (1, [128, 4 * 64])]:
        lacks.append(f"cache_spec of an FFN-only layer and a mixer-only "
                     f"state-space layer with two groups of B and C gives "
                     f"{got}")
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    try:
        with pt.program_guard(main, startup):
            llama.build_llama_forward(
                1, 8, vocab_size=16, hidden=256, num_layers=2, num_heads=2,
                num_kv_heads=1, intermediate=0, head_dim=128, name="probe",
                attn_impl="xla", layer_pattern=pattern)
        got = {v.name: list(v.shape)
               for v in main.global_block().all_parameters()}
    except Exception as e:  # noqa: BLE001 — any failure: not built
        got = f"{type(e).__name__}: {e}"
    want = {"probe.blk0.moe.router.w": [256, 4],
            "probe.blk0.moe.latent_down.w": [256, 64],
            "probe.blk0.moe.up.w": [4, 64, 128],
            "probe.blk0.moe.down.w": [4, 128, 64],
            "probe.blk0.moe.latent_up.w": [64, 256],
            "probe.blk0.moe.shared_up.w": [256, 192],
            "probe.blk1.ssd_conv.w": [channels, 4]}
    if isinstance(got, str) or any(got.get(n) != s for n, s in want.items()) \
            or "probe.blk0.ln2" in got or "probe.blk1.ln2" in got:
        lacks.append(
            "a layer of latent experts of two matrices alone and a grouped "
            "state-space mixer alone build " + (got if isinstance(got, str)
            else str({n: got.get(n) for n in want})))
    if lacks:
        raise SystemExit("nemotron_h_engine: this program cannot run "
                         "nemotron3-super-120b-a12b: " + "; ".join(lacks))


def layer_pattern(cfg: dict) -> list:
    """One entry per layer that is run."""
    if cfg["expand"] * cfg["hidden_size"] \
            != cfg["mamba_num_heads"] * cfg["mamba_head_dim"]:
        raise ValueError("nemotron_h_engine: expand * hidden_size is not "
                         "mamba_num_heads * mamba_head_dim")
    if cfg["mamba_proj_bias"] or cfg["attention_bias"] or cfg["mlp_bias"] \
            or cfg["use_bias"] or cfg["mamba_hidden_act"] != "silu" \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["sliding_window"] is not None \
            or cfg["moe_shared_expert_overlap"]:
        raise ValueError("nemotron_h_engine builds no projection bias, "
                         "SiLU in the state-space layers, no group limit "
                         "on the router, full attention")
    share = cfg["expert_share"]
    ssd = {"kind": "ssd", "heads": int(cfg["mamba_num_heads"]),
           "head_dim": int(cfg["mamba_head_dim"]),
           "state": int(cfg["ssm_state_size"]),
           "groups": int(cfg["n_groups"]), "conv": int(cfg["conv_kernel"]),
           "conv_bias": bool(cfg["use_conv_bias"])}
    experts = {"experts": int(share["router_experts"]),
               "held": (int(share["first"]), int(cfg["n_routed_experts"])),
               "top_k": int(cfg["num_experts_per_tok"]),
               "width": int(cfg["moe_intermediate_size"]),
               "latent": int(cfg["moe_latent_size"]),
               "activation": cfg["mlp_hidden_act"], "gated": False,
               "route_from": "normed", "score": "sigmoid",
               "expert_bias": True,
               "norm_topk": bool(cfg["norm_topk_prob"]),
               "route_scale": float(cfg["routed_scaling_factor"]),
               "shared_width": int(cfg["moe_shared_expert_intermediate_size"])
               * int(cfg["n_shared_experts"])}
    entries = {
        "M": {"mixer": ssd, "ffn": None},
        "*": {"mixer": "attention", "ffn": None, "rope": False,
              "window": None,
              "attn_precision": cfg["as_run"]["attention_precision"]},
        "E": {"mixer": None, "ffn": experts}}
    letters = cfg["hybrid_override_pattern"]
    if len(letters) != cfg["num_hidden_layers"] or set(letters) - set(entries):
        raise ValueError(f"nemotron_h_engine knows the letters M, * and E, "
                         f"one a layer: got {letters!r} for "
                         f"{cfg['num_hidden_layers']} layers")
    return [entries[c] for c in letters]


def model_args(cfg: dict) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments.
    ``intermediate_size`` is a dense FFN's width, and no layer has one."""
    require_program()
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"],
                intermediate=cfg["intermediate_size"],
                rms_norm_eps=cfg["layer_norm_epsilon"],
                tie_head=bool(cfg["tie_word_embeddings"]),
                layer_pattern=layer_pattern(cfg))


def _sibling(name):
    """A builder beside this file, loaded by its path."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "benchmark_builders_" + name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_expert_bias(scope, cfg: dict, seed: int, name: str = "llama"):
    """Every expert layer's selection bias [router_experts] from ``seed``
    (``lfm2_engine``'s rule and code: normal at
    ``assumed.expert_bias_scale``, expert layers only)."""
    _sibling("lfm2_engine").seed_expert_bias(
        scope, dict(cfg, num_experts=cfg["expert_share"]["router_experts"]),
        seed, name)


def seed_delta_gates(scope, cfg: dict, seed: int, name: str = "llama"):
    """Every state-space layer's ``A_log`` and ``dt_bias`` [heads] from
    ``seed`` (``granite_hybrid_engine``'s rule and code: A uniform in (1,
    16), dt log-uniform in [``time_step_min``, ``time_step_max``], which
    lies over ``time_step_floor``)."""
    if (cfg["time_step_min"], cfg["time_step_max"]) != (0.001, 0.1) \
            or cfg["time_step_floor"] > cfg["time_step_min"]:
        raise ValueError("nemotron_h_engine draws dt as the sibling does: "
                         "log-uniform in [0.001, 0.1], over the floor")
    _sibling("granite_hybrid_engine").seed_delta_gates(
        scope, dict(cfg, mamba_n_heads=cfg["mamba_num_heads"]), seed, name)


def engine(cfg, mix, *, scope=None, num_slots=None, keep_logits=False,
           buckets=None):
    args = model_args(cfg)
    from paddle_tpu.serving import GenerationEngine

    e = mix["engine"]
    return GenerationEngine(
        args, scope=scope,
        num_slots=num_slots or e["num_slots"],
        max_seq_len=e["max_seq_len"],
        prefill_buckets=buckets or e["prefill_buckets"],
        max_new_tokens=int(mix["output_len"]["max"]),
        queue_cap=4096, deadline_ms=float(mix["deadline_ms"]),
        paged=True, page_tokens=e["page_tokens"],
        prefill_chunk=e["prefill_chunk"], prefix_reuse=e["prefix_reuse"],
        speculate=e["speculate"], attn_impl="auto",
        keep_logits=keep_logits, seed=0, eos_id=int(cfg["assumed"]["eos_id"]))
