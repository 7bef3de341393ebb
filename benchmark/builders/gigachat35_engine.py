"""Builder for ``gigachat35-432b-a28b``: published keys -> the model
arguments of ``models/llama.py`` (a layer pattern as long as the depth:
``full_attention_layers`` names the latent (MLA) attention layers, with
interleaved rotary pairs, YaRN's frequencies and an output gate; the
others are the gated delta rule with two value heads a key head and a
full-rank ``2 sigmoid`` output gate; the layers before
``first_k_dense_replace`` carry the dense SwiGLU, the others this chip's
share of the routed experts beside the shared expert; every SwiGLU clamped
at ``swiglu_limit``; the sandwich norm layout; the untied head over the
vocabulary slice), and a paged ``GenerationEngine`` sized by the mix's
``engine`` group.  The driver calls ``require_program``, ``engine``,
``seed_expert_bias`` and ``seed_delta_gates`` and knows nothing else of
the family; the two seeders are the siblings', by import."""
from __future__ import annotations

import math


def require_program():
    """The program must know a latent cache kind, or nothing is built:
    asked of the program's own description of its layers, before a device
    is claimed or a weight drawn."""
    import importlib

    # (``paddle_tpu.models`` exports a function of the module's name)
    llama = importlib.import_module("paddle_tpu.models.llama")
    lacks = []
    if "mla" not in llama.DEFAULT_LAYER:
        lacks.append("models/llama.py's layer pattern has no 'mla' (a "
                     "latent attention layer and its latent_pages cache)")
    else:
        try:
            kinds = {e["kind"] for e in llama.cache_spec(
                "probe", 1, [{"mla": {"kv_rank": 512, "rope_dim": 64}}],
                num_slots=1, num_pages=2, page_tokens=16, num_kv_heads=1,
                head_dim=128, hidden=128)}
        except Exception as e:  # noqa: BLE001 — any failure: not built
            kinds = {f"error: {e}"}
        if kinds != {"latent_pages"}:
            lacks.append(f"cache_spec gives {sorted(kinds)} for a latent "
                         f"layer, not latent_pages")
    if lacks:
        raise SystemExit("gigachat35_engine: this program cannot run "
                         "gigachat35-432b-a28b: " + "; ".join(lacks))


def softmax_scale(cfg: dict) -> float:
    """``(nope + rope)^-1/2``, times ``(0.1 ln factor + 1)^2`` under
    ``use_mla_scaling_factor``."""
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if cfg["use_mla_scaling_factor"]:
        scale *= (0.1 * math.log(cfg["rope_scaling"]["factor"]) + 1.0) ** 2
    return scale


def layer_pattern(cfg: dict) -> list:
    """One entry per layer that is run."""
    rs, share = cfg["rope_scaling"], cfg["expert_share"]
    if rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("gigachat35_engine builds YaRN with a cos / sin "
                         "factor of 1 (mscale == mscale_all_dim)")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("gigachat35_engine builds groups of one "
                         "(grouped expert selection is not built)")
    if cfg["use_shared_expert_sigmoid"] or cfg["hidden_act"] != "silu":
        raise ValueError("gigachat35_engine builds an ungated shared "
                         "expert and SiLU gates")
    mla = {"q_rank": int(cfg["q_lora_rank"]),
           "kv_rank": int(cfg["kv_lora_rank"]),
           "nope_dim": int(cfg["qk_nope_head_dim"]),
           "rope_dim": int(cfg["qk_rope_head_dim"]),
           "v_dim": int(cfg["v_head_dim"]), "scale": softmax_scale(cfg),
           "interleave": bool(cfg["rope_interleave"]),
           "yarn": {"factor": rs["factor"],
                    "original_max": rs["original_max_position_embeddings"],
                    "beta_fast": rs["beta_fast"],
                    "beta_slow": rs["beta_slow"]}}
    delta = {"kind": "gated_delta",
             "key_heads": int(cfg["linear_num_key_heads"]),
             "value_heads": int(cfg["linear_num_value_heads"]),
             "key_dim": int(cfg["linear_key_head_dim"]),
             "value_dim": int(cfg["linear_value_head_dim"]),
             "conv": int(cfg["linear_conv_kernel_dim"]),
             "gate": "sigmoid",
             "gate_scale": float(cfg["linear_sigmoid_gate_scale"])}
    experts = {"experts": int(share["router_experts"]),
               "held": (int(share["first"]), int(cfg["n_routed_experts"])),
               "top_k": cfg["num_experts_per_tok"],
               "width": cfg["moe_intermediate_size"], "activation": "silu",
               "route_from": "normed", "score": "sigmoid",
               "expert_bias": True,
               "norm_topk": bool(cfg["norm_topk_prob"]),
               "route_scale": float(cfg["routed_scaling_factor"]),
               "shared_width": cfg["moe_intermediate_size"]
               * int(cfg["n_shared_experts"])}
    latent = set(cfg["full_attention_layers"])
    return [dict({"mixer": "attention", "mla": mla,
                  "attn_gate": bool(cfg["gated_attention"])}
                 if i in latent else {"mixer": delta},
                 window=None, rope=True,
                 ffn="dense" if i < cfg["first_k_dense_replace"]
                 else experts,
                 swiglu_limit=float(cfg["swiglu_limit"]))
            for i in range(cfg["num_hidden_layers"])]


def model_args(cfg: dict) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments.  A latent
    layer reads neither ``num_kv_heads`` nor ``head_dim``."""
    require_program()
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["qk_head_dim"],
                intermediate=cfg["intermediate_size"],
                rms_norm_eps=cfg["rms_norm_eps"],
                rope_base=float(cfg["rope_theta"]),
                tie_head=bool(cfg["tie_word_embeddings"]),
                norm=cfg["layernorm_type"],
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
    ``assumed.expert_bias_scale``, expert layers only), then, where the
    configuration states ``assumed.bias_balance``, moved as the release
    trains it: until the experts' loads are even (:func:`balance_bias`)."""
    _sibling("lfm2_engine").seed_expert_bias(
        scope, dict(cfg, num_experts=cfg["expert_share"]["router_experts"]),
        seed, name)
    if cfg["assumed"].get("bias_balance"):
        balance_bias(scope, cfg, seed, name)


def router_sample(scope, cfg: dict, seed: int):
    """Pre-sigmoid router logits [rows, expert layers, router_experts] of
    ``assumed.bias_balance``'s sample: seeded prompts and the tokens the
    model answers them with, through a small engine on the weights in
    ``scope`` (closed, its pool and state out of the scope, on return)."""
    import numpy as np

    from paddle_tpu.serving import GenerationEngine

    cal = cfg["assumed"]["bias_balance"]
    rng = np.random.default_rng([int(seed), 4747])
    gen = GenerationEngine(
        model_args(cfg), scope=scope, num_slots=cal["slots"],
        max_seq_len=cal["positions"], prefill_buckets=[cal["rung"]],
        max_new_tokens=cal["new_tokens"], queue_cap=4096,
        deadline_ms=600000.0, paged=True, page_tokens=cal["page_tokens"],
        prefill_chunk=0, prefix_reuse=False, speculate=False,
        attn_impl="auto", keep_logits=True, seed=0,
        eos_id=int(cfg["assumed"]["eos_id"]))
    try:
        futures = [gen.submit(rng.integers(0, cfg["vocab_size"],
                                           int(n)).tolist(),
                              cal["new_tokens"])
                   for n in rng.integers(cal["rung"] // 2, cal["rung"] + 1,
                                         cal["requests"])]
        return np.concatenate([np.stack(f.result(600)["router_logits"])
                               for f in futures]).astype("float64")
    finally:
        gen.close()
        scope.erase(list(gen.cache_names) + list(gen.state_names))


def balance_bias(scope, cfg: dict, seed: int, name: str = "llama"):
    """Move every expert layer's selection bias until the loads over a
    sample are even, by the rule the family trains it with (DeepSeek-V3's
    auxiliary-loss-free balancing: after a batch, an overloaded expert's
    bias goes down by a step and an underloaded one's up), the step
    decaying; the seeded draw is where it starts.  Random weights route
    unevenly (one expert of 256 gets twelve to sixteen times the mean
    load), and which experts are the popular ones changes with the seed:
    the work of a chip that holds 8 of them then follows the seed, not
    the traffic.  The bias moves the choice and never the weights."""
    import jax.numpy as jnp
    import numpy as np

    cal = cfg["assumed"]["bias_balance"]
    top_k = int(cfg["num_experts_per_tok"])
    scores = 1.0 / (1.0 + np.exp(-router_sample(scope, cfg, seed)))
    experts = scores.shape[-1]
    worst = []
    layers = [i for i in range(cfg["num_hidden_layers"])
              if i >= cfg["first_k_dense_replace"]]
    for j, i in enumerate(layers):
        var = f"{name}.blk{i}.moe.expert_bias"
        bias = np.asarray(scope.find_var(var), "float64")

        def loads(b):
            chosen = np.argpartition(-(scores[:, j] + b), top_k - 1,
                                     axis=-1)[:, :top_k]
            return np.bincount(chosen.ravel(), minlength=experts)

        before, step = loads(bias), float(cal["step"])
        for _ in range(int(cal["iterations"])):
            load = loads(bias)
            bias -= step * np.sign(load - load.mean())
            step *= float(cal["decay"])
        worst.append((before.max() / before.mean(),
                      loads(bias).max() / before.mean()))
        scope.set_var(var, jnp.asarray(bias, jnp.float32))
    print(f"[gigachat35_engine] selection bias balanced over "
          f"{scores.shape[0]} sampled rows: the fullest expert's load over "
          f"the mean, layer by layer, "
          + ", ".join(f"{a:.1f} -> {b:.2f}" for a, b in worst), flush=True)


def seed_delta_gates(scope, cfg: dict, seed: int, name: str = "llama"):
    """Every delta layer's ``A_log`` and ``dt_bias``, one a VALUE head,
    from ``seed`` (``olmo_hybrid_engine``'s rule and code)."""
    _sibling("olmo_hybrid_engine").seed_delta_gates(scope, cfg, seed, name)


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
