"""Builder for ``deepseek-v2``: published keys -> the model arguments of
``models/llama.py`` (every layer latent (MLA) attention with interleaved
rotary pairs and YaRN's frequencies, the softmax scale carrying YaRN's
``mscale_all_dim`` factor squared; the layers before
``first_k_dense_replace`` over the dense SwiGLU, the others over this
chip's share of the softmax-routed experts, chosen by group-limited greedy
selection, beside the shared experts, which the program keeps as one
SwiGLU of their summed width; pre-norm RMSNorm; the untied head over the
vocabulary slice), and a paged ``GenerationEngine`` sized by the mix's
``engine`` group, which prefills in chunks over latent pages.  The driver
calls ``require_program`` and ``engine`` and knows nothing else of the
family; an engine built on a scope it is handed first holds the weights to
the configuration's ``assumed.weights_seed`` (``fixed_weights``)."""
from __future__ import annotations


def require_program():
    """The program must know group-limited selection and a chunk program
    over latent pages, or nothing is built: asked of the program's own
    description of itself, before a device is claimed or a weight drawn."""
    import importlib
    import inspect

    # (``paddle_tpu.models`` exports a function of the module's name)
    llama = importlib.import_module("paddle_tpu.models.llama")
    moe = importlib.import_module("paddle_tpu.parallel.moe")
    lacks = []
    if "n_group" not in inspect.signature(moe.route_top_k).parameters:
        lacks.append("parallel/moe.py route_top_k has no 'n_group' "
                     "(group-limited greedy expert selection)")
    if "chunk_pages" not in inspect.signature(llama._mla_mixer).parameters:
        lacks.append("models/llama.py _mla_mixer takes no chunk of rows (a "
                     "chunked prefill over latent pages)")
    if not hasattr(llama, "_mla_scale"):
        lacks.append("models/llama.py has no _mla_scale (YaRN's "
                     "mscale_all_dim in the softmax scale)")
    if lacks:
        raise SystemExit("deepseek_v2_engine: this program cannot run "
                         "deepseek-v2: " + "; ".join(lacks))


def layer_pattern(cfg: dict) -> list:
    """One entry per layer that is run."""
    rs, share = cfg["rope_scaling"], cfg["expert_share"]
    if rs["type"] != "yarn":
        raise ValueError("deepseek_v2_engine builds YaRN")
    if cfg["topk_method"] != "group_limited_greedy" \
            or cfg["scoring_func"] != "softmax" or cfg["moe_layer_freq"] != 1:
        raise ValueError("deepseek_v2_engine builds group-limited greedy "
                         "selection over softmax scores in every layer "
                         "behind the leading dense ones")
    if cfg["hidden_act"] != "silu" or cfg["attention_bias"]:
        raise ValueError("deepseek_v2_engine builds SiLU gates and no "
                         "attention bias")
    mla = {"q_rank": int(cfg["q_lora_rank"]),
           "kv_rank": int(cfg["kv_lora_rank"]),
           "nope_dim": int(cfg["qk_nope_head_dim"]),
           "rope_dim": int(cfg["qk_rope_head_dim"]),
           "v_dim": int(cfg["v_head_dim"]), "interleave": True,
           # (the program derives the softmax scale from these)
           "yarn": {"factor": rs["factor"],
                    "original_max": rs["original_max_position_embeddings"],
                    "beta_fast": rs["beta_fast"],
                    "beta_slow": rs["beta_slow"], "mscale": rs["mscale"],
                    "mscale_all_dim": rs["mscale_all_dim"]}}
    experts = {"experts": int(share["router_experts"]),
               "held": (int(share["first"]), int(cfg["n_routed_experts"])),
               "top_k": cfg["num_experts_per_tok"],
               "width": cfg["moe_intermediate_size"], "activation": "silu",
               "route_from": "normed", "score": "softmax",
               "norm_topk": bool(cfg["norm_topk_prob"]),
               "route_scale": float(cfg["routed_scaling_factor"]),
               "n_group": int(cfg["n_group"]),
               "topk_group": int(cfg["topk_group"]),
               # n shared experts that are SUMMED are one SwiGLU of n
               # times the width
               "shared_width": cfg["moe_intermediate_size"]
               * int(cfg["n_shared_experts"])}
    return [{"mixer": "attention", "mla": mla, "window": None, "rope": True,
             "ffn": "dense" if i < cfg["first_k_dense_replace"]
             else experts}
            for i in range(cfg["num_hidden_layers"])]


def model_args(cfg: dict) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments.  A latent
    layer reads neither ``num_kv_heads`` nor ``head_dim``."""
    require_program()
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                intermediate=cfg["intermediate_size"],
                rms_norm_eps=cfg["rms_norm_eps"],
                rope_base=float(cfg["rope_theta"]),
                tie_head=bool(cfg["tie_word_embeddings"]),
                layer_pattern=layer_pattern(cfg))


def fixed_weights(scope, cfg: dict, name: str = "llama"):
    """``assumed.weights_seed``: every matrix in ``scope`` redrawn from
    THAT seed (``harness.seeded_weights``, the harness's own draw), once
    a scope, whatever ``--seed`` drew there.  With random weights the
    tokens of a run like a few experts far better than the rest, and
    which group those fall in follows the weights' seed: the share of
    pairs this chip's group holds, and with it a chunk's and a step's
    work, then follows the seed and not the traffic (the configuration's
    ``assumed`` has the readings).  A trained model's groups are evenly
    liked (the release trains with a device-level balance loss); it has no
    selection bias to move, so the draw is held instead."""
    seed = cfg["assumed"]["weights_seed"]
    if getattr(scope, "_weights_seed_drawn", None) == seed:
        return
    import jax

    import harness

    names = [n for n in scope.local_var_names()
             if n.startswith(name + ".") and ".pool_" not in n]
    harness.seeded_weights(scope, names, seed)
    jax.block_until_ready([scope.find_var(n) for n in names])
    scope._weights_seed_drawn = seed


def engine(cfg, mix, *, scope=None, num_slots=None, keep_logits=False,
           buckets=None):
    args = model_args(cfg)
    from paddle_tpu.serving import GenerationEngine

    if scope is not None:
        # (before the engine's pools are made: the redraw holds a second
        # copy of the largest matrix for a moment)
        fixed_weights(scope, cfg)

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
