"""Builder for ``lfm2-24b-a2b``: published keys -> the model arguments of
``models/llama.py`` (a layer pattern as long as the depth: ``layer_types``
gives each layer's mixer, a gated short convolution of ``conv_L_cache``
taps or QK-normed grouped-query attention; the layers before
``num_dense_layers`` carry the dense SwiGLU, the others the sigmoid-scored
experts with their selection bias; the tied head), and a paged
``GenerationEngine`` sized by the mix's ``engine`` group.  The driver
calls ``engine`` and ``seed_expert_bias`` and knows nothing else of the
family."""
from __future__ import annotations


def layer_pattern(cfg: dict) -> list:
    """One entry per layer that is run."""
    experts = {"experts": cfg["num_experts"],
               "top_k": cfg["num_experts_per_tok"],
               "width": cfg["moe_intermediate_size"], "activation": "silu",
               "route_from": "normed", "score": "sigmoid",
               "expert_bias": bool(cfg["use_expert_bias"]),
               "norm_topk": bool(cfg["norm_topk_prob"]),
               "route_scale": float(cfg["routed_scaling_factor"])}
    conv = {"kind": "conv", "L_cache": int(cfg["conv_L_cache"]),
            "bias": bool(cfg["conv_bias"])}
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"lfm2_engine knows conv and full_attention "
                         f"layers, got {sorted(set(kinds))}")
    return [{"mixer": conv if kind == "conv" else "attention",
             "window": None, "rope": True,
             "attn_precision": cfg["as_run"]["attention_precision"],
             "ffn": "dense" if i < cfg["num_dense_layers"] else experts}
            for i, kind in enumerate(kinds)]


def model_args(cfg: dict) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments.  The row's
    ``head_dim`` is null: a head is ``hidden_size / num_attention_heads``."""
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                intermediate=cfg["intermediate_size"],
                rms_norm_eps=cfg["norm_eps"],
                rope_base=float(cfg["rope_parameters"]["rope_theta"]),
                qk_norm=bool(cfg["assumed"]["qk_norm"]),
                tie_head=bool(cfg["assumed"]["tie_word_embeddings"]),
                layer_pattern=layer_pattern(cfg))


def seed_expert_bias(scope, cfg: dict, seed: int, name: str = "llama"):
    """Draw every expert layer's selection bias [E] from ``seed``: normal
    with the standard deviation ``assumed.expert_bias_scale`` (the file
    says why).  The program initialises it to zero and the harness's
    redraw leaves vectors alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.key(np.uint32(int(seed) % 2 ** 32))
    scale = float(cfg["assumed"]["expert_bias_scale"])
    for i in range(cfg["num_hidden_layers"]):
        var = f"{name}.blk{i}.moe.expert_bias"
        if scope.find_var(var) is None:
            continue
        scope.set_var(var, scale * jax.random.normal(
            jax.random.fold_in(key, 7000 + i), (cfg["num_experts"],),
            jnp.float32))


def engine(cfg, mix, *, scope=None, num_slots=None, keep_logits=False,
           buckets=None):
    from paddle_tpu.serving import GenerationEngine

    e = mix["engine"]
    return GenerationEngine(
        model_args(cfg), scope=scope,
        num_slots=num_slots or e["num_slots"],
        max_seq_len=e["max_seq_len"],
        prefill_buckets=buckets or e["prefill_buckets"],
        max_new_tokens=int(mix["output_len"]["max"]),
        queue_cap=4096, deadline_ms=float(mix["deadline_ms"]),
        paged=True, page_tokens=e["page_tokens"],
        prefill_chunk=e["prefill_chunk"], prefix_reuse=e["prefix_reuse"],
        speculate=e["speculate"], attn_impl="auto",
        keep_logits=keep_logits, seed=0, eos_id=-1)
