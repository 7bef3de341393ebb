"""Builder for ``olmo-hybrid-7b``: published keys -> the model arguments of
``models/llama.py`` (a layer pattern as long as the depth: ``layer_types``
gives each layer's mixer, gated delta-rule linear attention or full
attention without rotary embedding; the norms on the mixer's and the
MLP's output; QK-norm over the whole projection; the untied head), and a
paged ``GenerationEngine`` sized by the mix's ``engine`` group.  The
driver calls ``engine`` and ``seed_delta_gates`` and knows nothing else of
the family."""
from __future__ import annotations


def layer_pattern(cfg: dict) -> list:
    """One entry per layer that is run."""
    delta = {"kind": "gated_delta",
             "key_heads": int(cfg["linear_num_key_heads"]),
             "value_heads": int(cfg["linear_num_value_heads"]),
             "key_dim": int(cfg["linear_key_head_dim"]),
             "value_dim": int(cfg["linear_value_head_dim"]),
             "conv": int(cfg["linear_conv_kernel_dim"]),
             "neg_eigval": bool(cfg["linear_allow_neg_eigval"])}
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if set(kinds) - {"linear_attention", "full_attention"}:
        raise ValueError(f"olmo_hybrid_engine knows linear_attention and "
                         f"full_attention layers, got {sorted(set(kinds))}")
    if cfg["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("olmo_hybrid_engine builds the full layers "
                         "without rotary embedding (rope_theta null)")
    return [{"mixer": delta if kind == "linear_attention" else "attention",
             "window": None, "rope": False, "ffn": "dense",
             "attn_precision": cfg["as_run"]["attention_precision"]}
            for kind in kinds]


def model_args(cfg: dict) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments.  The row's
    ``head_dim`` is null: a head is ``hidden_size / num_attention_heads``."""
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                intermediate=cfg["intermediate_size"],
                rms_norm_eps=cfg["rms_norm_eps"],
                qk_norm=cfg["assumed"]["qk_norm"],
                norm=cfg["assumed"]["norm"],
                tie_head=bool(cfg["tie_word_embeddings"]),
                layer_pattern=layer_pattern(cfg))


def seed_delta_gates(scope, cfg: dict, seed: int, name: str = "llama"):
    """Draw every linear layer's ``A_log`` and ``dt_bias`` [heads] from
    ``seed`` as the family's modelling code initialises them (A uniform
    in (0, 16), ``A_log = log A``; dt log-uniform in [0.001, 0.1],
    ``dt_bias = dt + log(-expm1(-dt))``), so that decay differs by head
    and by seed.  The program draws them from the layer's name and the
    harness's redraw leaves vectors alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.key(np.uint32(int(seed) % 2 ** 32))
    heads = int(cfg["linear_num_value_heads"])
    for i in range(cfg["num_hidden_layers"]):
        var = f"{name}.blk{i}.gdn_A_log"
        if scope.find_var(var) is None:
            continue
        ka, kd = jax.random.split(jax.random.fold_in(key, 4100 + i))
        a = jax.random.uniform(ka, (heads,), jnp.float32, 1e-3, 16.0)
        dt = jnp.exp(jax.random.uniform(kd, (heads,), jnp.float32,
                                        np.log(1e-3), np.log(0.1)))
        scope.set_var(var, jnp.log(a))
        scope.set_var(f"{name}.blk{i}.gdn_dt_bias",
                      dt + jnp.log(-jnp.expm1(-dt)))


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
        keep_logits=keep_logits, seed=0, eos_id=int(cfg["assumed"]["eos_id"]))
