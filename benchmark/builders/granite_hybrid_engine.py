"""Builder for ``granite-4.0-h-micro``: published keys -> the model
arguments of ``models/llama.py`` (a layer pattern as long as the depth:
``layer_types`` gives each layer's mixer, a state-space duality (Mamba-2)
layer or grouped-query attention without rotary embedding; the dense
SwiGLU in every layer; the family's four multipliers; the tied head), and a
paged ``GenerationEngine`` sized by the mix's ``engine`` group.  The driver
calls ``engine`` and ``seed_delta_gates`` and knows nothing else of the
family.  On a tree whose program lacks the mixer or the multipliers the
module refuses as it is loaded, with a message, before a weight is
drawn."""
from __future__ import annotations


def require_program():
    """The program must know the state-space mixer and the three scalars
    the family multiplies by, or nothing is built: asked of the program's
    own description of itself."""
    import importlib
    import inspect

    # (``paddle_tpu.models`` exports a function of the module's name)
    llama = importlib.import_module("paddle_tpu.models.llama")
    lacks = []
    if not hasattr(llama, "_ssd_mixer"):
        lacks.append("models/llama.py has no _ssd_mixer (a state-space "
                     "duality layer: mixer kind 'ssd')")
    takes = inspect.signature(llama.build_llama_decode).parameters
    for key in ("embed_scale", "residual_scale", "attn_scale"):
        if key not in takes:
            lacks.append(f"models/llama.py build_llama_decode takes no "
                         f"'{key}'")
    if lacks:
        raise SystemExit("granite_hybrid_engine: this program cannot run "
                         "granite-4.0-h-micro: " + "; ".join(lacks))


require_program()


def layer_pattern(cfg: dict) -> list:
    """One entry per layer that is run."""
    if cfg["mamba_expand"] * cfg["hidden_size"] \
            != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("granite_hybrid_engine: mamba_expand * hidden_size "
                         "is not mamba_n_heads * mamba_d_head")
    if cfg["mamba_proj_bias"] or cfg["attention_bias"] \
            or cfg["num_local_experts"] or cfg["hidden_act"] != "silu" \
            or cfg["position_embedding_type"] != "nope" \
            or cfg["normalization_function"] != "rmsnorm" \
            or cfg["shared_intermediate_size"] != cfg["intermediate_size"]:
        raise ValueError("granite_hybrid_engine builds no projection bias, "
                         "no routed experts, SiLU gates, RMSNorm, no "
                         "position embedding and one shared SwiGLU a layer")
    ssd = {"kind": "ssd", "heads": int(cfg["mamba_n_heads"]),
           "head_dim": int(cfg["mamba_d_head"]),
           "state": int(cfg["mamba_d_state"]),
           "groups": int(cfg["mamba_n_groups"]),
           "conv": int(cfg["mamba_d_conv"]),
           "conv_bias": bool(cfg["mamba_conv_bias"])}
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"granite_hybrid_engine knows mamba and attention "
                         f"layers, got {sorted(set(kinds))}")
    return [{"mixer": ssd if kind == "mamba" else "attention",
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
                intermediate=cfg["shared_intermediate_size"],
                rms_norm_eps=cfg["rms_norm_eps"],
                tie_head=bool(cfg["tie_word_embeddings"]),
                embed_scale=float(cfg["embedding_multiplier"]),
                residual_scale=float(cfg["residual_multiplier"]),
                attn_scale=float(cfg["attention_multiplier"]),
                logit_scale=1.0 / float(cfg["logits_scaling"]),
                layer_pattern=layer_pattern(cfg))


def seed_delta_gates(scope, cfg: dict, seed: int, name: str = "llama"):
    """Draw every state-space layer's ``A_log`` and ``dt_bias`` [heads]
    from ``seed`` as the family's modelling code initialises them (A
    uniform in (1, 16), ``A_log = log A``; dt log-uniform in [0.001, 0.1],
    ``dt_bias = dt + log(-expm1(-dt))``; D stays ones), so that decay
    differs by head, layer and seed.  The program draws them from the
    layer's name and the harness's redraw leaves vectors alone.  (The name
    is the driver's, ``serve_delta``: the constants of a recurrence's
    decay.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.key(np.uint32(int(seed) % 2 ** 32))
    heads = int(cfg["mamba_n_heads"])
    for i in range(cfg["num_hidden_layers"]):
        var = f"{name}.blk{i}.ssd_A_log"
        if scope.find_var(var) is None:
            continue
        ka, kd = jax.random.split(jax.random.fold_in(key, 5900 + i))
        a = jax.random.uniform(ka, (heads,), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(kd, (heads,), jnp.float32,
                                        np.log(1e-3), np.log(0.1)))
        scope.set_var(var, jnp.log(a))
        scope.set_var(f"{name}.blk{i}.ssd_dt_bias",
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
