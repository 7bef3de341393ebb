"""Builder for ``solar-open2-250b``: published keys -> the model arguments of
``models/llama.py`` (a layer pattern as long as the depth: ``gqa_layers``
names the softmax-attention layers, without rotary embedding and with an
output gate; the others are Kimi Delta Attention, the gated delta rule
with a log decay a key channel; every layer's FFN is this chip's share of
the routed experts beside the shared expert; the untied head over the
vocabulary slice), and a paged ``GenerationEngine`` sized by the mix's
``engine`` group.  The driver calls ``require_program``, ``engine``,
``seed_expert_bias`` and ``seed_delta_gates`` and knows nothing else of
the family."""
from __future__ import annotations


def require_program():
    """The program must know an expert layer's held range and a decay a
    key channel, or nothing is built: a program that ignored the range
    would allocate every expert the router scores."""
    import inspect

    from paddle_tpu import layers
    from paddle_tpu.ops import gated_delta_ops

    lacks = []
    if "held" not in inspect.signature(layers.moe_routed_ffn).parameters:
        lacks.append("layers.moe_routed_ffn has no 'held' (an expert "
                     "layer told which experts it holds)")
    if not hasattr(gated_delta_ops, "chunk_terms_channel"):
        lacks.append("ops/gated_delta_ops.py has no chunk_terms_channel "
                     "(a log decay a key channel)")
    if lacks:
        raise SystemExit("solar_open2_engine: this program cannot run "
                         "solar-open2-250b: " + "; ".join(lacks))


def layer_pattern(cfg: dict) -> list:
    """One entry per layer that is run."""
    lin = cfg["linear_attn_config"]
    if lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise ValueError("solar_open2_engine builds one state a head "
                         "(linear_attn_config.num_kv_heads null)")
    if cfg["use_rope"] or cfg["first_k_dense_replace"]:
        raise ValueError("solar_open2_engine builds NoPE attention and no "
                         "leading dense layer")
    share, low = cfg["expert_share"], cfg["assumed"]["low_rank"]
    kda = {"kind": "gated_delta", "key_heads": int(lin["num_heads"]),
           "value_heads": int(lin["num_heads"]),
           "key_dim": int(lin["head_dim"]), "value_dim": int(lin["head_dim"]),
           "conv": int(lin["short_conv_kernel_size"]),
           "neg_eigval": bool(cfg["kda_allow_neg_eigval"]),
           "decay": "channel", "decay_rank": int(low),
           "gate": "sigmoid", "gate_rank": int(low)}
    if cfg["kda_use_full_proj"]:
        raise ValueError("solar_open2_engine builds the low-rank decay and "
                         "gate projections (kda_use_full_proj false)")
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
    return [{"mixer": "attention" if i in cfg["gqa_layers"] else kda,
             "window": None, "rope": False, "ffn": experts,
             "attn_gate": bool(cfg["use_gqa_gate"]),
             "attn_precision": cfg["as_run"]["attention_precision"]}
            for i in range(cfg["num_hidden_layers"])]


def model_args(cfg: dict) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments.  There is
    no dense FFN (``first_k_dense_replace`` 0), so ``intermediate`` is
    never used."""
    require_program()
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], intermediate=0,
                rms_norm_eps=cfg["rms_norm_eps"],
                tie_head=bool(cfg["tie_word_embeddings"]),
                layer_pattern=layer_pattern(cfg))


def _key(seed):
    import jax
    import numpy as np

    return jax.random.key(np.uint32(int(seed) % 2 ** 32))


def seed_expert_bias(scope, cfg: dict, seed: int, name: str = "llama"):
    """Draw every layer's selection bias [router_experts] from ``seed``:
    normal with the standard deviation ``assumed.expert_bias_scale`` (the
    file says why).  The program initialises it to zero and the harness's
    redraw leaves vectors alone."""
    import jax
    import jax.numpy as jnp

    scale = float(cfg["assumed"]["expert_bias_scale"])
    for i in range(cfg["num_hidden_layers"]):
        scope.set_var(f"{name}.blk{i}.moe.expert_bias",
                      scale * jax.random.normal(
                          jax.random.fold_in(_key(seed), 7000 + i),
                          (int(cfg["expert_share"]["router_experts"]),),
                          jnp.float32))


def seed_delta_gates(scope, cfg: dict, seed: int, name: str = "llama"):
    """Draw every KDA layer's ``A_log`` [heads] and ``dt_bias`` [heads *
    head_dim] from ``seed`` as the family's modelling code initialises
    them (A uniform in (0, 16), ``A_log = log A``; dt log-uniform in
    [0.001, 0.1], ``dt_bias = dt + log(-expm1(-dt))``), so that decay
    differs by head, channel, layer and seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lin = cfg["linear_attn_config"]
    heads, d = int(lin["num_heads"]), int(lin["head_dim"])
    for i in range(cfg["num_hidden_layers"]):
        var = f"{name}.blk{i}.gdn_A_log"
        if scope.find_var(var) is None:
            continue
        ka, kd = jax.random.split(jax.random.fold_in(_key(seed), 4300 + i))
        a = jax.random.uniform(ka, (heads,), jnp.float32, 1e-3, 16.0)
        dt = jnp.exp(jax.random.uniform(kd, (heads * d,), jnp.float32,
                                        np.log(1e-3), np.log(0.1)))
        scope.set_var(var, jnp.log(a))
        scope.set_var(f"{name}.blk{i}.gdn_dt_bias",
                      dt + jnp.log(-jnp.expm1(-dt)))


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
