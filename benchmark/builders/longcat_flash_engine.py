"""Builder for ``longcat-flash-chat``: published keys -> the model arguments
of ``models/llama.py`` and a paged ``GenerationEngine`` sized by the mix's
``engine`` group, which prefills in chunks over latent pages.

A published layer (two latent-attention sublayers, two dense SwiGLUs, one
expert branch that leaves behind the first sublayer and joins behind the
second dense SwiGLU) is TWO pattern layers: the first with ``"ffn":
"dense"`` and the ``"branch"`` (the router over all its 768 outputs, of
which the last 256 are identity experts, chosen by ``softmax + bias``,
weighed ``6 p`` unnormalised; this chip's share of the 512 real experts),
the second with ``"ffn": "dense"`` and ``"join": True``.  So the program
runs ``2 * num_layers`` layers, each with a latent cache of its own; both
inner norms of latent attention carry the configuration's
``mla_scale_*_lora`` factors.  The driver calls ``require_program`` and
``engine`` and knows nothing else of the family; an engine built on a
scope it is handed first draws the selection bias there
(``seed_expert_bias``)."""
from __future__ import annotations


def require_program():
    """The program must know identity experts under a softmax router with
    a selection bias and a branch that joins the stream a sublayer later,
    or nothing is built: asked of the program's own description of itself,
    before a device is claimed or a weight drawn."""
    import importlib
    import inspect

    # (``paddle_tpu.models`` exports a function of the module's name)
    llama = importlib.import_module("paddle_tpu.models.llama")
    moe = importlib.import_module("paddle_tpu.parallel.moe")
    lacks = []
    if not hasattr(moe, "_softmax_biased"):
        lacks.append("parallel/moe.py route_top_k has no softmax router "
                     "that chooses by softmax + bias and weighs by the "
                     "unbiased softmax (_softmax_biased)")
    if "zero_experts" not in inspect.signature(
            moe.moe_routed_tokens).parameters:
        lacks.append("parallel/moe.py moe_routed_tokens has no "
                     "'zero_experts' (identity experts: a pick that adds "
                     "its weight times the row and reaches no matmul)")
    if "branch" not in llama.DEFAULT_LAYER \
            or "carry" not in inspect.signature(llama.llama_block).parameters:
        lacks.append("models/llama.py has no layer-pattern 'branch' / "
                     "'join' (an expert branch carried past a sublayer)")
    if lacks:
        raise SystemExit("longcat_flash_engine: this program cannot run "
                         "longcat-flash-chat: " + "; ".join(lacks))


def layer_pattern(cfg: dict) -> list:
    """The two pattern layers of one published layer."""
    share = cfg["expert_share"]
    if cfg["attention_method"] != "MLA" or cfg["attention_bias"]:
        raise ValueError("longcat_flash_engine builds latent attention "
                         "without bias")
    if cfg["zero_expert_type"] != "identity" \
            or int(share["zero_experts"]) != int(cfg["zero_expert_num"]):
        raise ValueError("longcat_flash_engine builds identity experts as "
                         "the last zero_expert_num of the router's outputs")
    hidden = cfg["hidden_size"]
    mla = {"q_rank": int(cfg["q_lora_rank"]),
           "kv_rank": int(cfg["kv_lora_rank"]),
           "nope_dim": int(cfg["qk_nope_head_dim"]),
           "rope_dim": int(cfg["qk_rope_head_dim"]),
           "v_dim": int(cfg["v_head_dim"]), "interleave": True,
           "q_norm_scale": (hidden / cfg["q_lora_rank"]) ** 0.5
           if cfg["mla_scale_q_lora"] else 1.0,
           "kv_norm_scale": (hidden / cfg["kv_lora_rank"]) ** 0.5
           if cfg["mla_scale_kv_lora"] else 1.0}
    experts = {"experts": int(share["router_experts"]),
               "zero_experts": int(cfg["zero_expert_num"]),
               "held": (int(share["first"]), int(cfg["n_routed_experts"])),
               "top_k": int(cfg["moe_topk"]),
               "width": int(cfg["expert_ffn_hidden_size"]),
               "activation": "silu", "route_from": "normed",
               "score": "softmax", "expert_bias": True, "norm_topk": False,
               "route_scale": float(cfg["routed_scaling_factor"])}
    sublayer = {"mixer": "attention", "mla": mla, "window": None,
                "rope": True, "ffn": "dense"}
    return [dict(sublayer, branch=experts), dict(sublayer, join=True)]


def model_args(cfg: dict) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments.  A latent
    layer reads neither ``num_kv_heads`` nor ``head_dim``."""
    require_program()
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=2 * cfg["num_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_attention_heads"],
                head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                intermediate=cfg["ffn_hidden_size"],
                rms_norm_eps=cfg["rms_norm_eps"],
                rope_base=float(cfg["rope_theta"]), tie_head=False,
                layer_pattern=layer_pattern(cfg))


def seed_expert_bias(scope, cfg: dict, name: str = "llama"):
    """Every branch's selection bias [768] drawn non-zero, once a scope:
    normal at ``assumed.expert_bias_scale`` from ``assumed.
    expert_bias_seed`` (the file says why: the program initialises it to
    zero, the harness's redraw leaves vectors alone, and a bias of zeros
    would leave "moves the choice, never the weights" unchecked)."""
    if getattr(scope, "_expert_bias_drawn", False):
        return
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.key(np.uint32(
        int(cfg["assumed"]["expert_bias_seed"]) % 2 ** 32))
    scale = float(cfg["assumed"]["expert_bias_scale"])
    for i in range(cfg["num_layers"]):
        scope.set_var(
            f"{name}.blk{2 * i}.moe.expert_bias",
            scale * jax.random.normal(
                jax.random.fold_in(key, 6600 + i),
                (int(cfg["expert_share"]["router_experts"]),), jnp.float32))
    scope._expert_bias_drawn = True


def engine(cfg, mix, *, scope=None, num_slots=None, keep_logits=False,
           buckets=None):
    args = model_args(cfg)
    from paddle_tpu.serving import GenerationEngine

    if scope is not None:
        seed_expert_bias(scope, cfg)
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
