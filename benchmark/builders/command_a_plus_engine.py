"""Builder for ``command-a-plus-05-2026``: published keys -> the model
arguments of ``models/llama.py`` (a layer pattern as long as the depth:
``layer_types`` names the sliding-window layers, with interleaved rotary
pairs, and the full layers, with none; every layer's FFN is this chip's
share of the sigmoid-routed experts beside the four shared experts, which
the program keeps as one SwiGLU of four times the width at a quarter; the
parallel block under one LayerNorm; the tied head over the vocabulary
slice), and a paged ``GenerationEngine`` sized by the mix's ``engine``
group, which prefills in chunks over both page kinds.  The driver calls
``require_program`` and ``engine`` and knows nothing else of the
family."""
from __future__ import annotations


def require_program():
    """The program must know the parallel block and a chunk program over
    two page kinds, or nothing is built: asked of the program's own
    description of itself, before a device is claimed or a weight
    drawn."""
    import importlib
    import inspect

    # (``paddle_tpu.models`` exports a function of the module's name)
    llama = importlib.import_module("paddle_tpu.models.llama")
    lacks = []
    try:
        llama._norm_modes("parallel")
    except Exception:  # noqa: BLE001 — any failure: not built
        lacks.append("models/llama.py has no norm layout 'parallel' (one "
                     "norm a layer, x + attention(h) + ffn(h))")
    if "norm_kind" not in inspect.signature(llama.llama_block).parameters:
        lacks.append("llama_block has no 'norm_kind' (a LayerNorm in the "
                     "decoder block)")
    if "num_window_pages" not in inspect.signature(
            llama._chunk_forward).parameters:
        lacks.append("the chunk program takes no second block table (a "
                     "chunked prefill over full and window pages)")
    if lacks:
        raise SystemExit("command_a_plus_engine: this program cannot run "
                         "command-a-plus-05-2026: " + "; ".join(lacks))


def layer_pattern(cfg: dict) -> list:
    """One entry per layer that is run."""
    if cfg["first_k_dense_replace"] or cfg["use_qk_norm"] \
            or cfg["attention_bias"]:
        raise ValueError("command_a_plus_engine builds no leading dense "
                         "layer, no QK-norm and no attention bias")
    if cfg["position_embedding_type"] != "rope_gptj" \
            or cfg["rotary_pct"] != 1:
        raise ValueError("command_a_plus_engine builds interleaved rotary "
                         "pairs over the whole head (rope_gptj, rotary_pct "
                         "1)")
    if cfg["expert_selection_fn"] != "sigmoid" \
            or cfg["shared_expert_combination_strategy"] != "average" \
            or not cfg["use_gated_activation"] or cfg["hidden_act"] != "silu":
        raise ValueError("command_a_plus_engine builds sigmoid selection, "
                         "averaged shared experts and SwiGLU experts")
    share, n_shared = cfg["expert_share"], int(cfg["num_shared_experts"])
    experts = {"experts": int(share["router_experts"]),
               "held": (int(share["first"]), int(cfg["num_experts"])),
               "top_k": cfg["num_experts_per_tok"],
               "width": cfg["intermediate_size"], "activation": "silu",
               "route_from": "normed", "score": "sigmoid",
               "expert_bias": False,
               "norm_topk": bool(cfg["norm_topk_prob"]),
               # the mean of n SwiGLUs is one SwiGLU of n times the width
               # whose output is divided by n
               "shared_width": cfg["intermediate_size"] * n_shared,
               "shared_scale": 1.0 / n_shared}
    kinds = {"sliding_attention": True, "full_attention": False}
    return [{"window": cfg["sliding_window"] if kinds[kind] else None,
             "rope": kinds[kind], "rope_interleave": True, "ffn": experts,
             "attn_precision": cfg["as_run"]["attention_precision"]}
            for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def model_args(cfg: dict) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments.  There is
    no dense FFN (``first_k_dense_replace`` 0), so ``intermediate`` is
    never used."""
    require_program()
    if cfg["rms_norm_eps"] is not None or not cfg["use_parallel_block"]:
        raise ValueError("command_a_plus_engine builds the parallel block "
                         "under a LayerNorm (rms_norm_eps null)")
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], intermediate=0,
                rms_norm_eps=cfg["layer_norm_eps"],
                rope_base=float(cfg["rope_theta"]),
                tie_head=bool(cfg["tie_word_embeddings"]),
                norm="parallel", norm_kind="layer",
                logit_scale=float(cfg["logit_scale"]),
                layer_pattern=layer_pattern(cfg))


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
