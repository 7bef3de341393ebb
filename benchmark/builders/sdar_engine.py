"""Builder for ``sdar-30b-a3b-chat``: published keys -> the model arguments
of ``models/llama.py`` (``head_dim``, epsilon, RoPE base, QK-norm, every
layer's FFN the SwiGLU experts routed from the normed post-attention
stream, the prefill attention's precision as the configuration's
``as_run`` states it) with ``block_diffusion`` from the configuration's
``assumed.generation`` (the catalog gives neither block length nor
schedule), and a paged ``GenerationEngine`` sized by the mix's ``engine``
group.  The driver calls ``engine`` and knows nothing else of the
family."""
from __future__ import annotations


def model_args(cfg: dict, passes: int = None) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments.  There is
    no dense layer (``decoder_sparse_step`` 1, ``mlp_only_layers`` []), so
    ``intermediate_size`` is never used.  ``passes``: the mix's, else the
    configuration's."""
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("sdar_engine builds every layer with experts")
    gen = cfg["assumed"]["generation"]
    experts = {"experts": cfg["num_experts"],
               "top_k": cfg["num_experts_per_tok"],
               "width": cfg["moe_intermediate_size"],
               "activation": cfg["hidden_act"], "route_from": "normed"}
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], intermediate=0,
                rms_norm_eps=cfg["rms_norm_eps"],
                rope_base=float(cfg["rope_theta"]),
                qk_norm=bool(gen["qk_norm"]),
                layer_pattern=[{
                    "window": None, "rope": True, "ffn": experts,
                    "attn_precision": cfg["as_run"]["attention_precision"]}],
                block_diffusion={"block": int(gen["block_length"]),
                                 "passes": int(passes or gen["passes"]),
                                 "mask_id": int(gen["mask_token_id"])})


def engine(cfg, mix, *, scope=None, num_slots=None, keep_logits=False,
           buckets=None):
    from paddle_tpu.serving import GenerationEngine

    e = mix["engine"]
    return GenerationEngine(
        model_args(cfg, mix.get("passes")), scope=scope,
        num_slots=num_slots or e["num_slots"],
        max_seq_len=e["max_seq_len"],
        prefill_buckets=buckets or e["prefill_buckets"],
        max_new_tokens=int(mix["output_len"]["max"]),
        queue_cap=4096, deadline_ms=float(mix["deadline_ms"]),
        paged=True, page_tokens=e["page_tokens"],
        prefill_chunk=e["prefill_chunk"], prefix_reuse=e["prefix_reuse"],
        speculate=e["speculate"], attn_impl="auto",
        keep_logits=keep_logits, seed=0, eos_id=-1)
