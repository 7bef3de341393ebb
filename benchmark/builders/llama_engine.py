"""Builder for decoder configurations served through ``models/llama.py``
by a paged ``GenerationEngine``: published keys -> the engine's model
arguments, and the engine sized by the mix's ``engine`` group.  A
configuration names it under ``"builder"``; ``serve.py`` calls ``engine``
and knows nothing else of the family."""
from __future__ import annotations


def model_args(cfg: dict) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments."""
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                intermediate=cfg["intermediate_size"])


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
