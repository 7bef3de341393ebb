"""Builder for ``smallthinker-21b-a3b``: published keys -> the model
arguments of ``models/llama.py`` (``head_dim``, epsilon, RoPE base and the
per-layer pattern: window or full attention, RoPE or none, routed ReGLU
experts), and a paged ``GenerationEngine`` sized by the mix's ``engine``
group.  ``serve.py`` calls ``engine`` and knows nothing else of the
family.

The check engine (``keep_logits``) also keeps the program's router logits
of the rows it yields, and leaves them where the plain reference finds
them (``cfg["_program_router"]``): routing is discrete, and at a near tie
the reference takes the program's choice (the configuration's
``check_tolerance.why``)."""
from __future__ import annotations

import numpy as np


def layer_pattern(cfg: dict) -> list:
    """One entry per layer that is run, from the published layouts:
    ``sliding_window_layout`` 1 is window attention, ``rope_layout`` 1 is
    RoPE; every layer's FFN is the primary experts."""
    experts = {"experts": cfg["moe_num_primary_experts"],
               "top_k": cfg["moe_num_active_primary_experts"],
               "width": cfg["moe_ffn_hidden_size"], "activation": "relu"}
    n = cfg["num_hidden_layers"]
    return [{"window": cfg["sliding_window_size"] if w else None,
             "rope": bool(r), "ffn": experts}
            for w, r in zip(cfg["sliding_window_layout"][:n],
                            cfg["rope_layout"][:n])]


def model_args(cfg: dict) -> dict:
    """Published keys -> ``GenerationEngine`` model arguments.  There is
    no dense FFN anywhere, so ``intermediate`` is never used."""
    return dict(vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], intermediate=0,
                rms_norm_eps=cfg["rms_norm_eps"],
                rope_base=float(cfg["rope_theta"]),
                layer_pattern=layer_pattern(cfg))


def engine(cfg, mix, *, scope=None, num_slots=None, keep_logits=False,
           buckets=None):
    from paddle_tpu.serving import GenerationEngine

    class CheckEngine(GenerationEngine):
        """``generate`` also leaves the result's router logits where the
        plain reference finds them.  ``serve.reference_check`` hands the
        reference nothing of a result but ``cfg``, so that is the way."""

        def generate(self, prompt, max_new_tokens=None, timeout=None):
            res = super().generate(prompt, max_new_tokens, timeout)
            cfg["_program_router"] = {
                "ids": list(prompt) + list(res["tokens"]),
                "first_row": len(prompt) - 1,
                "logits": np.stack(res["router_logits"])}  # [T, L, E]
            return res

    e = mix["engine"]
    return (CheckEngine if keep_logits else GenerationEngine)(
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
