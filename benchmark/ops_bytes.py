"""Operations and bytes an algorithm needs, from shapes alone.

These are the numerators of every MFU and roofline share the benchmark
reports.  They count what the mathematics requires (recomputation, padding
to a bucket and dead slots do not count), from the configuration's
published keys, so no PR that changes the program can move them.
"""
from __future__ import annotations


# -- BERT MLM training -------------------------------------------------------

def bert_train_flops_per_sequence(cfg: dict, seq: int, n_pred: int) -> float:
    """Matmul FLOPs of one training sequence (a copy of
    ``bench.bert_train_flops_per_sample``).  Per token and layer: QKV
    projection 6H^2, scores and PV 4HS, output projection 2H^2, FFN 4HI
    (a matmul is 2mk per output element).  The MLM head runs on the
    ``n_pred`` gathered positions: transform 2H^2 plus vocabulary
    projection 2HV each.  Training is three times the forward pass
    (backward is twice the forward's matmuls)."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 6 * h * h + 2 * h * h + 4 * h * seq + 4 * h * i
    head = 2 * h * h + 2 * h * cfg["vocab_size"]
    fwd = cfg["num_hidden_layers"] * per_layer * seq + head * n_pred
    return 3.0 * fwd


def bert_train_flops_per_token(cfg: dict, seq: int, n_pred: int) -> float:
    return bert_train_flops_per_sequence(cfg, seq, n_pred) / seq


# -- Mistral (grouped-query decoder) serving --------------------------------

def mistral_layer_params(cfg: dict) -> int:
    """Matmul parameters of one decoder layer: fused QKV, attention
    output, gate and up, down."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    d = h // cfg["num_attention_heads"]
    qkv = h * (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) * d
    return qkv + h * h + 2 * h * i + i * h


def mistral_weight_bytes(cfg: dict, itemsize: int) -> int:
    """Bytes of every weight a decode step reads: all layers' matrices and
    norms, the final norm and the untied head.  The embedding table is
    read one row per token and is left out."""
    h = cfg["hidden_size"]
    per_layer = mistral_layer_params(cfg) + 2 * h
    return itemsize * (cfg["num_hidden_layers"] * per_layer + h
                       + h * cfg["vocab_size"])


def mistral_kv_bytes_per_token(cfg: dict, itemsize: int) -> int:
    """K and V of one position over all layers."""
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * d * itemsize \
        * cfg["num_hidden_layers"]


def mistral_prefill_flops(cfg: dict, n_tokens: int) -> float:
    """FLOPs of prefilling ``n_tokens`` real prompt tokens: every layer's
    matmuls (2 per parameter and token), causal attention (scores and PV
    over the lower triangle: 2 * n^2 * hidden per layer, half of the full
    4 * n^2 * hidden) and the head on the last position only."""
    h = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    matmul = 2.0 * mistral_layer_params(cfg) * n_tokens
    attn = 2.0 * n_tokens * n_tokens * h
    return layers * (matmul + attn) + 2.0 * h * cfg["vocab_size"]


def mistral_decode_step_bytes(cfg: dict, live_kv_tokens: float,
                              itemsize: int) -> float:
    """Bytes one decode step must read: the weights once, and K and V of
    every live position.  Bytes-bound: a decode step does about two FLOPs
    per weight byte per sequence."""
    return mistral_weight_bytes(cfg, itemsize) \
        + live_kv_tokens * mistral_kv_bytes_per_token(cfg, itemsize)


def mistral_decode_step_flops(cfg: dict, n_seqs: int,
                              live_kv_tokens: float) -> float:
    """FLOPs of one decode step for ``n_seqs`` sequences holding
    ``live_kv_tokens`` cached positions in all."""
    h = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    matmul = 2.0 * (layers * mistral_layer_params(cfg)
                    + h * cfg["vocab_size"]) * n_seqs
    return matmul + layers * 4.0 * h * live_kv_tokens
