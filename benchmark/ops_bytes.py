"""Operations and bytes an algorithm needs, from shapes alone.

These are the numerators of every MFU and roofline share the benchmark
reports.  They count what the mathematics requires (recomputation, padding
to a bucket and dead slots do not count), from the configuration's
published keys, so no PR that changes the program can move them.
"""
from __future__ import annotations

from typing import NamedTuple


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


# -- item sizes, by the class of array ---------------------------------------

class ItemSizes(NamedTuple):
    """Bytes an item of the arrays a serving program holds, by class, as
    the harness observed them on the engine that ran
    (``harness.observe_engine``): ``weights`` (the matrices), ``pages``
    (K/V, window and latent pools), ``state`` (slot state: convolution
    rows, delta matrices, state-space state); ``kept`` is what a
    configuration's ``as_run.bfloat16.keeps_float32`` keeps in float32
    whatever the weights are in (norm weights, the router and its bias,
    the convolution's taps, the decay's constants and projections, and
    what a recurrence's kernel reads a token): 4."""
    weights: int
    pages: int
    state: int
    kept: int = 4


def sizes_of(itemsize) -> ItemSizes:
    """``itemsize`` as ``ItemSizes``; a plain number is every class alike
    (4: a float32 program)."""
    if isinstance(itemsize, ItemSizes):
        return itemsize
    return ItemSizes(itemsize, itemsize, itemsize, itemsize)


# -- Mistral (grouped-query decoder) serving --------------------------------

def mistral_layer_params(cfg: dict) -> int:
    """Matmul parameters of one decoder layer: fused QKV, attention
    output, gate and up, down."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    d = h // cfg["num_attention_heads"]
    qkv = h * (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) * d
    return qkv + h * h + 2 * h * i + i * h


def mistral_weight_bytes(cfg: dict, itemsize) -> int:
    """Bytes of every weight a decode step reads: all layers' matrices and
    norms (kept float32), the final norm and the untied head.  The
    embedding table is read one row per token and is left out."""
    sz = sizes_of(itemsize)
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    return sz.weights * (layers * mistral_layer_params(cfg)
                         + h * cfg["vocab_size"]) \
        + sz.kept * (layers * 2 * h + h)


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
                              itemsize) -> float:
    """Bytes one decode step must read: the weights once, and K and V of
    every live position.  Bytes-bound: a decode step does about two FLOPs
    per weight byte per sequence.  ``itemsize``: ``ItemSizes`` (or a
    number for every class alike)."""
    return mistral_weight_bytes(cfg, itemsize) + live_kv_tokens \
        * mistral_kv_bytes_per_token(cfg, sizes_of(itemsize).pages)


def mistral_decode_step_flops(cfg: dict, n_seqs: int,
                              live_kv_tokens: float) -> float:
    """FLOPs of one decode step for ``n_seqs`` sequences holding
    ``live_kv_tokens`` cached positions in all."""
    h = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    matmul = 2.0 * (layers * mistral_layer_params(cfg)
                    + h * cfg["vocab_size"]) * n_seqs
    return matmul + layers * 4.0 * h * live_kv_tokens
