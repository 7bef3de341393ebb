"""Operations and bytes the ``smallthinker-21b-a3b`` configuration needs,
from shapes alone: the numerators of ``decode_step_roofline.mix`` and
``prefill_roofline.pool``.  They count the least the mathematics requires
(the experts a token was routed to, the keys inside a window, the head on
one row), from the configuration's published keys, so no PR that changes
the program can move them.  A count never exceeds what the program does: a
share over 100% is a fault of the count.
"""
from __future__ import annotations

from ops_bytes import sizes_of


def _dims(cfg: dict):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (h, d, cfg["num_attention_heads"] * d,
            cfg["num_key_value_heads"] * d)


def attention_params(cfg: dict) -> int:
    """One layer's attention matrices (fused QKV, output) and its router:
    read whole by every step."""
    h, _, q, kv = _dims(cfg)
    return h * (q + 2 * kv) + q * h + h * cfg["moe_num_primary_experts"]


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def window_layer_count(cfg: dict) -> int:
    return sum(cfg["sliding_window_layout"][:cfg["num_hidden_layers"]])


def kv_bytes_per_position(cfg: dict, itemsize: int) -> int:
    """K and V of one position in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def decode_step_bytes(cfg: dict, experts_touched: float,
                      positions_full: float, positions_window: float,
                      itemsize) -> float:
    """Bytes one decode step must read: every layer's attention matrices,
    router and norms (the router and the norms kept float32); the experts that got a token (``experts_touched``,
    the mean over the layers); the final norm and the untied head; K and
    V of the live positions, whole contexts in the full layers
    (``positions_full``) and what lies inside the window in the window
    layers (``positions_window``), both summed over the live slots.  The
    embedding is read one row a token and is left out."""
    h = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    n_window = window_layer_count(cfg)
    weights = layers * (attention_params(cfg) + 2 * h
                        + experts_touched * expert_params(cfg)) \
        + h + h * cfg["vocab_size"]
    sz = sizes_of(itemsize)
    kept = layers * (2 * h + h * cfg["moe_num_primary_experts"]) + h
    kv = kv_bytes_per_position(cfg, sz.pages) * (
        (layers - n_window) * positions_full + n_window * positions_window)
    return sz.weights * (weights - kept) + sz.kept * kept + kv


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """FLOPs of prefilling ``n_tokens`` real prompt tokens: per layer the
    attention matrices and the router (2 per parameter and token), the
    experts a token is routed to (``moe_num_active_primary_experts`` of
    them), causal attention inside the band (scores and PV: 4 x head_dim
    per query head and attended key; a full layer attends n (n + 1) / 2
    pairs, a window layer W (W + 1) / 2 + (n - W) W of them past the
    window), and the head on the last position only."""
    _, d, _, _ = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    n_window = window_layer_count(cfg)
    n, w = float(n_tokens), float(cfg["sliding_window_size"])
    matmul = 2.0 * n * (attention_params(cfg)
                        + cfg["moe_num_active_primary_experts"]
                        * expert_params(cfg))
    pairs_full = n * (n + 1) / 2
    pairs_window = pairs_full if n <= w \
        else w * (w + 1) / 2 + (n - w) * w
    per_pair = 4.0 * d * cfg["num_attention_heads"]
    attn = per_pair * ((layers - n_window) * pairs_full
                       + n_window * pairs_window)
    return layers * matmul + attn \
        + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
