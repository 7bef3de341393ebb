"""Operations and bytes the ``sdar-30b-a3b-chat`` configuration needs, from
shapes alone: the numerators of ``block_step_roofline.blk`` and
``prefill_roofline.pool``.  They count the least the mathematics requires
(the experts a row was routed to, the keys a block-causal row attends, no
head in a prefill that yields no row), from the configuration's published
keys, so no PR that changes the program can move them.  A count never
exceeds what the program does: a share over 100% is a fault of the count.
"""
from __future__ import annotations

from ops_bytes import sizes_of


def attention_params(cfg: dict) -> int:
    """One layer's attention matrices (fused QKV, output) and its router:
    read whole by every pass."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * (q + 2 * kv) + q * h + h * cfg["num_experts"]


def norm_params(cfg: dict) -> int:
    """One layer's norm weights: two over the stream, two over a head."""
    return 2 * cfg["hidden_size"] + 2 * cfg["head_dim"]


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def kv_bytes_per_position(cfg: dict, itemsize: int) -> int:
    """K and V of one position in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def pass_bytes(cfg: dict, experts_touched: float, live_positions: float,
               rows: float, itemsize) -> float:
    """Bytes one pass over the grid must read: every layer's attention
    matrices, router and norms; the experts that got a row
    (``experts_touched``, the mean over the layers); the final norm and
    the untied head, once whatever the rows; K and V of the positions the
    live slots attend (``live_positions``: each slot's committed
    positions and its block, summed over the slots), in every layer; and
    one embedding row a row.  A denoising and a commit pass read the
    same."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    weights = layers * (attention_params(cfg) + norm_params(cfg)
                        + experts_touched * expert_params(cfg)) \
        + h + h * cfg["vocab_size"] + rows * h
    sz = sizes_of(itemsize)
    # kept float32: the norms and the router
    kept = layers * (norm_params(cfg) + h * cfg["num_experts"]) + h
    kv = kv_bytes_per_position(cfg, sz.pages) * layers * live_positions
    return sz.weights * (weights - kept) + sz.kept * kept + kv


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """FLOPs of prefilling ``n_tokens`` prompt tokens (whole blocks).  The
    prefill yields K/V alone, so what it needs is every layer's QKV
    projection and, for all but the last layer (whose output nobody
    reads: there is no head, and the compiler drops it), block-causal
    attention (scores and PV: 4 x head_dim per query head and attended
    key; a row of block b attends the (b + 1) B keys up to its block's
    end, B^2 m (m + 1) / 2 pairs over m blocks), the output projection,
    the router and the ``num_experts_per_tok`` experts a token is routed
    to; 2 per parameter and token."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    block = cfg["assumed"]["generation"]["block_length"]
    layers = cfg["num_hidden_layers"]
    n, m = float(n_tokens), float(n_tokens) / block
    qkv = 2.0 * n * h * (q + 2 * kv)
    rest = 2.0 * n * (q * h + h * cfg["num_experts"]
                      + cfg["num_experts_per_tok"] * expert_params(cfg))
    attn = 4.0 * d * cfg["num_attention_heads"] \
        * block * block * m * (m + 1) / 2
    return layers * qkv + (layers - 1) * (rest + attn)
