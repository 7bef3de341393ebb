"""Operations and bytes the ``command-a-plus-05-2026`` configuration needs,
from shapes alone: the numerators of ``decode_step_roofline.pool``,
``prefill_chunk_roofline.pool``, ``chunk_attention_roofline.pool`` and
``paged_kernel_roofline.pool``.  They count the least the mathematics
requires whatever implements it, for THIS chip's share (the held experts
that got a row, never the absent ones; the four shared experts; the
router over all its experts; the keys a row admits, a window's worth in
the sliding layers; the tied head over the vocabulary slice on one row),
from the configuration's keys, so no PR that changes the program can move
them.  A count never exceeds what the program does: a share over 100% is
a fault of the count.
"""
from __future__ import annotations

from ops_bytes import sizes_of


def window_layer_count(cfg: dict) -> int:
    return sum(kind == "sliding_attention"
               for kind in cfg["layer_types"][:cfg["num_hidden_layers"]])


def attention_params(cfg: dict) -> int:
    """Fused QKV and the output projection: no bias, no QK-norm."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * (q + 2 * kv) + q * h


def expert_params(cfg: dict) -> int:
    """One expert (routed or shared): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["expert_share"]["router_experts"]


def held_pairs_per_token(cfg: dict) -> float:
    """Token-expert pairs a token gives this chip, in expectation: its
    ``num_experts_per_tok`` over the router's experts, times those held."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["expert_share"]["router_experts"]


def kv_bytes_per_position(cfg: dict, itemsize: int) -> int:
    """K and V of one position in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def pair_flops(cfg: dict) -> int:
    """Scores and PV of one (row, column) pair over every query head: 4 x
    head_dim a head."""
    return 4 * cfg["head_dim"] * cfg["num_attention_heads"]


def paged_kernel_bytes(cfg: dict, live_positions: float,
                       live_positions_window: float,
                       itemsize) -> float:
    """Bytes the paged decode kernels of one step must read: K and V of
    the positions the live slots attend, whole contexts in the full layer
    and what lies inside the window in the sliding layers."""
    n_window = window_layer_count(cfg)
    return kv_bytes_per_position(cfg, sizes_of(itemsize).pages) * (
        (cfg["num_hidden_layers"] - n_window) * live_positions
        + n_window * live_positions_window)


def decode_step_bytes(cfg: dict, experts_held_touched: float,
                      live_positions: float, live_positions_window: float,
                      itemsize) -> float:
    """Bytes one decode step over the grid must move: every layer's
    attention matrices and its one norm; the router over all its experts,
    the held experts that got a row (``experts_held_touched``, the mean
    over the layers) and the four shared experts; the final norm and the
    tied matrix over the vocabulary slice, once (as the head; the rows
    the embedding reads of it are left out); K and V of the positions the
    live slots attend."""
    h = cfg["hidden_size"]
    weights = h + h * cfg["vocab_size"] + cfg["num_hidden_layers"] * (
        h + attention_params(cfg) + router_params(cfg)
        + expert_params(cfg) * (experts_held_touched
                                + cfg["num_shared_experts"]))
    sz = sizes_of(itemsize)
    # kept float32: the norms and the router
    kept = h + cfg["num_hidden_layers"] * (h + router_params(cfg))
    return sz.weights * (weights - kept) + sz.kept * kept \
        + paged_kernel_bytes(cfg, live_positions, live_positions_window,
                             sz)


def chunk_pairs(cfg: dict, n_tokens: int, base: int):
    """``(full, window)``: the (row, column) pairs the ``n_tokens`` rows
    of a chunk at ``base`` admit in a full layer (``j <= base + t``) and in
    a sliding one (the last ``sliding_window`` of them)."""
    n, b, w = int(n_tokens), int(base), int(cfg["sliding_window"])
    full = n * b + n * (n + 1) // 2
    # rows whose b + t + 1 columns all lie inside the window
    inside = min(max(w - b, 0), n)
    window = inside * b + inside * (inside + 1) // 2 + (n - inside) * w
    return full, window


def chunk_flops(cfg: dict, n_tokens: int, base: int) -> float:
    """FLOPs of one prefill chunk's ``n_tokens`` real rows at ``base``:
    every layer's projections, the router over all its experts, the
    expected held pairs' experts and the four shared experts at 2 per
    parameter and row; attention over the admitted columns.  The head
    runs on one row of a prompt's last chunk and is left out."""
    n_window = window_layer_count(cfg)
    layers = cfg["num_hidden_layers"]
    full, window = chunk_pairs(cfg, n_tokens, base)
    matmul = 2.0 * n_tokens * (
        attention_params(cfg) + router_params(cfg)
        + (held_pairs_per_token(cfg) + cfg["num_shared_experts"])
        * expert_params(cfg))
    return layers * matmul + pair_flops(cfg) * float(
        (layers - n_window) * full + n_window * window)


def chunk_attention_flops(cfg: dict, attended_pairs: float,
                          itemsize: int) -> float:
    """FLOPs the chunk attention kernels of one chunk must do:
    ``attended_pairs`` is summed over the layers (the engine's span says
    what the chunk's real rows admit), each pair scores and PV over 128
    query heads of 128.  ``itemsize`` is the reader's and is not read."""
    return float(pair_flops(cfg)) * attended_pairs
