"""Operations and bytes the ``olmo-hybrid-7b`` configuration needs, from
shapes alone: the numerators of ``decode_step_roofline.pool``,
``prefill_roofline.pool``, ``paged_kernel_roofline.pool``,
``delta_step_roofline.pool`` and ``delta_chunk_roofline.pool``.  They count
the least the mathematics requires whatever implements it (the
recurrence's 6 x 96 x 192 operations a head a token, a slot's state read
once and written once a step, the keys a causal row attends, the head on
one row), from the configuration's published keys, so no PR that changes
the program can move them.  A count never exceeds what the program does: a
share over 100% is a fault of the count.
"""
from __future__ import annotations

from ops_bytes import sizes_of


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def n_linear(cfg: dict) -> int:
    return sum(kind == "linear_attention" for kind in layer_kinds(cfg))


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def delta_dims(cfg: dict):
    """``(heads, key_dim, value_dim, channels of the convolution)``."""
    heads = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return heads, dk, dv, heads * (2 * dk + dv)


def linear_mixer_params(cfg: dict) -> int:
    """q | k | v, the gate, the output projection, a | b and the taps."""
    h = cfg["hidden_size"]
    heads, _, dv, channels = delta_dims(cfg)
    return h * channels + 2 * h * heads * dv + h * 2 * heads \
        + channels * cfg["linear_conv_kernel_dim"]


def attention_mixer_params(cfg: dict) -> int:
    """Fused QKV and the output projection (no bias; the two QK-norm
    weights are counted with the norms)."""
    return 4 * cfg["hidden_size"] ** 2


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def kv_bytes_per_position(cfg: dict, itemsize: int) -> int:
    """K and V of one position in one full-attention layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def delta_state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    """One linear layer's matrix state of one slot."""
    heads, dk, dv, _ = delta_dims(cfg)
    return heads * dk * dv * itemsize


def conv_state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    """One linear layer's convolution rows of one slot."""
    return (cfg["linear_conv_kernel_dim"] - 1) * delta_dims(cfg)[3] \
        * itemsize


def paged_kernel_bytes(cfg: dict, live_positions: float,
                       itemsize) -> float:
    """Bytes the paged decode kernels of one step must read: K and V of
    the positions the live slots attend, in every full-attention layer."""
    return kv_bytes_per_position(cfg, sizes_of(itemsize).pages) \
        * (len(layer_kinds(cfg)) - n_linear(cfg)) * live_positions


def delta_step_bytes(cfg: dict, state_slots: float, itemsize) -> float:
    """Bytes the delta-state steps of one decode step must move: the
    state of every linear layer, read once and written once, for the
    ``state_slots`` slots the step advanced."""
    return 2 * delta_state_bytes_per_slot(cfg, sizes_of(itemsize).state) \
        * n_linear(cfg) * state_slots


def delta_chunk_bytes(cfg: dict, scan_tokens: float, itemsize) -> float:
    """Bytes the delta rule of one prefill must move in every linear
    layer: q, k, v, the log decay and beta of every real token read, its
    output written, and the state it leaves written once (it starts from
    none).  What the rule reads and writes a token is float32 whatever
    the weights are in (kept: the convolution's result, the L2 norms, the
    decay); the state is the state's."""
    sz = sizes_of(itemsize)
    heads, dk, dv, _ = delta_dims(cfg)
    per_token = heads * (2 * dk + 2 * dv + 2)
    return n_linear(cfg) * (sz.kept * per_token * scan_tokens
                            + sz.state * heads * dk * dv)


def decode_step_bytes(cfg: dict, live_positions: float, state_slots: float,
                      itemsize) -> float:
    """Bytes one decode step over the grid must move: every layer's mixer,
    its SwiGLU and its norms (a full layer's two QK-norm weights, a linear
    layer's decay constants and output-norm weight among them); the final
    norm and the untied head, once; a row of the embedding a slot; K and V
    of the positions the live slots attend (``live_positions``, summed
    over the slots) in the full-attention layers; and both states of
    every linear layer, read and written, for the ``state_slots`` slots
    the step advanced."""
    h = cfg["hidden_size"]
    heads, _, dv, _ = delta_dims(cfg)
    sz = sizes_of(itemsize)
    channels = delta_dims(cfg)[3]
    weights = h + h * cfg["vocab_size"] + state_slots * h
    # kept float32: norms, a | b, the taps, the decay's constants
    kept = h
    for kind in layer_kinds(cfg):
        weights += 2 * h + dense_params(cfg)
        weights += linear_mixer_params(cfg) + 2 * heads + dv \
            if kind == "linear_attention" \
            else attention_mixer_params(cfg) + 2 * h
        kept += 2 * h + (h * 2 * heads
                         + channels * cfg["linear_conv_kernel_dim"]
                         + 2 * heads + dv if kind == "linear_attention"
                         else 2 * h)
    state = 2 * conv_state_bytes_per_slot(cfg, sz.state) * n_linear(cfg) \
        * state_slots + delta_step_bytes(cfg, state_slots, sz)
    return sz.weights * (weights - kept) + sz.kept * kept + state \
        + paged_kernel_bytes(cfg, live_positions, sz)


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """FLOPs of prefilling ``n_tokens`` prompt tokens: every layer's
    projections and SwiGLU at 2 per parameter and token; a linear layer's
    convolution (2 x taps a channel) and recurrence (6 x key_dim x
    value_dim a head a token: the state read along k, corrected by an
    outer product and read along q); a full layer's causal attention
    (scores and PV: 4 x head_dim per query head and attended key,
    n (n + 1) / 2 pairs); the head on one row."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    heads, dk, dv, channels = delta_dims(cfg)
    taps = cfg["linear_conv_kernel_dim"]
    n = float(n_tokens)
    flops = 2.0 * h * cfg["vocab_size"]
    for kind in layer_kinds(cfg):
        flops += 2.0 * n * dense_params(cfg)
        if kind == "linear_attention":
            flops += 2.0 * n * (linear_mixer_params(cfg) - channels * taps) \
                + 2.0 * n * taps * channels + 6.0 * n * heads * dk * dv
        else:
            flops += 2.0 * n * attention_mixer_params(cfg) \
                + 4.0 * d * cfg["num_attention_heads"] * n * (n + 1) / 2
    return flops
