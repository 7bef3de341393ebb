"""Operations and bytes the ``granite-4.0-h-micro`` configuration needs,
from shapes alone: the numerators of ``decode_step_roofline.pool``,
``prefill_roofline.pool``, ``paged_kernel_roofline.pool``,
``ssm_step_roofline.pool`` and ``ssm_chunk_roofline.pool``.  They count
the least the mathematics requires whatever implements it (the
recurrence's 6 x 64 x 128 operations a head a token, a slot's state read
once and written once a step, the keys a causal row attends, the head on
one row), from the configuration's published keys, so no PR that changes
the program can move them.  A count never exceeds what the program does: a
share over 100% is a fault of the count.
"""
from __future__ import annotations

from ops_bytes import sizes_of


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def n_mamba(cfg: dict) -> int:
    return sum(kind == "mamba" for kind in layer_kinds(cfg))


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def ssm_dims(cfg: dict):
    """``(heads, head_dim, state rows, channels of the convolution: x | B
    | C)``."""
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"]
    return heads, p, n, heads * p + 2 * cfg["mamba_n_groups"] * n


def mamba_mixer_params(cfg: dict) -> int:
    """z | xBC | dt, the output projection, the taps and their bias, the
    three constants a head and the gated norm's weight."""
    h = cfg["hidden_size"]
    heads, p, _, channels = ssm_dims(cfg)
    inner = heads * p
    return h * (inner + channels + heads) + inner * h \
        + channels * (cfg["mamba_d_conv"] + 1) + 3 * heads + inner


def attention_mixer_params(cfg: dict) -> int:
    """Fused QKV over 32 query and 8 KV heads, and the output projection
    (no bias)."""
    d = head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    return cfg["hidden_size"] * (2 * q + 2 * cfg["num_key_value_heads"] * d)


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def kv_bytes_per_position(cfg: dict, itemsize: int) -> int:
    """K and V of one position in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def ssm_state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    """One state-space layer's matrix state of one slot."""
    heads, p, n, _ = ssm_dims(cfg)
    return heads * p * n * itemsize


def conv_state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    """One state-space layer's convolution rows of one slot."""
    return (cfg["mamba_d_conv"] - 1) * ssm_dims(cfg)[3] * itemsize


def paged_kernel_bytes(cfg: dict, live_positions: float,
                       itemsize) -> float:
    """Bytes the paged decode kernels of one step must read: K and V of
    the positions the live slots attend, in every attention layer."""
    return kv_bytes_per_position(cfg, sizes_of(itemsize).pages) \
        * (len(layer_kinds(cfg)) - n_mamba(cfg)) * live_positions


def ssm_step_bytes(cfg: dict, state_slots: float, itemsize) -> float:
    """Bytes the state steps of one decode step must move: the matrix
    state of every state-space layer, read once and written once, for the
    ``state_slots`` slots the step advanced."""
    return 2 * ssm_state_bytes_per_slot(cfg, sizes_of(itemsize).state) \
        * n_mamba(cfg) * state_slots


def ssm_chunk_bytes(cfg: dict, scan_tokens: float, itemsize) -> float:
    """Bytes the recurrence of one prefill must move in every state-space
    layer: x, B, C and dt of every real token read, its output written,
    and the state it leaves written once (it starts from none).  The same
    tokens' operations (``ssm_chunk_flops``) take less of the chip than
    these bytes do, so the bytes are the scan kernel's floor.  What the
    recurrence reads and writes a token is float32 whatever the weights
    are in (kept: the convolution's result, dt); the state is the
    state's."""
    sz = sizes_of(itemsize)
    heads, p, n, _ = ssm_dims(cfg)
    per_token = 2 * heads * p + 2 * n + heads
    return n_mamba(cfg) * (sz.kept * per_token * scan_tokens
                           + sz.state * heads * p * n)


def ssm_chunk_flops(cfg: dict, scan_tokens: float) -> float:
    """Operations of the same: a head a token decays its state, adds an
    outer product and reads it along C, 6 x head_dim x state."""
    heads, p, n, _ = ssm_dims(cfg)
    return 6.0 * heads * p * n * n_mamba(cfg) * scan_tokens


def decode_step_bytes(cfg: dict, live_positions: float, state_slots: float,
                      itemsize) -> float:
    """Bytes one decode step over the grid must move: every layer's mixer,
    its SwiGLU and its two norms; the final norm and the tied table, once
    (the head reads it whole; the slots' embedding rows are among its
    rows); K and V of the positions the live slots attend
    (``live_positions``, summed over the slots) in the attention layers;
    and both states of every state-space layer, read and written, for the
    ``state_slots`` slots the step advanced."""
    h = cfg["hidden_size"]
    sz = sizes_of(itemsize)
    heads, p, _, channels = ssm_dims(cfg)
    weights = h + h * cfg["vocab_size"]
    # kept float32: norms, the taps and their bias, the three constants a
    # head, the gated norm's weight
    kept = h
    for kind in layer_kinds(cfg):
        weights += 2 * h + dense_params(cfg)
        weights += mamba_mixer_params(cfg) if kind == "mamba" \
            else attention_mixer_params(cfg)
        kept += 2 * h + (channels * (cfg["mamba_d_conv"] + 1) + 3 * heads
                         + heads * p if kind == "mamba" else 0)
    state = 2 * conv_state_bytes_per_slot(cfg, sz.state) * n_mamba(cfg) \
        * state_slots + ssm_step_bytes(cfg, state_slots, sz)
    return sz.weights * (weights - kept) + sz.kept * kept + state \
        + paged_kernel_bytes(cfg, live_positions, sz)


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """FLOPs of prefilling ``n_tokens`` prompt tokens: every layer's
    projections and SwiGLU at 2 per parameter and token; a state-space
    layer's convolution (2 x taps a channel) and recurrence (6 x head_dim x
    state a head a token); an attention layer's causal attention (scores
    and PV: 4 x head_dim per query head and attended key, n (n + 1) / 2
    pairs); the head on one row."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    heads, p, _, channels = ssm_dims(cfg)
    taps = cfg["mamba_d_conv"]
    n = float(n_tokens)
    matrices = h * (heads * p + channels + heads) + heads * p * h
    flops = 2.0 * h * cfg["vocab_size"]
    for kind in layer_kinds(cfg):
        flops += 2.0 * n * dense_params(cfg)
        if kind == "mamba":
            flops += 2.0 * n * matrices + 2.0 * n * taps * channels \
                + ssm_chunk_flops(cfg, n) / n_mamba(cfg)
        else:
            flops += 2.0 * n * attention_mixer_params(cfg) \
                + 4.0 * d * cfg["num_attention_heads"] * n * (n + 1) / 2
    return flops
