"""Operations and bytes the ``solar-open2-250b`` configuration needs, from
shapes alone: the numerators of ``decode_step_roofline.pool``,
``prefill_roofline.pool``, ``paged_kernel_roofline.pool``,
``delta_step_roofline.pool`` and ``delta_chunk_roofline.pool``.  They count
the least the mathematics requires whatever implements it, for THIS
chip's share (the held experts that got a row, never the absent ones; the
shared expert; the router over all its experts; the recurrence's 6 x 128
x 128 operations a head a token and its decay at 128 values a head a
token; a slot's state read once and written once a step; the keys a
causal row attends; the head over the vocabulary slice on one row), from
the configuration's keys, so no PR that changes the program can move
them.  A count never exceeds what the program does: a share over 100% is
a fault of the count.
"""
from __future__ import annotations

from ops_bytes import sizes_of


def layer_kinds(cfg: dict) -> list:
    return ["attention" if i in cfg["gqa_layers"] else "kda"
            for i in range(cfg["num_hidden_layers"])]


def n_kda(cfg: dict) -> int:
    return sum(kind == "kda" for kind in layer_kinds(cfg))


def kda_dims(cfg: dict):
    """``(heads, head_dim, channels of the convolution: q | k | v)``."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], \
        3 * lin["num_heads"] * lin["head_dim"]


def kda_mixer_params(cfg: dict) -> int:
    """q | k | v, the output projection, the two low-rank pairs (decay,
    output gate), beta, the taps, ``A_log``, ``dt_bias`` and the head
    norm's weight."""
    h, low = cfg["hidden_size"], cfg["assumed"]["low_rank"]
    heads, d, channels = kda_dims(cfg)
    return h * channels + heads * d * h + 2 * (h * low + low * heads * d) \
        + h * heads \
        + channels * cfg["linear_attn_config"]["short_conv_kernel_size"] \
        + heads + heads * d + d


def attention_mixer_params(cfg: dict) -> int:
    """Fused QKV, the output gate and the output projection."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * (q + 2 * kv) + q * h + (h * q if cfg["use_gqa_gate"] else 0)


def expert_params(cfg: dict) -> int:
    """One expert (routed or shared): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix over all its experts, and the selection bias."""
    return (cfg["hidden_size"] + 1) * cfg["expert_share"]["router_experts"]


def held_pairs_per_token(cfg: dict) -> float:
    """Token-expert pairs a token gives this chip, in expectation: its
    ``num_experts_per_tok`` over the router's experts, times those held."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["expert_share"]["router_experts"]


def kv_bytes_per_position(cfg: dict, itemsize: int) -> int:
    """K and V of one position in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def kda_state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    """One KDA layer's matrix state of one slot."""
    heads, d, _ = kda_dims(cfg)
    return heads * d * d * itemsize


def conv_state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    """One KDA layer's convolution rows of one slot."""
    return (cfg["linear_attn_config"]["short_conv_kernel_size"] - 1) \
        * kda_dims(cfg)[2] * itemsize


def paged_kernel_bytes(cfg: dict, live_positions: float,
                       itemsize) -> float:
    """Bytes the paged decode kernels of one step must read: K and V of
    the positions the live slots attend, in every attention layer."""
    return kv_bytes_per_position(cfg, sizes_of(itemsize).pages) \
        * (len(layer_kinds(cfg)) - n_kda(cfg)) * live_positions


def kda_step_bytes(cfg: dict, state_slots: float, itemsize) -> float:
    """Bytes the delta-state steps of one decode step must move: the
    state of every KDA layer, read once and written once, for the
    ``state_slots`` slots the step advanced."""
    return 2 * kda_state_bytes_per_slot(cfg, sizes_of(itemsize).state) \
        * n_kda(cfg) * state_slots


def kda_chunk_bytes(cfg: dict, scan_tokens: float, itemsize) -> float:
    """Bytes the delta rule of one prefill must move in every KDA layer:
    q, k, v and the log decay (a value a key channel) of every real token
    and its beta read, its output written, and the state it leaves
    written once (it starts from none).  What the rule reads and writes
    a token is float32 whatever the weights are in (kept); the state is
    the state's."""
    sz = sizes_of(itemsize)
    heads, d, _ = kda_dims(cfg)
    per_token = heads * (5 * d + 1)
    return n_kda(cfg) * (sz.kept * per_token * scan_tokens
                         + sz.state * heads * d * d)


def decode_step_bytes(cfg: dict, experts_held_touched: float,
                      live_positions: float, state_slots: float,
                      itemsize) -> float:
    """Bytes one decode step over the grid must move: every layer's mixer
    and its two norms; the router over all its experts with its bias, the
    held experts that got a row (``experts_held_touched``, the mean over
    the layers) and the shared expert; the final norm and the untied
    head over the vocabulary slice, once; a row of the embedding a slot;
    K and V of the positions the live slots attend (``live_positions``,
    summed over the slots) in the attention layers; and both states of
    every KDA layer, read and written, for the ``state_slots`` slots the
    step advanced."""
    h = cfg["hidden_size"]
    sz = sizes_of(itemsize)
    low = cfg["assumed"]["low_rank"]
    heads, d, channels = kda_dims(cfg)
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"]
    weights = h + h * cfg["vocab_size"] + state_slots * h
    # kept float32: norms, the router and its bias, the decay's low-rank
    # pair, beta, the taps, A_log, dt_bias and the head norm's weight
    kept = h
    for kind in layer_kinds(cfg):
        weights += 2 * h + (kda_mixer_params(cfg) if kind == "kda"
                            else attention_mixer_params(cfg))
        weights += router_params(cfg) + expert_params(cfg) * (
            experts_held_touched + cfg["n_shared_experts"])
        kept += 2 * h + router_params(cfg) + (
            h * low + low * heads * d + h * heads + channels * taps
            + heads + heads * d + d if kind == "kda" else 0)
    state = 2 * conv_state_bytes_per_slot(cfg, sz.state) * n_kda(cfg) \
        * state_slots + kda_step_bytes(cfg, state_slots, sz)
    return sz.weights * (weights - kept) + sz.kept * kept + state \
        + paged_kernel_bytes(cfg, live_positions, sz)


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """FLOPs of prefilling ``n_tokens`` prompt tokens: every layer's
    projections at 2 per parameter and token; a KDA layer's convolution
    (2 x taps a channel) and recurrence (6 x 128 x 128 a head a token:
    the state read along k, corrected by an outer product and read along
    q; its row scaling 128 x 128 more); an attention layer's causal
    attention (scores and PV: 4 x head_dim per query head and attended
    key, n (n + 1) / 2 pairs); the router over all its experts, the
    expected held pairs' experts and the shared expert; the head on one
    row."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, dk, channels = kda_dims(cfg)
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"]
    n = float(n_tokens)
    flops = 2.0 * h * cfg["vocab_size"]
    for kind in layer_kinds(cfg):
        if kind == "kda":
            matrices = kda_mixer_params(cfg) - channels * taps \
                - heads - heads * dk - dk
            flops += 2.0 * n * matrices + 2.0 * n * taps * channels \
                + 7.0 * n * heads * dk * dk
        else:
            flops += 2.0 * n * attention_mixer_params(cfg) \
                + 4.0 * d * cfg["num_attention_heads"] * n * (n + 1) / 2
        flops += 2.0 * n * (
            h * cfg["expert_share"]["router_experts"]
            + (held_pairs_per_token(cfg) + cfg["n_shared_experts"])
            * expert_params(cfg))
    return flops
