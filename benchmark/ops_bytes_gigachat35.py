"""Operations and bytes the ``gigachat35-432b-a28b`` configuration needs,
from shapes alone: the numerators of ``decode_step_roofline.pool``,
``prefill_roofline.pool``, ``mla_decode_bytes_roofline.pool``,
``mla_decode_flops_roofline.pool``, ``mla_prefill_roofline.pool``,
``delta_step_roofline.pool`` and ``delta_chunk_roofline.pool``.  They count the
least the mathematics requires whatever implements it, for THIS chip's
share (the held experts that got a row, never the absent ones; the shared
expert; the router over all its experts; the head over the vocabulary
slice on one row; a latent row a cached position, at the pool's row size
as run, read once; the recurrence's 6 x 128 x 128 operations a VALUE head
a token; a slot's state read once and written once a step), from the
configuration's keys, so no PR that changes the program can move them.  A
count never exceeds what the program does: a share over 100% is a fault
of the count.
"""
from __future__ import annotations

from ops_bytes import sizes_of


def layer_kinds(cfg: dict) -> list:
    return ["mla" if i in cfg["full_attention_layers"] else "delta"
            for i in range(cfg["num_hidden_layers"])]


def n_delta(cfg: dict) -> int:
    return sum(kind == "delta" for kind in layer_kinds(cfg))


def n_mla(cfg: dict) -> int:
    return len(layer_kinds(cfg)) - n_delta(cfg)


def delta_dims(cfg: dict):
    """``(value heads, key_dim, value_dim, channels of the convolution: q
    | k of the key heads, v of the value heads)``."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return hv, dk, dv, hk * 2 * dk + hv * dv


def delta_mixer_params(cfg: dict) -> int:
    """q | k | v, the gate z, the output projection, a | b, the taps, the
    decay constants and the head norm's weight."""
    h = cfg["hidden_size"]
    heads, _, dv, channels = delta_dims(cfg)
    return h * channels + 2 * h * heads * dv + h * 2 * heads \
        + channels * cfg["linear_conv_kernel_dim"] + 2 * heads + dv


def mla_mixer_params(cfg: dict) -> int:
    """The two low-rank pairs with their norms, the output gate and the
    output projection."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return h * rq + rq + rq * heads * (dn + dr) + h * (c + dr) + c \
        + c * heads * (dn + dv) + heads * dv * h \
        + (h * heads * dv if cfg["gated_attention"] else 0)


def mixer_params(cfg: dict, kind: str) -> int:
    return mla_mixer_params(cfg) if kind == "mla" \
        else delta_mixer_params(cfg)


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One expert (routed or shared): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix over all its experts, and the selection bias."""
    return (cfg["hidden_size"] + 1) * cfg["expert_share"]["router_experts"]


def held_pairs_per_token(cfg: dict) -> float:
    """Token-expert pairs a token gives this chip, in expectation."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["expert_share"]["router_experts"]


def latent_row_bytes(cfg: dict, itemsize: int) -> int:
    """One cached position of one latent layer, as the pool keeps it:
    ``as_run.latent_row.lanes`` numbers (``[c_kv | k_r]`` in whole lane
    tiles)."""
    return int(cfg["as_run"]["latent_row"]["lanes"]) * itemsize


def delta_state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    heads, dk, dv, _ = delta_dims(cfg)
    return heads * dk * dv * itemsize


def conv_state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    return (cfg["linear_conv_kernel_dim"] - 1) * delta_dims(cfg)[3] \
        * itemsize


def mla_decode_bytes(cfg: dict, latent_positions: float,
                     itemsize) -> float:
    """Bytes the latent decode kernels of one step must read: one row a
    cached position the live slots attend, once, in every latent layer."""
    return latent_row_bytes(cfg, sizes_of(itemsize).pages) * n_mla(cfg) \
        * latent_positions


def mla_decode_flops(cfg: dict, latent_positions: float,
                     itemsize: int = 4) -> float:
    """Operations of the same kernels: every head's score over ``c_kv |
    k_r`` and its value sum over ``c_kv``, 2 a multiply-add, a cached
    row."""
    c, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * ((c + dr) + c) \
        * n_mla(cfg) * latent_positions


def mla_prefill_flops(cfg: dict, n_tokens: float, itemsize: int = 4) -> float:
    """Operations of the expanded prefill attention over a prompt of
    ``n_tokens``: scores over keys of ``nope + rope`` and the value sum
    over ``v``, 2 a multiply-add, n (n + 1) / 2 causal pairs a head: the
    work, whatever padding the kernel runs."""
    n = float(n_tokens)
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) * n * (n + 1) / 2 * n_mla(cfg)


def delta_step_bytes(cfg: dict, state_slots: float, itemsize) -> float:
    """The delta state of every delta layer, read once and written once,
    for the slots the step advanced."""
    return 2 * delta_state_bytes_per_slot(cfg, sizes_of(itemsize).state) \
        * n_delta(cfg) \
        * state_slots


def delta_chunk_bytes(cfg: dict, scan_tokens: float, itemsize) -> float:
    """Bytes the delta rule of one prefill must move in every delta layer:
    q, k (a row a VALUE head, as the ops take them), v, the log decay and
    beta of every real token read, its output written, and the state it
    leaves written once.  What the rule reads and writes a token is
    float32 whatever the weights are in (kept); the state is the
    state's."""
    sz = sizes_of(itemsize)
    heads, dk, dv, _ = delta_dims(cfg)
    per_token = heads * (2 * dk + 2 * dv + 2)
    return n_delta(cfg) * (sz.kept * per_token * scan_tokens
                           + sz.state * heads * dk * dv)


def decode_step_bytes(cfg: dict, experts_held_touched: float,
                      latent_positions: float, state_slots: float,
                      itemsize) -> float:
    """Bytes one decode step over the grid must move: every layer's mixer
    and its four norms; a leading layer's dense SwiGLU; an expert layer's
    router over all its experts with its bias, the held experts that got
    a row (``experts_held_touched``, the mean over the expert layers) and
    the shared expert; the final norm and the untied head over the
    vocabulary slice, once; a row of the embedding a slot; a latent row a
    live position in the latent layers; and both states of every delta
    layer, read and written, for the ``state_slots`` slots advanced."""
    h = cfg["hidden_size"]
    sz = sizes_of(itemsize)
    heads, _, dv, channels = delta_dims(cfg)
    weights = h + h * cfg["vocab_size"] + state_slots * h
    # kept float32: norms (the two low-rank pairs' too), the router and its
    # bias, a | b, the taps, the decay's constants, the head norm's weight
    kept = h
    for i, kind in enumerate(layer_kinds(cfg)):
        weights += 4 * h + mixer_params(cfg, kind)
        kept += 4 * h + (
            cfg["q_lora_rank"] + cfg["kv_lora_rank"] if kind == "mla"
            else h * 2 * heads + channels * cfg["linear_conv_kernel_dim"]
            + 2 * heads + dv)
        if i < cfg["first_k_dense_replace"]:
            weights += dense_params(cfg)
        else:
            weights += router_params(cfg) + expert_params(cfg) * (
                experts_held_touched + cfg["n_shared_experts"])
            kept += router_params(cfg)
    state = 2 * conv_state_bytes_per_slot(cfg, sz.state) * n_delta(cfg) \
        * state_slots + delta_step_bytes(cfg, state_slots, sz)
    return sz.weights * (weights - kept) + sz.kept * kept + state \
        + mla_decode_bytes(cfg, latent_positions, sz)


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """FLOPs of prefilling ``n_tokens`` prompt tokens: every layer's
    projections at 2 per parameter and token; a delta layer's convolution
    (2 x taps a channel) and recurrence (6 x 128 x 128 a value head a
    token); a latent layer's expanded causal attention; the dense SwiGLU
    or the router over all its experts, the expected held pairs' experts
    and the shared expert; the head on one row."""
    h = cfg["hidden_size"]
    heads, dk, dv, channels = delta_dims(cfg)
    taps = cfg["linear_conv_kernel_dim"]
    n = float(n_tokens)
    flops = 2.0 * h * cfg["vocab_size"]
    for i, kind in enumerate(layer_kinds(cfg)):
        if kind == "delta":
            matrices = delta_mixer_params(cfg) - channels * taps \
                - 2 * heads - dv
            flops += 2.0 * n * matrices + 2.0 * n * taps * channels \
                + 6.0 * n * heads * dk * dv
        else:
            matrices = mla_mixer_params(cfg) - cfg["q_lora_rank"] \
                - cfg["kv_lora_rank"]
            flops += 2.0 * n * matrices
        if i < cfg["first_k_dense_replace"]:
            flops += 2.0 * n * dense_params(cfg)
        else:
            flops += 2.0 * n * (
                h * cfg["expert_share"]["router_experts"]
                + (held_pairs_per_token(cfg) + cfg["n_shared_experts"])
                * expert_params(cfg))
    return flops + mla_prefill_flops(cfg, n)
