"""Operations and bytes the ``deepseek-v2`` configuration needs, from shapes
alone: the numerators of ``decode_step_roofline.pool``,
``mla_decode_bytes_roofline.pool``, ``mla_decode_flops_roofline.pool``,
``prefill_chunk_roofline.pool`` and ``chunk_attention_roofline.pool``.
They count the least the mathematics requires whatever implements it, for
THIS chip's share (the held experts that got a row, never the absent ones;
the two shared experts; the router over all its experts; the head over the
vocabulary slice on one row; a latent row a cached position, at the pool's
row size as run, read once; an admitted (row, column) pair at the EXPANDED
form's 640 operations a head, each cached row expanded once), from the
configuration's keys, so no PR that changes the program can move them.  A
count never exceeds what the program does: a share over 100% is a fault of
the count.
"""
from __future__ import annotations

from ops_bytes import sizes_of


def mla_mixer_params(cfg: dict) -> int:
    """The two low-rank pairs with their norms and the output projection:
    no bias, no gate."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return h * rq + rq + rq * heads * (dn + dr) + h * (c + dr) + c \
        + c * heads * (dn + dv) + heads * dv * h


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One expert (routed or shared): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["expert_share"]["router_experts"]


def held_pairs_per_token(cfg: dict) -> float:
    """Token-expert pairs a token gives this chip, in expectation: its
    ``num_experts_per_tok`` over the router's experts, times those held
    (group-limited selection moves which rows they come from, not their
    number where the groups are evenly liked)."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["expert_share"]["router_experts"]


def latent_row_bytes(cfg: dict, itemsize: int) -> int:
    """One cached position of one layer, as the pool keeps it:
    ``as_run.latent_row.lanes`` numbers (``[c_kv | k_r]`` in whole lane
    tiles)."""
    return int(cfg["as_run"]["latent_row"]["lanes"]) * itemsize


def mla_decode_bytes(cfg: dict, latent_positions: float,
                     itemsize) -> float:
    """Bytes the latent decode kernels of one step must read: one row a
    cached position the live slots attend, once, in every layer."""
    return latent_row_bytes(cfg, sizes_of(itemsize).pages) \
        * cfg["num_hidden_layers"] * latent_positions


def mla_decode_flops(cfg: dict, latent_positions: float,
                     itemsize: int = 4) -> float:
    """Operations of the same kernels: every head's score over ``c_kv |
    k_r`` and its value sum over ``c_kv``, 2 a multiply-add, a cached row
    (the absorbed form: 2,176 a head a row)."""
    c, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * ((c + dr) + c) \
        * cfg["num_hidden_layers"] * latent_positions


def decode_step_bytes(cfg: dict, experts_held_touched: float,
                      latent_positions: float, itemsize) -> float:
    """Bytes one decode step over the grid must move: every layer's
    latent attention matrices and its two norms; the leading layer's dense
    SwiGLU; an expert layer's router over all its experts, the held
    experts that got a row (``experts_held_touched``, the mean over the
    expert layers) and the two shared experts; the final norm and the
    untied head over the vocabulary slice, once (the rows the embedding
    reads are left out); a latent row a live position in every layer."""
    h = cfg["hidden_size"]
    sz = sizes_of(itemsize)
    weights = h + h * cfg["vocab_size"]
    # kept float32: norms (the two low-rank pairs' too) and the router
    kept = h
    for i in range(cfg["num_hidden_layers"]):
        weights += 2 * h + mla_mixer_params(cfg)
        kept += 2 * h + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
        if i < cfg["first_k_dense_replace"]:
            weights += dense_params(cfg)
        else:
            weights += router_params(cfg) + expert_params(cfg) * (
                experts_held_touched + cfg["n_shared_experts"])
            kept += router_params(cfg)
    return sz.weights * (weights - kept) + sz.kept * kept \
        + mla_decode_bytes(cfg, latent_positions, sz)


def pair_flops(cfg: dict) -> int:
    """Scores and PV of one admitted (row, column) pair over every head in
    the EXPANDED form: keys of ``nope + rope``, values of ``v``, 2 a
    multiply-add: 128 x 640 = 81,920."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def chunk_pairs(n_tokens: int, base: int) -> int:
    """The (row, column) pairs the ``n_tokens`` rows of a chunk at ``base``
    admit in one layer: ``j <= base + t``."""
    n, b = int(n_tokens), int(base)
    return n * b + n * (n + 1) // 2


def chunk_flops(cfg: dict, n_tokens: int, base: int) -> float:
    """FLOPs of one prefill chunk's ``n_tokens`` real rows at ``base``:
    every layer's projections (``W_kvb`` on the chunk's own rows: each
    row's keys and values made once), the dense SwiGLU or the router over
    all its experts, the expected held pairs' experts and the two shared
    experts, at 2 per parameter and row; attention over the admitted
    pairs in the expanded form.  What the kernel expands again of the
    cached rows is not the model's work and is left out; the head runs on
    one row of a prompt's last chunk and is left out."""
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    matrices = mla_mixer_params(cfg) - cfg["q_lora_rank"] \
        - cfg["kv_lora_rank"]
    matmul = layers * matrices + dense * dense_params(cfg) \
        + (layers - dense) * (
            router_params(cfg)
            + (held_pairs_per_token(cfg) + cfg["n_shared_experts"])
            * expert_params(cfg))
    return 2.0 * n_tokens * matmul \
        + float(pair_flops(cfg)) * layers * chunk_pairs(n_tokens, base)


def chunk_attention_flops(cfg: dict, attended_pairs: float,
                          itemsize: int) -> float:
    """FLOPs the latent chunk kernels of one chunk must do:
    ``attended_pairs`` is summed over the layers (the engine's span says
    what the chunk's real rows admit), each pair the expanded form's
    81,920, each row expanded once (by the projections, not here),
    whatever the kernel re-expands.  ``itemsize`` is the reader's and is
    not read."""
    return float(pair_flops(cfg)) * attended_pairs
