"""Operations and bytes the ``longcat-flash-chat`` configuration needs, from
shapes alone: the numerators of ``decode_step_roofline.pool``,
``mla_decode_bytes_roofline.pool``, ``mla_decode_flops_roofline.pool``,
``prefill_chunk_roofline.pool`` and ``chunk_attention_roofline.pool``.
They count the least the mathematics requires whatever implements it, for
THIS chip's share: a published layer's TWO latent sublayers (at the heads
held here), its TWO dense SwiGLUs, its router over all 768 outputs and its
ONE expert branch (the held experts that got a row, never the absent
ones); an IDENTITY pick is no bytes and no FLOPs: it has no weights, and
its one multiply-add a row is left out with the norms and the residual
adds; the head over the vocabulary slice on one row; a latent row a cached
position a sublayer, at the pool's row size as run, read once; an admitted
(row, column) pair at the EXPANDED form's operations a head.  From the
configuration's keys, so no PR that changes the program can move them.  A
count never exceeds what the program does: a share over 100% is a fault of
the count.
"""
from __future__ import annotations

from ops_bytes import sizes_of

SUBLAYERS = 2            # latent sublayers (and dense SwiGLUs) a layer


def mla_mixer_params(cfg: dict) -> int:
    """One latent sublayer: the two low-rank pairs with their norms and
    the output projection, at the heads held here: no bias, no gate."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return h * rq + rq + rq * heads * (dn + dr) + h * (c + dr) + c \
        + c * heads * (dn + dv) + heads * dv * h


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg: dict) -> int:
    """One real expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix over ALL its outputs, identity experts too, and
    its selection bias."""
    e = cfg["expert_share"]["router_experts"]
    return cfg["hidden_size"] * e + e


def layer_params_outside_experts(cfg: dict) -> int:
    """A published layer less its routed experts: two latent sublayers,
    two dense SwiGLUs, four norms, the router."""
    return SUBLAYERS * (mla_mixer_params(cfg) + dense_params(cfg)
                        + 2 * cfg["hidden_size"]) + router_params(cfg)


def weight_params(cfg: dict) -> int:
    """Every parameter this chip holds: the cut's arithmetic."""
    return cfg["num_layers"] * (
        layer_params_outside_experts(cfg)
        + cfg["n_routed_experts"] * expert_params(cfg)) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def held_pairs_per_token(cfg: dict) -> float:
    """Token-expert pairs a token gives this chip a layer, in expectation:
    its ``moe_topk`` picks over the router's outputs, times the real
    experts held (a third of the picks are identity under even routing,
    and cost nothing anywhere)."""
    return cfg["moe_topk"] * cfg["n_routed_experts"] \
        / cfg["expert_share"]["router_experts"]


def latent_row_bytes(cfg: dict, itemsize: int) -> int:
    """One cached position of one sublayer, as the pool keeps it:
    ``as_run.latent_row.lanes`` numbers."""
    return int(cfg["as_run"]["latent_row"]["lanes"]) * itemsize


def mla_decode_bytes(cfg: dict, latent_positions: float,
                     itemsize) -> float:
    """Bytes the latent decode kernels of one step must read: one row a
    cached position the live slots attend, once, in every sublayer."""
    return latent_row_bytes(cfg, sizes_of(itemsize).pages) * SUBLAYERS \
        * cfg["num_layers"] * latent_positions


def mla_decode_flops(cfg: dict, latent_positions: float,
                     itemsize: int = 4) -> float:
    """Operations of the same kernels: every held head's score over ``c_kv
    | k_r`` and its value sum over ``c_kv``, 2 a multiply-add, a cached
    row (the absorbed form)."""
    c, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * ((c + dr) + c) \
        * SUBLAYERS * cfg["num_layers"] * latent_positions


def decode_step_bytes(cfg: dict, experts_held_touched: float,
                      latent_positions: float, itemsize) -> float:
    """Bytes one decode step over the grid must move: every layer's
    weights outside its routed experts and the held experts that got a row
    (``experts_held_touched``, the mean over the expert layers); the final
    norm and the untied head over the vocabulary slice, once (the rows the
    embedding reads are left out); a latent row a live position in every
    sublayer.  An identity pick reads nothing."""
    h = cfg["hidden_size"]
    weights = h + h * cfg["vocab_size"] + cfg["num_layers"] * (
        layer_params_outside_experts(cfg)
        + expert_params(cfg) * experts_held_touched)
    sz = sizes_of(itemsize)
    # kept float32: norms (the low-rank pairs' too), the router, its bias
    kept = h + cfg["num_layers"] * (
        SUBLAYERS * (2 * h + cfg["q_lora_rank"] + cfg["kv_lora_rank"])
        + router_params(cfg))
    return sz.weights * (weights - kept) + sz.kept * kept \
        + mla_decode_bytes(cfg, latent_positions, sz)


def pair_flops(cfg: dict) -> int:
    """Scores and PV of one admitted (row, column) pair over every held
    head in the EXPANDED form: keys of ``nope + rope``, values of ``v``, 2
    a multiply-add: 8 x 640 = 5,120."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def chunk_pairs(n_tokens: int, base: int) -> int:
    """The (row, column) pairs the ``n_tokens`` rows of a chunk at ``base``
    admit in one sublayer: ``j <= base + t``."""
    n, b = int(n_tokens), int(base)
    return n * b + n * (n + 1) // 2


def chunk_flops(cfg: dict, n_tokens: int, base: int) -> float:
    """FLOPs of one prefill chunk's ``n_tokens`` real rows at ``base``:
    every layer's two latent sublayers' projections, two dense SwiGLUs,
    the router over all its outputs and the expected held pairs' experts,
    at 2 per parameter and row; attention over the admitted pairs of every
    sublayer in the expanded form.  Identity picks add no FLOPs; what the
    kernel expands again of the cached rows is not the model's work and is
    left out; the head runs on one row of a prompt's last chunk and is
    left out."""
    layers = cfg["num_layers"]
    matrices = mla_mixer_params(cfg) - cfg["q_lora_rank"] \
        - cfg["kv_lora_rank"]
    e = cfg["expert_share"]["router_experts"]
    matmul = layers * (SUBLAYERS * (matrices + dense_params(cfg))
                       + cfg["hidden_size"] * e
                       + held_pairs_per_token(cfg) * expert_params(cfg))
    return 2.0 * n_tokens * matmul + float(pair_flops(cfg)) * SUBLAYERS \
        * layers * chunk_pairs(n_tokens, base)


def chunk_attention_flops(cfg: dict, attended_pairs: float,
                          itemsize: int) -> float:
    """FLOPs the latent chunk kernels of one chunk must do:
    ``attended_pairs`` is summed over the sublayers (the engine's span
    says what the chunk's real rows admit), each pair the expanded form's
    5,120.  ``itemsize`` is the reader's and is not read."""
    return float(pair_flops(cfg)) * attended_pairs
