"""Operations and bytes the ``nemotron3-super-120b-a12b`` configuration
needs, from shapes alone: the numerators of ``decode_step_roofline.pool``,
``prefill_roofline.pool``, ``paged_kernel_roofline.pool``,
``ssm_step_roofline.pool``, ``ssm_chunk_roofline.pool`` and
``expert_kernel_roofline.pool``.  They count the least the mathematics
requires whatever implements it (the recurrence's 6 x 64 x 128 operations
a head a token, a slot's state read once and written once a step, B and C
of every group once a token, an expert's two matrices in the latent width
once a step where it got a row, the latent pair and the full-width shared
expert once, the keys a causal row attends, the head on one row), from the
configuration's published keys, so no PR that changes the program can move
them.  A count never exceeds what the program does: a share over 100% is a
fault of the count.
"""
from __future__ import annotations

from ops_bytes import sizes_of


def layer_kinds(cfg: dict) -> list:
    """One letter a layer: ``M`` a state-space mixer, ``*`` attention,
    ``E`` the expert layer; a layer is that one sublayer."""
    return list(cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]])


def n_of(cfg: dict, letter: str) -> int:
    return layer_kinds(cfg).count(letter)


def ssm_dims(cfg: dict):
    """``(heads, head_dim, state rows, groups of B and C, channels of the
    convolution: x | B | C)``."""
    heads, p, n, g = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"], cfg["n_groups"]
    return heads, p, n, g, heads * p + 2 * g * n


def mamba_params(cfg: dict) -> int:
    """z | xBC | dt, the output projection, the taps and their bias, the
    three constants a head and the gated norm's weight."""
    h = cfg["hidden_size"]
    heads, p, _, _, channels = ssm_dims(cfg)
    inner = heads * p
    return h * (inner + channels + heads) + inner * h \
        + channels * (cfg["conv_kernel"] + 1) + 3 * heads + inner


def attention_params(cfg: dict) -> int:
    """Fused QKV over 32 query and 2 key-value heads of 128, and the
    output projection (no bias)."""
    d = cfg["head_dim"]
    q = cfg["num_attention_heads"] * d
    return cfg["hidden_size"] * (2 * q + 2 * cfg["num_key_value_heads"] * d)


def expert_params(cfg: dict) -> int:
    """One routed expert: two matrices in the latent width, no gate."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def expert_layer_fixed_params(cfg: dict) -> int:
    """What an expert layer reads whatever the routing: the router over
    all its experts with its bias, the latent pair (down and up) and the
    shared expert's two matrices at FULL width."""
    h = cfg["hidden_size"]
    return (h + 1) * cfg["expert_share"]["router_experts"] \
        + 2 * h * cfg["moe_latent_size"] \
        + 2 * h * cfg["moe_shared_expert_intermediate_size"] \
        * cfg["n_shared_experts"]


def held_pairs_per_token(cfg: dict) -> float:
    """Token-expert pairs a token gives this chip, in expectation: its
    ``num_experts_per_tok`` over the router's experts, times those held."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["expert_share"]["router_experts"]


def kv_bytes_per_position(cfg: dict, itemsize: int) -> int:
    """K and V of one position in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def ssm_state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    """One state-space layer's matrix state of one slot."""
    heads, p, n, _, _ = ssm_dims(cfg)
    return heads * p * n * itemsize


def conv_state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    """One state-space layer's convolution rows of one slot."""
    return (cfg["conv_kernel"] - 1) * ssm_dims(cfg)[4] * itemsize


def paged_kernel_bytes(cfg: dict, live_positions: float,
                       itemsize) -> float:
    """Bytes the paged decode kernels of one step must read: K and V of
    the positions the live slots attend, in every attention layer."""
    return kv_bytes_per_position(cfg, sizes_of(itemsize).pages) \
        * n_of(cfg, "*") \
        * live_positions


def ssm_step_bytes(cfg: dict, state_slots: float, itemsize) -> float:
    """Bytes the state steps of one decode step must move: the matrix
    state of every state-space layer, read once and written once, for the
    ``state_slots`` slots the step advanced (B and C of the groups are
    under a thousandth of it)."""
    return 2 * ssm_state_bytes_per_slot(cfg, sizes_of(itemsize).state) \
        * n_of(cfg, "M") \
        * state_slots


def ssm_chunk_bytes(cfg: dict, scan_tokens: float, itemsize) -> float:
    """Bytes the recurrence of one prefill must move in every state-space
    layer: x, dt and every group's B and C of every real token read, its
    output written, and the state it leaves written once (it starts from
    none).  What the recurrence reads and writes a token is float32
    whatever the weights are in (kept); the state is the state's."""
    sz = sizes_of(itemsize)
    heads, p, n, g, _ = ssm_dims(cfg)
    per_token = 2 * heads * p + 2 * g * n + heads
    return n_of(cfg, "M") * (sz.kept * per_token * scan_tokens
                             + sz.state * heads * p * n)


def ssm_chunk_flops(cfg: dict, scan_tokens: float) -> float:
    """Operations of the same: a head a token decays its state, adds an
    outer product and reads it along C, 6 x head_dim x state."""
    heads, p, n, _, _ = ssm_dims(cfg)
    return 6.0 * heads * p * n * n_of(cfg, "M") * scan_tokens


def expert_kernel_bytes(cfg: dict, experts_held_touched: float,
                        pairs_held: float, itemsize) -> float:
    """Bytes the grouped products of one decode step must move: the two
    matrices of every held expert that got a row (``experts_held_touched``,
    the mean over the expert layers), in every expert layer, and the held
    pairs' rows (``pairs_held``, summed over the layers) into and out of
    both products: latent in and width out, width in and latent out."""
    rows = 2 * (cfg["moe_latent_size"] + cfg["moe_intermediate_size"])
    # (the rows are activations that enter and leave a product of the
    # weights' dtype: the weights' size)
    return sizes_of(itemsize).weights * (
        expert_params(cfg) * experts_held_touched * n_of(cfg, "E")
        + rows * pairs_held)


def decode_step_bytes(cfg: dict, experts_held_touched: float,
                      live_positions: float, state_slots: float,
                      itemsize) -> float:
    """Bytes one decode step over the grid must move: every layer's one
    norm and its one sublayer (a state-space mixer; attention; or the
    router, the latent pair, the full-width shared expert and the held
    experts that got a row, ``experts_held_touched`` the mean over the
    expert layers); the final norm and the untied head over the vocabulary
    slice, once; a row of the embedding a slot; K and V of the positions
    the live slots attend (``live_positions``, summed over the slots) in
    the attention layers; and both states of every state-space layer, read
    and written, for the ``state_slots`` slots the step advanced."""
    h = cfg["hidden_size"]
    sz = sizes_of(itemsize)
    heads, p, _, _, channels = ssm_dims(cfg)
    weights = h + h * cfg["vocab_size"] + state_slots * h
    # kept float32: norms; the taps and their bias, the three constants a
    # head and the gated norm's weight; the router and its bias
    kept = h
    for kind in layer_kinds(cfg):
        weights += h + {
            "M": mamba_params(cfg), "*": attention_params(cfg),
            "E": expert_layer_fixed_params(cfg)
            + expert_params(cfg) * experts_held_touched}[kind]
        kept += h + {
            "M": channels * (cfg["conv_kernel"] + 1) + 3 * heads
            + heads * p, "*": 0,
            "E": (h + 1) * cfg["expert_share"]["router_experts"]}[kind]
    state = 2 * conv_state_bytes_per_slot(cfg, sz.state) * n_of(cfg, "M") \
        * state_slots + ssm_step_bytes(cfg, state_slots, sz)
    return sz.weights * (weights - kept) + sz.kept * kept + state \
        + paged_kernel_bytes(cfg, live_positions, sz)


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """FLOPs of prefilling ``n_tokens`` prompt tokens: every layer's
    projections at 2 per parameter and token; a state-space layer's
    convolution (2 x taps a channel) and recurrence (6 x head_dim x state
    a head a token); an attention layer's causal attention (scores and
    PV: 4 x head_dim per query head and attended key, n (n + 1) / 2
    pairs); an expert layer's router over all its experts, latent pair,
    shared expert and the expected held pairs' experts; the head on one
    row."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, p, _, _, channels = ssm_dims(cfg)
    taps = cfg["conv_kernel"]
    n = float(n_tokens)
    matrices = h * (heads * p + channels + heads) + heads * p * h
    flops = 2.0 * h * cfg["vocab_size"]
    for kind in layer_kinds(cfg):
        if kind == "M":
            flops += 2.0 * n * matrices + 2.0 * n * taps * channels \
                + ssm_chunk_flops(cfg, n) / n_of(cfg, "M")
        elif kind == "*":
            flops += 2.0 * n * attention_params(cfg) \
                + 4.0 * d * cfg["num_attention_heads"] * n * (n + 1) / 2
        else:
            flops += 2.0 * n * (
                expert_layer_fixed_params(cfg)
                - cfg["expert_share"]["router_experts"]
                + held_pairs_per_token(cfg) * expert_params(cfg))
    return flops
