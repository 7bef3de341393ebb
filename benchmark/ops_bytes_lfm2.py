"""Operations and bytes the ``lfm2-24b-a2b`` configuration needs, from
shapes alone: the numerators of ``decode_step_roofline.pool``,
``prefill_roofline.pool`` and ``paged_kernel_roofline.pool``.  They count
the least the mathematics requires (the experts a row was routed to, the
keys a causal row attends, the head on one row), from the configuration's
published keys, so no PR that changes the program can move them.  A count
never exceeds what the program does: a share over 100% is a fault of the
count.
"""
from __future__ import annotations

from ops_bytes import sizes_of


def layer_kinds(cfg: dict) -> list:
    """``(mixer, dense)`` of every layer that is run."""
    return [(kind, i < cfg["num_dense_layers"]) for i, kind in enumerate(
        cfg["layer_types"][:cfg["num_hidden_layers"]])]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def conv_mixer_params(cfg: dict) -> int:
    """Input projection (2048 -> 6144), output projection, the depthwise
    kernel."""
    h = cfg["hidden_size"]
    return h * 3 * h + h * h + h * cfg["conv_L_cache"]


def attention_mixer_params(cfg: dict) -> int:
    """Fused QKV, output, the two QK-norm weights."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * (q + 2 * kv) + q * h + 2 * d


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix and the selection bias."""
    return (cfg["hidden_size"] + 1) * cfg["num_experts"]


def kv_bytes_per_position(cfg: dict, itemsize: int) -> int:
    """K and V of one position in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def state_bytes_per_slot(cfg: dict, itemsize: int) -> int:
    """One conv layer's state of one slot: ``L_cache - 1`` rows."""
    return (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * itemsize


def paged_kernel_bytes(cfg: dict, live_positions: float,
                       itemsize) -> float:
    """Bytes the paged decode kernels of one step must read: K and V of
    the positions the live slots attend, in every attention layer."""
    n_attn = sum(kind != "conv" for kind, _ in layer_kinds(cfg))
    return kv_bytes_per_position(cfg, sizes_of(itemsize).pages) * n_attn \
        * live_positions


def decode_step_bytes(cfg: dict, experts_touched: float,
                      live_positions: float, state_slots: float,
                      itemsize) -> float:
    """Bytes one decode step over the grid must move: every layer's mixer
    and its two norms; the leading dense SwiGLU; in every expert layer the
    router, its bias and the experts that got a row (``experts_touched``,
    the mean over the layers); the final norm and the tied table, once
    (the head's product reads all of it; a slot's embedding row is in
    it); K and V of the positions the live slots attend
    (``live_positions``, summed over the slots) in every attention layer;
    and the state of every conv layer, read and written, for the
    ``state_slots`` slots the step advanced."""
    h = cfg["hidden_size"]
    sz = sizes_of(itemsize)
    weights = h + h * cfg["vocab_size"]
    kept = h            # kept float32: norms, taps, the router and its bias
    n_conv = 0
    for kind, dense in layer_kinds(cfg):
        weights += 2 * h + (conv_mixer_params(cfg) if kind == "conv"
                            else attention_mixer_params(cfg))
        weights += dense_params(cfg) if dense else \
            router_params(cfg) + experts_touched * expert_params(cfg)
        kept += 2 * h + (h * cfg["conv_L_cache"] if kind == "conv"
                         else 2 * head_dim(cfg)) \
            + (0 if dense else router_params(cfg))
        n_conv += kind == "conv"
    state = 2 * state_bytes_per_slot(cfg, sz.state) * n_conv * state_slots
    return sz.weights * (weights - kept) + sz.kept * kept + state \
        + paged_kernel_bytes(cfg, live_positions, sz)


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """FLOPs of prefilling ``n_tokens`` prompt tokens: every layer's mixer
    (a conv layer's two projections and ``L_cache`` multiply-adds a
    channel; an attention layer's QKV and output projections and causal
    attention, scores and PV: 4 x head_dim per query head and attended
    key, n (n + 1) / 2 pairs), the dense SwiGLU or the router and the
    ``num_experts_per_tok`` experts a token is routed to, and the tied
    head on one row; 2 per parameter and token."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    n = float(n_tokens)
    flops = 2.0 * h * cfg["vocab_size"]
    for kind, dense in layer_kinds(cfg):
        if kind == "conv":
            flops += 2.0 * n * conv_mixer_params(cfg)
        else:
            flops += 2.0 * n * (attention_mixer_params(cfg) - 2 * d) \
                + 4.0 * d * cfg["num_attention_heads"] * n * (n + 1) / 2
        flops += 2.0 * n * (dense_params(cfg) if dense else (
            h * cfg["num_experts"]
            + cfg["num_experts_per_tok"] * expert_params(cfg)))
    return flops
