"""moe_ffn graph op: Switch-style expert-parallel FFN.

New capability (SURVEY.md §2.6 — completes the TP/EP/CP/SP quartet; the
reference vintage has no MoE op). Lowering picks the TPU execution per
context, the same pattern as flash_attention:
  * `ep` axis bound (shard_map / build_spmd_step) -> all_to_all token
    dispatch over ICI (parallel/moe.py)
  * otherwise (single device or GSPMD build_sharded_step) -> dense
    einsum math; under GSPMD the expert weights are physically sharded
    by parallel.moe.moe_rules and XLA inserts the collectives.
"""
from __future__ import annotations

import contextlib

from .registry import in_var, register_op, set_out


def _moe_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", x.shape, x.dtype)
    set_out(op, block, "AuxLoss", (), "float32")
    if op.output("ExpertCount"):
        e = in_var(op, block, "GateW").shape[1]
        set_out(op, block, "ExpertCount", (e,), "float32")


@register_op("moe_ffn", infer=_moe_infer, grad="auto")
def _moe_ffn(ctx, op):
    from ..parallel.mesh import EP_AXIS
    from ..parallel.moe import moe_ffn_tokens

    x = ctx.get_input(op, "X")
    gate_w = ctx.get_input(op, "GateW")
    w1, b1 = ctx.get_input(op, "W1"), ctx.get_input(op, "B1")
    w2, b2 = ctx.get_input(op, "W2"), ctx.get_input(op, "B2")
    axes = getattr(ctx, "axis_names", ()) or ()
    axis = EP_AXIS if EP_AXIS in axes else None
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    out, aux, counts = moe_ffn_tokens(
        flat, gate_w, w1, b1, w2, b2,
        capacity_factor=float(op.attr("capacity_factor", 1.25)),
        axis_name=axis,
        activation=op.attr("activation", "gelu"))
    ctx.set_output(op, "Out", out.reshape(shape))
    ctx.set_output(op, "AuxLoss", aux)
    if op.output("ExpertCount"):
        ctx.set_output(op, "ExpertCount", counts)


def _moe_routed_infer(op, block):
    x = in_var(op, block, "X")
    e = in_var(op, block, "RouterW").shape[1]
    set_out(op, block, "Out", x.shape, x.dtype)
    set_out(op, block, "ExpertCount", (e,), "int32")
    if op.output("RouterLogits"):
        set_out(op, block, "RouterLogits", tuple(x.shape[:-1]) + (e,),
                "float32")
    if op.output("GroupRows"):
        set_out(op, block, "GroupRows", (int(op.attr("n_group")),), "int32")


@register_op("moe_routed_ffn", infer=_moe_routed_infer, grad=None)
def _moe_routed_ffn(ctx, op):
    """Dropless top-k mixture of gated experts (``parallel/moe.py``
    ``moe_routed_tokens``): X [B, S, H] is the experts' input, RouterX
    [B, S, H] what the router reads (an architecture may route from the
    layer's raw input, before attention), Valid [B] int the number of
    real rows of each batch row (optional: all; the rows behind them go
    through no expert and their Out is 0), ExpertBias [E] the
    selection bias of sigmoid scoring (optional).  With the attribute
    ``held_first`` GateUpW and DownW hold the experts from that index on
    alone, one chip's share of RouterW's E, and Out is their part of the
    sum.  GateUpW [E, H, I] (not 2I) makes the experts two matrices
    without a gate, ``W2 act(W1 x)``, and RouterX may be wider than X (a
    router that reads the full row beside experts that work in a latent
    one).  Attribute ``limit``: the experts' SwiGLU clamp.  Attributes
    ``n_group`` / ``topk_group``: group-limited selection
    (``route_top_k``); the output GroupRows [n_group] int32 then counts the
    valid rows that kept each group.  Attribute ``zero_experts`` Z: the
    last Z of RouterW's E outputs are identity experts (no weights; a pick
    adds its routing weight times X), ExpertBias then moves a softmax
    router's choice too, and ExpertCount's last Z count their picks.
    Attribute ``scope``: a ``jax.named_scope`` around the whole layer: the
    compiled module's ``op_name`` metadata carries it (the profiler's
    event names on a TPU do not: PERF.md section 6, PR 66).  Inference
    only."""
    import jax
    import jax.numpy as jnp

    from ..parallel.moe import group_keep, moe_routed_tokens
    from .math_ops import _mm_precision

    x = ctx.get_input(op, "X")
    shape = x.shape
    n_valid = ctx.get_input(op, "Valid") if op.single_input("Valid") \
        else None
    valid = None
    if n_valid is not None:
        t = jnp.arange(shape[1], dtype=jnp.int32)[None, :]
        valid = (t < n_valid.astype(jnp.int32)[:, None]).reshape(-1)
    n_group = int(op.attr("n_group", 1))
    topk_group = int(op.attr("topk_group", 1))
    router_x = ctx.get_input(op, "RouterX")
    scope = op.attr("scope", None)
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        out, counts, logits = moe_routed_tokens(
            x.reshape(-1, shape[-1]),
            router_x.reshape(-1, router_x.shape[-1]),
            ctx.get_input(op, "RouterW"), ctx.get_input(op, "GateUpW"),
            ctx.get_input(op, "DownW"), top_k=int(op.attr("top_k")),
            activation=op.attr("activation", "relu"), valid=valid,
            precision=_mm_precision(x.dtype),
            score=op.attr("score", "softmax"),
            expert_bias=ctx.get_input(op, "ExpertBias")
            if op.single_input("ExpertBias") else None,
            norm_topk=bool(op.attr("norm_topk", True)),
            route_scale=float(op.attr("route_scale", 1.0)),
            held_first=op.attr("held_first", None),
            limit=op.attr("limit", None),
            mesh_devices=ctx.mesh.devices.size if ctx.mesh is not None
            else 1,
            n_group=n_group, topk_group=topk_group,
            zero_experts=int(op.attr("zero_experts", 0)))
    ctx.set_output(op, "Out", out.reshape(shape))
    ctx.set_output(op, "ExpertCount", counts)
    if op.output("GroupRows"):
        kept = group_keep(jax.nn.softmax(logits, axis=-1), n_group,
                          topk_group)
        if valid is not None:
            kept = kept & valid[:, None]
        ctx.set_output(op, "GroupRows", kept.sum(axis=0).astype(jnp.int32))
    if op.output("RouterLogits"):
        ctx.set_output(op, "RouterLogits",
                       logits.reshape(shape[:-1] + (logits.shape[-1],)))
