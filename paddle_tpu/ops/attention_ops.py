"""Fused attention op.

Reference analog: operators/fused/multihead_matmul_op.cu (inference-only,
fixed layout). Here a first-class training op that picks the best TPU
execution per context:
  * `sp` mesh axis bound (shard_map)  -> ring attention over ICI
  * TPU backend, one device or a manual (shard_map) context
                                      -> pallas flash-attention kernel
  * TPU backend, GSPMD mesh           -> the same kernel per shard, through
                                         shard_map (batch over `dp`, heads
                                         over `mp`: `kernel_partition`);
                                         blockwise where the operands do
                                         not divide
  * CPU (tests/virtual mesh)          -> blockwise scan formulation
"""
from __future__ import annotations

import functools
import logging

from ..monitor import monitor as _monitor
from ..parallel.mesh import DP_AXIS, MP_AXIS, SP_AXIS
from .registry import (_lower_auto_grad, build_auto_grad_specs, in_var,
                       infer_auto_grad, register_op, set_out)

logger = logging.getLogger("paddle_tpu.ops.attention")

# which implementation each attention op lowered to, counted at trace
# time (per program build, like the collective_* stats)
_LOWERED = {
    "pallas": _monitor.get("attention_lowered_pallas"),
    "blockwise": _monitor.get("attention_lowered_blockwise"),
    "ring": _monitor.get("attention_lowered_ring"),
    "xla": _monitor.get("attention_lowered_xla"),
    # of the "pallas" ops, those that run the kernel per shard of a
    # multi-device mesh (shard_map over ctx.mesh): booked beside the
    # plain counter, which they also raise
    "pallas_sharded": _monitor.get("attention_lowered_pallas_sharded"),
    # the paged one-token decode step (ops/decode_ops.py): the Pallas
    # kernel over live pages, or the gather + einsum formulation
    "paged_decode": _monitor.get("attention_lowered_paged_decode"),
    "paged_decode_reference":
        _monitor.get("attention_lowered_paged_decode_reference"),
    # a latent (MLA) layer's two attentions (ops/latent_attention_ops.py):
    # the expanded prefill kernel, and the absorbed decode step as the
    # Pallas kernel over live latent pages or the gathered formulation
    "latent_prefill": _monitor.get("attention_lowered_latent_prefill"),
    "latent_decode": _monitor.get("attention_lowered_latent_decode"),
    "latent_decode_reference":
        _monitor.get("attention_lowered_latent_decode_reference"),
    # ... and a prefill chunk over latent pages: the Pallas kernel that
    # expands each key block in VMEM, or einsums over the whole view
    "latent_chunk": _monitor.get("attention_lowered_latent_chunk"),
    "latent_chunk_reference":
        _monitor.get("attention_lowered_latent_chunk_reference"),
    # a prefill chunk over the slot's cache view (ops/decode_ops.py
    # ``chunk_attention``): the Pallas kernel, or the einsum formulation
    "chunk_pallas": _monitor.get("attention_lowered_chunk_pallas"),
    "chunk_reference": _monitor.get("attention_lowered_chunk_reference"),
    # the same ops under a sliding window (``window`` attr set): booked
    # beside the plain counters, which they also raise
    "pallas_window": _monitor.get("attention_lowered_pallas_window"),
    "xla_window": _monitor.get("attention_lowered_xla_window"),
    "blockwise_window":
        _monitor.get("attention_lowered_blockwise_window"),
    "paged_decode_window":
        _monitor.get("attention_lowered_paged_decode_window"),
    "paged_decode_reference_window":
        _monitor.get("attention_lowered_paged_decode_reference_window"),
}
# how each grad op of the two attention ops got its gradients (per program
# build): off what the forward saved (its output and softmax statistic: the
# two backward kernels alone), or through the auto-grad op, which lowers
# the whole forward a second time inside jax.vjp
_GRAD = {
    "saved": _monitor.get("attention_grad_saved"),
    "relowered": _monitor.get("attention_grad_relowered"),
}
_downgrades_logged = set()


def _lowered(path, downgrade_reason=None, window=None, sharded=False):
    """Book the path taken.  On a TPU backend a reference formulation
    (blockwise, or the paged decode step's gather + einsum) is a
    downgrade from the Pallas kernels, not an equivalent: say so, once
    per reason."""
    _LOWERED[path].increase()
    if window is not None:
        _LOWERED[path + "_window"].increase()
    if sharded:
        _LOWERED[path + "_sharded"].increase()
    if downgrade_reason and downgrade_reason not in _downgrades_logged:
        _downgrades_logged.add(downgrade_reason)
        logger.warning("attention lowered to its reference formulation "
                       "(%s) on a TPU backend, not the Pallas kernels: %s",
                       path, downgrade_reason)


def kernel_partition(mesh_shape, manual_axes, batch, heads):
    """How a Pallas kernel runs under the mesh an op is lowered in, read
    from what the op can observe: the mesh's axes and its operands' shapes.

    ``mesh_shape``: axis name -> size of ``ctx.mesh`` (empty: no mesh);
    ``manual_axes``: ``ctx.axis_names``, the axes a surrounding
    ``shard_map`` has bound (``parallel/spmd.py``); ``batch``: the
    operands' leading dim; ``heads``: the head count of every operand
    that has a head dim (dim 1), or None where heads are not a dim of
    their own (the packed ``[B, S, 3H]`` projection, whose columns are
    ``(3, h, d)``-ordered).

    Returns one of
      * ``("direct", None)``: operands are whole (no mesh, one device) or
        already local (manual context): call the kernel as it is;
      * ``("shard_map", (batch_axes, head_axis))``: GSPMD context: wrap the
        kernel in ``shard_map`` over the mesh, dim 0 split over
        ``batch_axes`` and dim 1 over ``head_axis`` (either may be
        empty / None);
      * ``("reference", reason)``: no clean partition: keep the reference
        formulation, which GSPMD shards as it likes.  The kernel's work is
        never replicated over an axis.
    """
    if manual_axes:
        return "direct", None
    live = {a: n for a, n in mesh_shape.items() if n > 1}
    if not live:
        return "direct", None
    unknown = sorted(a for a in live if a not in (DP_AXIS, MP_AXIS))
    if unknown:
        return "reference", (
            f"mesh axis {', '.join(unknown)} (the kernels split over "
            f"{DP_AXIS} and {MP_AXIS} only)")
    # the batch over dp, as build_sharded_step shards the feeds
    dp, mp = live.get(DP_AXIS, 1), live.get(MP_AXIS, 1)
    if batch % dp:
        return "reference", (
            f"batch {batch} does not divide over {DP_AXIS}={dp}")
    if mp > 1 and heads is None:
        return "reference", (
            f"packed [B, S, 3H] columns cannot split over {MP_AXIS}={mp}")
    if mp > 1 and any(h % mp for h in heads):
        return "reference", (
            f"head counts {tuple(heads)} do not divide over {MP_AXIS}={mp}")
    return "shard_map", ((DP_AXIS,) if dp > 1 else (),
                         MP_AXIS if mp > 1 else None)


def kernel_route(ctx, batch, heads):
    """``kernel_partition`` for the context an op is lowered in; off a TPU
    backend the reference formulation, with no reason to log."""
    import jax

    if jax.default_backend() != "tpu":
        return "reference", None
    return kernel_partition(
        ctx.mesh.shape if ctx.mesh is not None else {},
        getattr(ctx, "axis_names", ()) or (), batch, heads)


# what each dim of a kernel operand is to the partition
_BHSD = ("batch", "heads", None, None)
_BSH = ("batch", None, None)
_BS = ("batch", None)


@functools.lru_cache(maxsize=64)
def _sharded_kernel(fn, static, mesh, in_specs, out_spec):
    """``fn(*operands, **static)`` per shard of ``mesh``, jitted and kept:
    the layers of a model call it with the same shapes, and a jitted
    callable is traced (kernel bodies, their JVP and transpose) once for
    all of them, not once a layer (2.5 s of set-up for BERT-base's 12)."""
    import jax

    from ..parallel.mesh import shard_map_compat

    # check off: a pallas_call has no replication rule
    return jax.jit(shard_map_compat(
        functools.partial(fn, **dict(static)), mesh, in_specs=in_specs,
        out_specs=out_spec))


def call_kernel(mesh, how, fn, operands, layouts, out_layout, **static):
    """``fn(*operands, **static)`` on the "direct" (``how`` None) or
    "shard_map" route of ``kernel_partition``, with ``how`` that route's
    second value.  ``layouts`` names, per operand, what each dim is to the
    partition ("batch", "heads" or None); under ``shard_map`` the kernel
    sees each device's local block.  ``out_layout`` is the output's layout,
    or a tuple of layouts where ``fn`` returns a tuple.  ``static`` are the
    kernel's non-array arguments (hashable)."""
    if how is None:
        return fn(*operands, **static)
    from jax.sharding import PartitionSpec as P

    batch_axes, head_axis = how
    place = {"batch": batch_axes or None, "heads": head_axis}

    def spec(layout):
        return P(*(place.get(role) for role in layout))

    if isinstance(out_layout[0], tuple):
        out_spec = tuple(spec(lay) for lay in out_layout)
    else:
        out_spec = spec(out_layout)
    return _sharded_kernel(
        fn, tuple(sorted(static.items())), mesh,
        tuple(spec(lay) for lay in layouts), out_spec)(*operands)


# ---------------------------------------------------------------------------
# the forward's softmax statistic, and the backward that reads it
# ---------------------------------------------------------------------------
#
# The forward kernels write the log-sum-exp of every score row beside the
# output; the backward kernels need both.  The kernel route lowers to
# ``flash_attention_lse`` / ``flash_attention_packed_lse``, which return
# both and differentiate as the kernels' own backward, so whatever takes
# jax.vjp of the forward lowering (the auto-grad op, a pipeline stage, the
# dygraph tracer, a ``run_program`` block) trains through it.  An op whose desc has
# the ``SoftmaxLse`` output slot (layers.flash_attention /
# flash_attention_qkv create it) binds the statistic there, and its grad op
# runs the two backward kernels on (inputs, Out, SoftmaxLse, dOut).
# Without the slot (a program saved before it existed), or off the kernel
# route, the grad op is the registry's auto-grad: jax.vjp of the forward
# lowering, which on the kernel route launches the forward kernel a second
# time (XLA does not merge two custom calls).

_STAT = "SoftmaxLse"
_BHS = ("batch", "heads", None)


def _bind_statistic(ctx, op, shape, lse=None):
    """Bind the op's ``SoftmaxLse`` slot, where its desc has one: the
    kernels' statistic, or zeros of its shape off the kernel route, where
    no backward reads it and XLA drops it.  Whoever lowers the op and
    collects its outputs (the auto-grad op, the dygraph tracer) finds
    every declared output bound."""
    if not op.single_output(_STAT):
        return
    if lse is None:
        import jax.numpy as jnp

        lse = jnp.zeros(shape, jnp.float32)
    ctx.set_output(op, _STAT, lse)


def _split_backward(q, k, v, out, lse, g, bias=None, *, causal, sm_scale):
    """(dq, dk, dv[, dbias]) off the forward's saved ``out`` and ``lse``:
    what ``flash_attention_lse``'s vjp computes, without the forward."""
    from .pallas.flash_attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q,
                                         _flash_backward)

    *grads, db = _flash_backward(q, k, v, out, lse, g, causal, sm_scale,
                                 DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, False,
                                 bias=bias)
    return tuple(grads) if bias is None else (*grads, db)


def _packed_backward(qkv, out, lse, g, bias=None, *, num_heads, causal,
                     sm_scale):
    """(dqkv[, dbias]) off the forward's saved ``out`` and ``lse``."""
    from .pallas.flash_attention import packed_backward

    dqkv, db = packed_backward(qkv, bias, out, lse, g, num_heads, causal,
                               sm_scale)
    return (dqkv,) if bias is None else (dqkv, db)


def _if_bias(bias, item):
    """``(item,)`` for an op with a score bias, ``()`` without: the bias
    operand, its layout and its gradient's ride last in every tuple."""
    return () if bias is None else (item,)


def _row_bias(ctx, op):
    """The op's additive score bias as rows [B, S] ([B,1,1,S]-style masks
    flattened), or None."""
    bias = ctx.get_input(op, "Bias") if op.single_input("Bias") else None
    if bias is not None and bias.ndim != 2:
        bias = bias.reshape(bias.shape[0], bias.shape[-1])
    return bias


def _split_route(ctx, op, q, k, v):
    """``kernel_route`` of a ``flash_attention`` op (or its grad op) that
    reaches the kernel-or-blockwise branch; None where an earlier branch
    takes it (the einsum formulation, ring / Ulysses)."""
    if op.attr("impl", "auto") == "xla" \
            or SP_AXIS in (getattr(ctx, "axis_names", ()) or ()):
        return None
    return kernel_route(ctx, q.shape[0],
                        (q.shape[1], k.shape[1], v.shape[1]))


def _packed_route(ctx, qkv, num_heads):
    """``kernel_route`` of a ``flash_attention_qkv`` op (or its grad op),
    and the packed kernels' own shape test."""
    H = qkv.shape[-1] // 3
    D = H // num_heads
    route, how = kernel_route(ctx, qkv.shape[0], None)
    if route != "reference" and not (H % 128 == 0 and D in (64, 128)):
        route, how = "reference", (
            f"hidden {H} / head_dim {D} (kernel needs hidden % 128 == 0 "
            f"and head_dim 64 or 128)")
    return route, how


def _saved(ctx, gop):
    """((Out, SoftmaxLse, dOut), None) of a grad op from the environment,
    or (None, why the saved backward cannot run)."""
    if getattr(ctx, "relowered", False):
        # double backward differentiates this lowering: the kernels have
        # no derivative of their own, the auto-grad formulation has
        return None, "lowered inside another op's vjp"
    names = [gop.single_input(s) for s in ("Out", _STAT, "Out@GRAD")]
    if names[1] is None:
        return None, f"the forward op has no {_STAT} output"
    vals = [ctx.env.get(n) for n in names]
    if any(v is None for v in vals):
        return None, f"Out, Out@GRAD or {_STAT} has no value"
    out, lse, g = vals
    return (out, lse, g.astype(out.dtype).reshape(out.shape)), None


def _write_grads(ctx, gop, grads):
    """Bind ``{forward input slot: gradient}`` to the grad op's outputs, in
    the dtype and shape of the forward input; one var in several slots
    gets their sum, and a var other ops read too accumulates, as in the
    auto-grad lowering."""
    total = {}
    for slot, val in grads.items():
        gname = gop.single_output(slot + "@GRAD")
        if not gname:
            continue
        x = ctx.get_input(gop, slot)
        val = val.astype(x.dtype).reshape(x.shape)
        total[gname] = total[gname] + val if gname in total else val
    for gname, val in total.items():
        if gname in ctx.env and gop.attr("__accumulate__", False):
            val = ctx.env[gname] + val
        ctx.env[gname] = val


def _attention_grad(saved_backward):
    """Lowering of an attention op's grad op.  ``saved_backward(ctx, gop)``
    runs the backward kernels off the forward's saved values and returns
    None, or returns why it cannot; then the auto-grad lowering does it.
    On a TPU backend that is a second forward kernel a layer, or the
    reference formulation where the kernels could run: say so, once per
    reason."""
    def lower(ctx, gop):
        import jax

        why = saved_backward(ctx, gop)
        if why is None:
            _GRAD["saved"].increase()
            return
        if not getattr(ctx, "relowered", False):
            _GRAD["relowered"].increase()
            if jax.default_backend() == "tpu" \
                    and why not in _downgrades_logged:
                _downgrades_logged.add(why)
                logger.warning(
                    "%s lowered as jax.vjp of the forward lowering, not "
                    "as the backward kernels off the forward's saved "
                    "output and statistic: %s", gop.type, why)
        _lower_auto_grad(ctx, gop)
    return lower


def _attention_grad_maker(fwd_op, block, helper):
    """The auto-grad desc without the statistic's cotangent: the statistic
    is a residual (stop_gradient), nothing ever produces that cotangent,
    and a grad op that names it as an input could not be differentiated
    again (double backward reads every input of the op it re-lowers)."""
    specs = build_auto_grad_specs(fwd_op, block, helper.no_grad_set)
    for spec in specs:
        spec["inputs"].pop(_STAT + "@GRAD", None)
    return specs


def _attn_infer(op, block):
    q = in_var(op, block, "Q")
    set_out(op, block, "Out", q.shape, q.dtype)
    # layout "bhsd": the only one the kernels, and so the statistic, have
    set_out(op, block, _STAT, q.shape[:3], "float32", stop_gradient=True)


@register_op("flash_attention", infer=_attn_infer,
             grad=_attention_grad_maker)
def _flash_attention(ctx, op):
    import jax

    from .pallas.flash_attention import (blockwise_attention,
                                         flash_attention_lse)
    from ..parallel.ring import ring_attention, ulysses_attention

    q = ctx.get_input(op, "Q")
    k = ctx.get_input(op, "K")
    v = ctx.get_input(op, "V")
    bias = _row_bias(ctx, op)
    causal = op.attr("causal", False)
    sm_scale = op.attr("scale", None)
    mode = op.attr("seq_parallel_mode", "ring")
    window = op.attr("window", None)
    if window is not None and (not causal or bias is not None):
        raise NotImplementedError(
            "flash_attention: a sliding window needs causal=True and "
            "no padding bias")
    # block-causal (block diffusion): j // mask_block <= i // mask_block
    mask_block = op.attr("mask_block", None)
    if mask_block is not None and (not causal or bias is not None
                                   or window is not None):
        raise NotImplementedError(
            "flash_attention: mask_block needs causal=True, no padding "
            "bias and no sliding window")
    blk = {} if mask_block is None else {"mask_block": int(mask_block)}
    # "highest": float32 operands reach both products whole, whatever
    # the mask; None is each route's default (the model's layer decides)
    precision = op.attr("precision", None)
    if precision is not None and bias is not None:
        raise NotImplementedError(
            "flash_attention: precision with a padding bias not "
            "supported (the biased kernel trains at the default)")
    prec = {} if precision is None else {"precision": str(precision)}

    if op.attr("impl", "auto") == "xla":
        if SP_AXIS in (getattr(ctx, "axis_names", ()) or ()):
            raise NotImplementedError(
                "flash_attention impl='xla' under sequence parallelism "
                "would attend over the local shard only; use impl='auto' "
                "(ring/Ulysses)")
        # einsum formulation: one op for the whole scores/softmax/PV
        # chain; layout "bshd" avoids materializing [B,h,S,d] transposes;
        # supports additive row bias, causal, and in-op probability
        # dropout (stateless key from the op's seed).  On v5e at S=128 it
        # measures within ~4% of the explicit-matmul build (763 vs 792
        # samples/s on the BERT bench) and well above the pallas kernel.
        import jax.numpy as jnp

        layout = op.attr("layout", "bhsd")
        d = q.shape[-1]
        scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
        eq = ("bqhd,bkhd->bhqk" if layout == "bshd"
              else "bhqd,bhkd->bhqk")
        # attr softmax_float32 (two-byte q, k, v of a serving program):
        # the scores are the product's float32 sum, the softmax float32,
        # the probabilities rounded where they enter the second product
        whole = {"preferred_element_type": jnp.float32} \
            if op.attr("softmax_float32", False) else {}
        s = jnp.einsum(eq, q, k, **prec, **whole) * scale
        if bias is not None:
            s = s + bias[:, None, None, :].astype(s.dtype)
        if causal:
            S = s.shape[-1]
            keep = jnp.tril(jnp.ones((S, S), bool))
            if mask_block is not None:
                blk_of = jnp.arange(S) // int(mask_block)
                keep = blk_of[:, None] >= blk_of[None, :]
            if window is not None:
                # i - window < j <= i: the window counts the token itself
                keep = keep & ~jnp.tril(jnp.ones((S, S), bool),
                                        -int(window))
            s = jnp.where(keep[None, None], s,
                          jnp.asarray(-1e30, s.dtype))
        p = jax.nn.softmax(s, axis=-1)
        prob = op.attr("dropout_prob", 0.0)
        if prob and not (ctx.is_test or op.attr("is_test", False)):
            keep = jax.random.bernoulli(ctx.rng(op), 1.0 - prob, p.shape)
            p = jnp.where(keep, p / (1.0 - prob), 0.0).astype(p.dtype)
        eo = ("bhqk,bkhd->bqhd" if layout == "bshd"
              else "bhqk,bhkd->bhqd")
        if whole:
            out = jnp.einsum(eo, p.astype(v.dtype), v, **prec,
                             **whole).astype(q.dtype)
        else:
            out = jnp.einsum(eo, p, v, **prec)
        _lowered("xla", window=window)
        ctx.set_output(op, "Out", out)
        _bind_statistic(ctx, op, q.shape[:3])
        return

    axes = getattr(ctx, "axis_names", ()) or ()
    if SP_AXIS in axes:
        if bias is not None or window is not None or blk or prec:
            raise NotImplementedError(
                "flash_attention: padding bias, a sliding window, a "
                "block-causal mask or a set precision under "
                "sequence parallelism not supported yet — pad-free "
                "bucketing or causal only")
        fn = ring_attention if mode == "ring" else ulysses_attention
        out = fn(q, k, v, SP_AXIS, causal=causal, sm_scale=sm_scale)
        _lowered("ring")
        ctx.set_output(op, "Out", out)
        _bind_statistic(ctx, op, q.shape[:3])
        return

    route, how = _split_route(ctx, op, q, k, v)
    lse = None
    if route == "reference":
        # CPU, or a mesh the operands do not divide over: the einsum
        # formulation, which the partitioner shards as it likes
        out, _ = blockwise_attention(q, k, v, causal=causal,
                                     sm_scale=sm_scale, bias=bias,
                                     window=window, **blk, **prec)
        _lowered("blockwise", how and f"flash_attention: {how}",
                 window=window)
    else:
        # one device or a manual context: the kernel as it is; a GSPMD
        # mesh: the kernel per shard (a bare pallas_call would pin the
        # layout; inside shard_map it sees its device's block)
        kw = dict(blk, **prec)
        if window is not None:
            kw["window"] = int(window)
        out, lse = call_kernel(
            ctx.mesh, how, flash_attention_lse,
            (q, k, v) + _if_bias(bias, bias),
            (_BHSD,) * 3 + _if_bias(bias, _BS), (_BHSD, _BHS),
            causal=causal, sm_scale=sm_scale, **kw)
        _lowered("pallas", window=window, sharded=how is not None)
    ctx.set_output(op, "Out", out)
    _bind_statistic(ctx, op, q.shape[:3], lse)


def _flash_attention_saved_backward(ctx, gop):
    q = ctx.get_input(gop, "Q")
    k = ctx.get_input(gop, "K")
    v = ctx.get_input(gop, "V")
    route = _split_route(ctx, gop, q, k, v)
    if route is None:
        return "the einsum or the ring / Ulysses branch"
    if route[0] == "reference":
        return route[1] or "not a TPU backend"
    if any(gop.attr(a, None) is not None
           for a in ("window", "mask_block", "precision")):
        return "window, mask_block and precision are forward only"
    saved, why = _saved(ctx, gop)
    if why:
        return why
    bias = _row_bias(ctx, gop)
    if bias is not None and route[1] and route[1][1] \
            and gop.single_output("Bias@GRAD"):
        # each mp shard holds its heads' part of dBias: the vjp through
        # shard_map sums them, the kernels called per shard would not
        return f"a gradient of Bias with heads split over {route[1][1]}"
    grads = call_kernel(
        ctx.mesh, route[1], _split_backward,
        (q, k, v) + saved + _if_bias(bias, bias),
        (_BHSD,) * 4 + (_BHS, _BHSD) + _if_bias(bias, _BS),
        (_BHSD,) * 3 + _if_bias(bias, _BS),
        causal=gop.attr("causal", False), sm_scale=gop.attr("scale", None))
    _write_grads(ctx, gop, dict(zip(("Q", "K", "V", "Bias"), grads)))
    return None


register_op("flash_attention_grad", infer=infer_auto_grad,
            lower=_attention_grad(_flash_attention_saved_backward))


def _attn_qkv_infer(op, block):
    qkv = in_var(op, block, "QKV")
    shape = list(qkv.shape)
    shape[-1] = shape[-1] // 3
    set_out(op, block, "Out", tuple(shape), qkv.dtype)
    set_out(op, block, _STAT, (shape[0], op.attr("num_heads"), shape[1]),
            "float32", stop_gradient=True)


@register_op("flash_attention_qkv", infer=_attn_qkv_infer,
             grad=_attention_grad_maker)
def _flash_attention_qkv(ctx, op):
    """Transpose-free fused attention on the packed QKV projection.

    QKV [B, S, 3H] -> Out [B, S, H].  On a TPU this lowers to
    the packed pallas kernels (ops/pallas/flash_attention.py:
    flash_attention_packed) whose grid reads 128-lane column chunks of
    the projection directly — none of the [B,S,3H] -> [3,B,h,S,d]
    transpose/slice traffic of the split-tensor path ever reaches HBM
    (measured ~2.4 GB/step of pure layout movement on the seq-512 BERT
    bench).  Under a GSPMD mesh the kernels run per batch shard through
    shard_map (``kernel_partition``).  On the CPU, and where the mesh has
    an ``mp`` axis (the packed columns are ``(3, h, d)``-ordered and do
    not split over it) or the batch does not divide, it lowers to an
    einsum formulation the partitioner can shard freely.

    Reference analog: operators/fused/multihead_matmul_op.cu takes the
    same packed [B, S, 3H] input (its "qkv weight" layout) — ours adds
    training (fwd+bwd) and long-sequence O(S) memory.
    """
    import jax.numpy as jnp

    from .pallas.flash_attention import flash_attention_packed_lse

    qkv = ctx.get_input(op, "QKV")
    bias = _row_bias(ctx, op)
    causal = op.attr("causal", False)
    sm_scale = op.attr("scale", None)
    nh = op.attr("num_heads")
    B, S, threeH = qkv.shape
    H = threeH // 3
    D = H // nh

    route, how = _packed_route(ctx, qkv, nh)
    lse = None
    if route != "reference":
        out, lse = call_kernel(
            ctx.mesh, how, flash_attention_packed_lse,
            (qkv,) + _if_bias(bias, bias), (_BSH,) + _if_bias(bias, _BS),
            (_BSH, _BHS), num_heads=nh, causal=causal, sm_scale=sm_scale)
        _lowered("pallas", sharded=how is not None)
    else:
        # fallback (CPU, or a mesh the packed form does not divide over):
        # blockwise online-softmax — keeps O(S) attention memory so
        # long-sequence mesh training doesn't regress to an [B,h,S,S]
        # materialization, and the einsum body is layout-free for the
        # partitioner
        from .pallas.flash_attention import blockwise_attention

        x = qkv.reshape(B, S, 3, nh, D)
        q = jnp.moveaxis(x[:, :, 0], 1, 2)               # [B,h,S,d]
        k = jnp.moveaxis(x[:, :, 1], 1, 2)
        v = jnp.moveaxis(x[:, :, 2], 1, 2)
        o, _ = blockwise_attention(q, k, v, causal=causal,
                                   sm_scale=sm_scale, bias=bias)
        out = jnp.moveaxis(o, 1, 2).reshape(B, S, H).astype(qkv.dtype)
        _lowered("blockwise", how and f"flash_attention_qkv: {how}")
    ctx.set_output(op, "Out", out)
    _bind_statistic(ctx, op, (B, nh, S), lse)


def _flash_attention_qkv_saved_backward(ctx, gop):
    qkv = ctx.get_input(gop, "QKV")
    nh = gop.attr("num_heads")
    route, how = _packed_route(ctx, qkv, nh)
    if route == "reference":
        return how or "not a TPU backend"
    saved, why = _saved(ctx, gop)
    if why:
        return why
    bias = _row_bias(ctx, gop)
    grads = call_kernel(
        ctx.mesh, how, _packed_backward,
        (qkv,) + saved + _if_bias(bias, bias),
        (_BSH, _BSH, _BHS, _BSH) + _if_bias(bias, _BS),
        (_BSH,) + _if_bias(bias, _BS),
        num_heads=nh, causal=gop.attr("causal", False),
        sm_scale=gop.attr("scale", None))
    _write_grads(ctx, gop, dict(zip(("QKV", "Bias"), grads)))
    return None


register_op("flash_attention_qkv_grad", infer=infer_auto_grad,
            lower=_attention_grad(_flash_attention_qkv_saved_backward))


# ---------------------------------------------------------------------------
# fused inference surfaces (reference operators/fused/) — on TPU these
# are plain compositions XLA fuses; the ops exist for API parity with
# the reference's pass-inserted fused kernels.
# ---------------------------------------------------------------------------
def _mm_infer(op, block):
    x = in_var(op, block, "Input")
    set_out(op, block, "Out", x.shape, x.dtype)


@register_op("multihead_matmul", infer=_mm_infer)
def _multihead_matmul(ctx, op):
    """Reference fused/multihead_matmul_op.cu: Input [B,S,D] projects to
    packed QKV via W [D,3,N,H] (+ Bias [3,N,H]), scaled dot-product
    attention with optional BiasQK added to the logits, heads merged
    back to [B,S,D]."""
    import jax
    import jax.numpy as jnp
    x = ctx.get_input(op, "Input")
    w = ctx.get_input(op, "W")
    bias = ctx.get_input(op, "Bias")
    n_head = int(op.attr("head_number"))
    alpha = float(op.attr("alpha", 1.0))
    B, S, D = x.shape
    H = D // n_head
    qkv = jnp.einsum("bsd,dknh->kbnsh", x.astype("float32"),
                     w.reshape(D, 3, n_head, H).astype("float32"))
    qkv = qkv + bias.reshape(3, 1, n_head, 1, H)
    q, k, v = qkv[0], qkv[1], qkv[2]
    logits = jnp.einsum("bnsh,bnth->bnst", q, k) * alpha
    if op.input("BiasQK"):
        logits = logits + ctx.get_input(op, "BiasQK").astype("float32")
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnst,bnth->bsnh", probs, v).reshape(B, S, D)
    ctx.set_output(op, "Out", out.astype(x.dtype))


def _skip_ln_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", x.shape, x.dtype)


@register_op("skip_layernorm", infer=_skip_ln_infer)
def _skip_layernorm(ctx, op):
    """out = LayerNorm(X + Y) (reference fused/skip_layernorm_op.cc)."""
    import jax.numpy as jnp
    x = ctx.get_input(op, "X")
    y = ctx.get_input(op, "Y")
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    eps = float(op.attr("epsilon", 1e-5))
    s = (x + y).astype("float32")
    mu = s.mean(-1, keepdims=True)
    var = ((s - mu) ** 2).mean(-1, keepdims=True)
    out = (s - mu) / jnp.sqrt(var + eps) * scale + bias
    ctx.set_output(op, "Out", out.astype(x.dtype))


def _feel_infer(op, block):
    ids0 = block.var(op.input("Ids")[0])
    emb0 = block.var(op.input("Embs")[0])
    set_out(op, block, "Out",
            (ids0.shape[0], ids0.shape[1], emb0.shape[1]), emb0.dtype)


@register_op("fused_embedding_eltwise_layernorm", infer=_feel_infer)
def _fused_embedding_eltwise_layernorm(ctx, op):
    """out = LayerNorm(sum_i Embs_i[Ids_i]) (reference
    fused/fused_embedding_eltwise_layernorm_op.cc)."""
    import jax.numpy as jnp
    ids = ctx.get_inputs(op, "Ids")
    embs = ctx.get_inputs(op, "Embs")
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    eps = float(op.attr("epsilon", 1e-5))
    s = None
    for i, e in zip(ids, embs):
        idx = i.reshape(i.shape[:2]).astype("int32")
        g = e[idx].astype("float32")
        s = g if s is None else s + g
    mu = s.mean(-1, keepdims=True)
    var = ((s - mu) ** 2).mean(-1, keepdims=True)
    out = (s - mu) / jnp.sqrt(var + eps) * scale + bias
    ctx.set_output(op, "Out", out.astype(embs[0].dtype))
