"""Neural-network op lowerings: conv / pool / norm / softmax / losses.

Replaces the reference's cuDNN-backed kernels (operators/conv_op.*,
conv_cudnn_op.cu, pool_op.*, batch_norm_op.*, layer_norm_op.*,
softmax_op.*, softmax_with_cross_entropy_op.*, cross_entropy_op.*,
dropout_op.*, operators/math/softmax.*) with lax/jnp lowerings: convs map
onto the MXU via lax.conv_general_dilated, pooling via lax.reduce_window,
and XLA fuses the pointwise epilogues.
"""
from __future__ import annotations

import functools
import logging

import numpy as np

from ..framework.core import Block, Operator, dtype_to_np
from ..monitor import monitor as _monitor
from .registry import (LowerContext, in_var, register_op, same_as_input,
                       set_out)


logger = logging.getLogger("paddle_tpu.ops.nn")


def _jnp():
    import jax.numpy as jnp
    return jnp


def _lax():
    import jax.lax as lax
    return lax


def _conv_precision(dtype):
    """f32 convs at full precision on TPU (DEFAULT would truncate operands
    to bf16 on the MXU); CPU's DEFAULT is already full f32."""
    import jax
    import jax.numpy as jnp
    if dtype in (jnp.bfloat16, np.float16):
        return None
    if jax.default_backend() == "cpu":
        return None
    return jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# softmax & friends
# ---------------------------------------------------------------------------

@register_op("softmax", infer=same_as_input())
def _softmax(ctx, op):
    import jax
    ctx.set_output(op, "Out",
                   jax.nn.softmax(ctx.get_input(op, "X"),
                                  axis=op.attr("axis", -1)))


@register_op("log_softmax", infer=same_as_input())
def _log_softmax(ctx, op):
    import jax
    ctx.set_output(op, "Out",
                   jax.nn.log_softmax(ctx.get_input(op, "X"),
                                      axis=op.attr("axis", -1)))


def _ce_infer(op: Operator, block: Block):
    x = in_var(op, block, "X")
    label = in_var(op, block, "Label")
    soft = op.attr("soft_label", False)
    out = list(label.shape if not soft else x.shape[:-1] + (1,))
    if not soft and (not out or out[-1] != 1):
        out = list(x.shape[:-1]) + [1]
    set_out(op, block, "Y", out, x.dtype)


@register_op("cross_entropy", infer=_ce_infer)
def _cross_entropy(ctx: LowerContext, op: Operator):
    """-log(p[label]); input X is already a probability distribution
    (reference operators/cross_entropy_op.h)."""
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    label = ctx.get_input(op, "Label")
    eps = 1e-12
    if op.attr("soft_label", False):
        y = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        if jnp.ndim(label) == jnp.ndim(x):
            label = jnp.squeeze(label, -1)
        p = jnp.take_along_axis(x, label[..., None].astype("int32"), axis=-1)
        ignore = op.attr("ignore_index", -100)
        y = -jnp.log(p + eps)
        if ignore >= 0:
            y = jnp.where(label[..., None] == ignore, 0.0, y)
    ctx.set_output(op, "Y", y)


def _swce_infer(op, block):
    x = in_var(op, block, "Logits")
    label = in_var(op, block, "Label")
    axis = op.attr("axis", -1) % len(x.shape)
    loss = list(x.shape)
    loss[axis] = 1
    set_out(op, block, "Softmax", x.shape, x.dtype)
    set_out(op, block, "Loss", loss, x.dtype)


@register_op("softmax_with_cross_entropy", infer=_swce_infer)
def _softmax_with_cross_entropy(ctx, op):
    """Logsumexp formulation: loss = lse(logits) - logit[label].

    Deliberately NOT log_softmax-then-gather — that materializes the
    full [N, V] log-prob tensor in HBM (297 MB for the BERT MLM head at
    batch 128, V=30522; profiled at ~5% of the train step as
    'data formatting' copies). Here the forward writes only [N, 1]
    reductions; the Softmax output is a pure elementwise of logits that
    XLA fuses into its consumer or DCEs when unused, and the vjp's
    softmax-minus-onehot recomputes from logits inside the backward
    matmul fusion."""
    import jax

    jnp = _jnp()
    logits = ctx.get_input(op, "Logits")
    label = ctx.get_input(op, "Label")
    axis = op.attr("axis", -1) % jnp.ndim(logits)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=axis, keepdims=True))
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m), axis=axis,
                              keepdims=True))
    softmax = jnp.exp(logits - lse)
    if op.attr("soft_label", False):
        # sum(label * (lse - logits)) — no [N,V] log-prob intermediate
        loss = jnp.sum(label * (lse - logits), axis=axis, keepdims=True)
    else:
        lab = label
        if jnp.ndim(lab) == jnp.ndim(logits):
            lab = jnp.squeeze(lab, axis)
        picked = jnp.take_along_axis(
            logits, jnp.expand_dims(lab.astype("int32"), axis),
            axis=axis)
        loss = lse - picked
        ignore = op.attr("ignore_index", -100)
        if ignore >= 0:
            loss = jnp.where(
                jnp.expand_dims(lab, axis) == ignore, 0.0, loss)
    ctx.set_output(op, "Softmax", softmax)
    ctx.set_output(op, "Loss", loss)


@register_op("sigmoid_cross_entropy_with_logits", infer=same_as_input())
def _sigmoid_ce(ctx, op):
    import jax
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    label = ctx.get_input(op, "Label")
    loss = jnp.maximum(x, 0) - x * label + jax.nn.softplus(-jnp.abs(x))
    ignore = op.attr("ignore_index", -100)
    if ignore >= 0:
        loss = jnp.where(label == ignore, 0.0, loss)
    if op.attr("normalize", False):
        n = jnp.maximum(jnp.sum((label != ignore).astype(x.dtype)), 1.0)
        loss = loss / n
    ctx.set_output(op, "Out", loss)


@register_op("bce_loss", infer=same_as_input())
def _bce_loss(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    label = ctx.get_input(op, "Label")
    eps = 1e-12
    out = -(label * jnp.log(x + eps) + (1 - label) * jnp.log(1 - x + eps))
    ctx.set_output(op, "Out", out)


def _loss_reduce_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", [], x.dtype)


@register_op("squared_l2_norm", infer=_loss_reduce_infer)
def _squared_l2_norm(ctx, op):
    x = ctx.get_input(op, "X")
    ctx.set_output(op, "Out", _jnp().sum(x * x))


@register_op("huber_loss", infer=lambda op, block: (
    set_out(op, block, "Out", in_var(op, block, "X").shape,
            in_var(op, block, "X").dtype),
    set_out(op, block, "Residual", in_var(op, block, "X").shape,
            in_var(op, block, "X").dtype)))
def _huber_loss(ctx, op):
    jnp = _jnp()
    x, y = ctx.get_input(op, "X"), ctx.get_input(op, "Y")
    d = op.attr("delta", 1.0)
    r = y - x
    out = jnp.where(jnp.abs(r) <= d, 0.5 * r * r,
                    d * (jnp.abs(r) - 0.5 * d))
    ctx.set_output(op, "Residual", r)
    ctx.set_output(op, "Out", out)


@register_op("smooth_l1_loss", infer=lambda op, block: (
    set_out(op, block, "Diff", in_var(op, block, "X").shape,
            in_var(op, block, "X").dtype),
    set_out(op, block, "Out",
            list(in_var(op, block, "X").shape[:1]) + [1],
            in_var(op, block, "X").dtype)))
def _smooth_l1(ctx, op):
    jnp = _jnp()
    x, y = ctx.get_input(op, "X"), ctx.get_input(op, "Y")
    sigma = op.attr("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    val = jnp.where(jnp.abs(d) < 1.0 / s2, 0.5 * d * d * s2,
                    jnp.abs(d) - 0.5 / s2)
    ctx.set_output(op, "Diff", d)
    ctx.set_output(op, "Out",
                   jnp.sum(val.reshape(val.shape[0], -1), -1, keepdims=True))


@register_op("mse_loss", infer=same_as_input())
def _mse(ctx, op):
    x, y = ctx.get_input(op, "X"), ctx.get_input(op, "Y")
    ctx.set_output(op, "Out", (x - y) ** 2)


def _kldiv_infer(op, block):
    x = in_var(op, block, "X")
    red = op.attrs.get("reduction", "mean")
    shape = x.shape if red == "none" else ()
    set_out(op, block, "Loss", shape, x.dtype)


@register_op("kldiv_loss", infer=_kldiv_infer)
def _kldiv(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    target = ctx.get_input(op, "Target")
    loss = jnp.where(target > 0, target * (jnp.log(target) - x), 0.0)
    red = op.attr("reduction", "mean")
    if red == "mean":
        loss = jnp.mean(loss)
    elif red == "sum":
        loss = jnp.sum(loss)
    elif red == "batchmean":
        loss = jnp.sum(loss) / jnp.shape(x)[0]
    ctx.set_output(op, "Loss", loss)


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------

def _dropout_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", x.shape, x.dtype)
    if op.output("Mask"):
        set_out(op, block, "Mask", x.shape, "uint8")


# sites lowered per program build, like the attention_lowered_* stats:
# onto XLA's RngBitGenerator, or onto threefry where a backend has none
_DROPOUT_LOWERED = {
    "hw_bits": _monitor.get("dropout_lowered_hw_bits"),
    "threefry": _monitor.get("dropout_lowered_threefry"),
}
# platforms whose XLA backend lowers RngBitGenerator
_BIT_GENERATOR_BACKENDS = ("tpu", "cpu", "gpu", "cuda", "rocm")
_dropout_logged = set()


def _log_once(msg, reason):
    if reason not in _dropout_logged:
        _dropout_logged.add(reason)
        logger.warning(msg, reason)


def _draw_mask_bits(key, *, shape, axes=()):
    """uint8 bits of ``shape`` from XLA's RngBitGenerator (JAX's ``rbg``),
    keyed by the key ``ctx.rng(op)`` hands the op: its key data, widened
    to the four words the generator's key holds the way JAX's own ``rbg``
    seed widens threefry's two (twice over), so the step, the program seed
    and ``__op_seed__`` decide the mask, and a process that sets
    ``jax_default_prng_impl`` to ``rbg`` draws the same masks.  Under
    ``shard_map`` (``axes``: the mesh axes dim 0 of ``shape`` is split
    over) a shard draws its block, with its index folded into the key.

    Bytes are drawn as bytes.  One site of ``[64, 512, 768]`` on a v5e
    (``tools/dropout_microbench.py``, my chip run, PR 33): the draw, the
    compare and the select over a bf16 input take 0.209 ms as ``u8``,
    0.567 ms from threefry, and 0.572 / 0.567 / 0.247 ms as a quarter as
    many ``u32`` bitcast to bytes (drawn flat, along the last dim, along
    the first: the bitcast re-tiles), beside 0.232 ms for one elementwise
    pass over the input alone; five chained sites 0.30 ms against 2.62
    (threefry) and 1.77-2.39 (words).
    """
    import jax

    if axes:
        key = jax.random.fold_in(key, jax.lax.axis_index(axes))
        shape = (shape[0] // jax.lax.axis_size(axes),) + shape[1:]
    words = _jnp().resize(jax.random.key_data(key).ravel(), 4)
    return jax.lax.rng_bit_generator(words, shape, dtype="uint8")[1]


def _mask_route(ctx, shape):
    """Where a site's bits are drawn, from the mesh the op is lowered in:
    ``None`` on a backend without a bit generator (threefry), else
    ``(mesh, how)`` for ``attention_ops.call_kernel``.  XLA's partitioner
    does not split an RngBitGenerator: under a GSPMD mesh every device
    would draw the global array and slice it (the compiled dp4 module,
    PR 33), so there the draw runs per ``dp`` shard through ``shard_map``,
    as the attention kernels do."""
    import jax

    from .attention_ops import kernel_partition

    backend = jax.default_backend()
    if backend not in _BIT_GENERATOR_BACKENDS:
        _log_once("dropout draws its mask bits with threefry: %s",
                  f"backend {backend!r} is not known to lower "
                  f"RngBitGenerator")
        return None
    route, how = "direct", None
    if shape:
        route, how = kernel_partition(
            ctx.mesh.shape if ctx.mesh is not None else {},
            getattr(ctx, "axis_names", ()) or (), shape[0], ())
    if route == "reference":
        _log_once("dropout draws each site's whole mask on every device "
                  "of the mesh: %s", how)
        how = None
    return ctx.mesh, how


def _dropout_keep(key, shape, thresh, route):
    """The keep mask of one site: uint8 bits >= ``thresh`` (keep
    probability 1 - thresh/256).  ``route``: ``_mask_route``'s value,
    hashable, so it rides ``_remat_dropout`` as a static argument and the
    backward regenerates the forward's bits."""
    import jax

    from .attention_ops import call_kernel

    if route is None:
        bits = jax.random.bits(key, shape, "uint8")
    else:
        mesh, how = route
        bits = call_kernel(mesh, how, _draw_mask_bits, (key,), ((),),
                           ("batch",) + (None,) * (len(shape) - 1),
                           shape=tuple(shape), axes=how[0] if how else ())
    return bits >= _jnp().uint8(thresh)


_REMAT_DROPOUT = None


def _remat_dropout():
    """Dropout whose backward REGENERATES the keep mask from the
    stateless key instead of saving it as a residual.

    The op saves just the key (a few bytes) and no ``[*x.shape]`` mask, so
    the forward select stays free to fuse into its producer.  What XLA
    makes of the two equal draws is its own choice: in the compiled
    BERT-base step (25 sites, v5e, PR 33) it merges them, 25
    ``rng-bit-generator`` of ``u8[64,512,768]`` whose 25 MB masks live
    from forward to backward, as it merged the threefry draws before.

    Built lazily on first dropout lowering so module import stays
    jax-free (the ops package convention).
    """
    global _REMAT_DROPOUT
    if _REMAT_DROPOUT is None:
        import jax
        jnp = _jnp()

        @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
        def fn(x, key, thresh, scale, route):
            keep = _dropout_keep(key, jnp.shape(x), thresh, route)
            return jnp.where(keep, x * scale if scale != 1.0 else x,
                             0.0).astype(x.dtype)

        def fwd(x, key, thresh, scale, route):
            return fn(x, key, thresh, scale, route), key

        def bwd(thresh, scale, route, key, g):
            keep = _dropout_keep(key, jnp.shape(g), thresh, route)
            dx = jnp.where(keep, g * scale if scale != 1.0 else g, 0.0)
            return dx.astype(g.dtype), None

        fn.defvjp(fwd, bwd)
        _REMAT_DROPOUT = fn
    return _REMAT_DROPOUT


@register_op("dropout", infer=_dropout_infer)
def _dropout(ctx: LowerContext, op: Operator):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    p = op.attr("dropout_prob", 0.5)
    impl = op.attr("dropout_implementation", "downgrade_in_infer")
    is_test = op.attr("is_test", False) or ctx.is_test
    if is_test:
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        ctx.set_output(op, "Out", out)
        if op.output("Mask"):
            ctx.set_output(op, "Mask",
                           jnp.ones(jnp.shape(x), dtype="uint8"))
        return
    # NOTE(perf): the bits are XLA's RngBitGenerator, not a Pallas kernel
    # with an in-kernel generator: XLA fuses the compare and select into
    # the surrounding elementwise chains, which a pallas_call boundary
    # would cut (a fused-dropout kernel: not measured since the ledger
    # began).  BERT-base at 64 x 512 on a v5e, 25 sites (my chip runs,
    # PR 33): step 222.9 ms with threefry bits, 195.4 ms with these.
    scale = (0.0 if p >= 1.0 else 1.0 / (1.0 - p)) \
        if impl == "upscale_in_train" else 1.0
    # raw-bits threshold instead of bernoulli: same keep distribution
    # (uniform bits >= p*2^n has probability ~1-p) without bernoulli's
    # bits->float conversion pass; keep-probability granularity 1/256 (p
    # quantized by <0.4%, irrelevant for regularization)
    if p >= 255.5 / 256.0:  # not representable in u8 granularity: drop all
        keep = jnp.zeros(jnp.shape(x), bool)
        out = jnp.where(keep, x, 0.0).astype(x.dtype)
        ctx.set_output(op, "Out", out)
        if op.output("Mask"):
            ctx.set_output(op, "Mask", keep.astype("uint8"))
        return
    thresh = round(max(p, 0.0) * 256.0)
    route = _mask_route(ctx, jnp.shape(x))
    if not getattr(ctx, "relowered", False):
        # a site once: its re-lowering inside the auto-grad op is the
        # same draw
        _DROPOUT_LOWERED["threefry" if route is None
                         else "hw_bits"].increase()
    if op.output("Mask"):
        # mask requested (reference-compat Mask output): materialize it
        keep = _dropout_keep(ctx.rng(op), jnp.shape(x), thresh, route)
        out = jnp.where(keep, x * scale if scale != 1.0 else x,
                        0.0).astype(x.dtype)
        ctx.set_output(op, "Out", out)
        ctx.set_output(op, "Mask", keep.astype("uint8"))
        return
    ctx.set_output(op, "Out",
                   _remat_dropout()(x, ctx.rng(op), thresh, scale, route))


# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------

def _conv_out_dim(i, k, pad0, pad1, stride, dil):
    if i == -1:
        return -1
    ke = (k - 1) * dil + 1
    return (i + pad0 + pad1 - ke) // stride + 1


def _resolve_padding(op, spatial, ksize, strides, dils):
    pad = op.attr("paddings", [0] * len(spatial))
    algo = op.attr("padding_algorithm", "EXPLICIT")
    n = len(spatial)
    if algo == "VALID":
        return [(0, 0)] * n
    if algo == "SAME":
        pairs = []
        for i in range(n):
            out = -(-spatial[i] // strides[i]) if spatial[i] != -1 else 1
            ke = (ksize[i] - 1) * dils[i] + 1
            total = max((out - 1) * strides[i] + ke - spatial[i], 0)
            pairs.append((total // 2, total - total // 2))
        return pairs
    if len(pad) == n:
        return [(p, p) for p in pad]
    if len(pad) == 2 * n:
        return [(pad[2 * i], pad[2 * i + 1]) for i in range(n)]
    return [(0, 0)] * n


def _conv2d_infer(op: Operator, block: Block):
    x = in_var(op, block, "Input")
    w = in_var(op, block, "Filter")
    fmt = op.attr("data_format", "NCHW")
    strides = op.attr("strides", [1, 1])
    dils = op.attr("dilations", [1, 1])
    if fmt in ("NCHW", "AnyLayout"):
        n, c, h, wd = x.shape
    else:
        n, h, wd, c = x.shape
    kh, kw = w.shape[2], w.shape[3]
    pads = _resolve_padding(op, [h, wd], [kh, kw], strides, dils)
    oh = _conv_out_dim(h, kh, pads[0][0], pads[0][1], strides[0], dils[0])
    ow = _conv_out_dim(wd, kw, pads[1][0], pads[1][1], strides[1], dils[1])
    oc = w.shape[0]
    out = [n, oc, oh, ow] if fmt in ("NCHW", "AnyLayout") else [n, oh, ow, oc]
    set_out(op, block, "Output", out, x.dtype)


def _conv2d_lower(ctx: LowerContext, op: Operator):
    lax = _lax()
    jnp = _jnp()
    x = ctx.get_input(op, "Input")
    w = ctx.get_input(op, "Filter")  # OIHW, as in the reference
    fmt = op.attr("data_format", "NCHW")
    if fmt == "AnyLayout":
        fmt = "NCHW"
    strides = tuple(op.attr("strides", [1, 1]))
    dils = tuple(op.attr("dilations", [1, 1]))
    groups = op.attr("groups", 1)
    if fmt == "NCHW":
        spatial = jnp.shape(x)[2:]
        dn = lax.conv_dimension_numbers(jnp.shape(x), jnp.shape(w),
                                        ("NCHW", "OIHW", "NCHW"))
    else:
        spatial = jnp.shape(x)[1:3]
        dn = lax.conv_dimension_numbers(jnp.shape(x), jnp.shape(w),
                                        ("NHWC", "OIHW", "NHWC"))
    pads = _resolve_padding(op, list(spatial),
                            [jnp.shape(w)[2], jnp.shape(w)[3]], strides, dils)
    # no preferred_element_type=f32 here: the result is rounded straight
    # back to x.dtype anyway (numerically identical — XLA's TPU conv
    # accumulates low-precision operands in f32 internally)
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pads, rhs_dilation=dils,
        dimension_numbers=dn, feature_group_count=groups,
        precision=_conv_precision(x.dtype))
    ctx.set_output(op, "Output", out.astype(x.dtype))


register_op("conv2d", infer=_conv2d_infer, lower=_conv2d_lower)
register_op("depthwise_conv2d", infer=_conv2d_infer, lower=_conv2d_lower)


def _conv2d_transpose_infer(op, block):
    x = in_var(op, block, "Input")
    w = in_var(op, block, "Filter")  # [in_c, out_c/groups, kh, kw]
    strides = op.attr("strides", [1, 1])
    dils = op.attr("dilations", [1, 1])
    pad = op.attr("paddings", [0, 0])
    groups = op.attr("groups", 1)
    fmt = op.attr("data_format", "NCHW")
    n, c, h, wd = x.shape if fmt == "NCHW" else (
        x.shape[0], x.shape[3], x.shape[1], x.shape[2])
    kh, kw = w.shape[2], w.shape[3]
    pads = _resolve_padding(op, [h, wd], [kh, kw], strides, dils)
    oh = (h - 1) * strides[0] - pads[0][0] - pads[0][1] + (kh - 1) * dils[0] + 1
    ow = (wd - 1) * strides[1] - pads[1][0] - pads[1][1] + (kw - 1) * dils[1] + 1
    oc = w.shape[1] * groups
    out_size = op.attr("output_size", [])
    if out_size:
        oh, ow = out_size
    out = [n, oc, oh, ow] if fmt == "NCHW" else [n, oh, ow, oc]
    set_out(op, block, "Output", out, x.dtype)


# depthwise flavor shares the lowering: groups come from the attr
# (reference conv_transpose_op.cc registers both names over one kernel)
@register_op("depthwise_conv2d_transpose",
             infer=_conv2d_transpose_infer)
@register_op("conv2d_transpose", infer=_conv2d_transpose_infer)
def _conv2d_transpose_lower(ctx, op):
    """Gradient-of-conv formulation (same as conv3d_transpose): dilate
    the input by the stride, flip the kernel, pad with k_eff-1-p per
    side. Round-5 fix: the previous lax.conv_transpose call passed the
    FORWARD pads as literal pads on the dilated input, which silently
    shrank outputs for stride>1 or p != (k-1)/2 (stride-1 SAME-style
    configs happened to coincide, which is why it survived). Groups
    (incl. depthwise_conv2d_transpose) via feature_group_count."""
    lax = _lax()
    jnp = _jnp()
    x = ctx.get_input(op, "Input")
    w = ctx.get_input(op, "Filter")  # IOHW [Cin, Cout/g, kh, kw]
    strides = tuple(op.attr("strides", [1, 1]))
    dils = tuple(op.attr("dilations", [1, 1]))
    fmt = op.attr("data_format", "NCHW")
    g = int(op.attr("groups", 1))
    ch_axis = 1 if fmt == "NCHW" else 3
    spatial = (jnp.shape(x)[2:] if fmt == "NCHW"
               else jnp.shape(x)[1:3])
    kh, kw = jnp.shape(w)[2], jnp.shape(w)[3]
    pads_f = _resolve_padding(op, list(spatial), [kh, kw], strides,
                              dils)
    ke = [(k - 1) * d + 1 for k, d in zip((kh, kw), dils)]
    default_out = [
        (spatial[i] - 1) * strides[i] - pads_f[i][0] - pads_f[i][1]
        + ke[i] for i in range(2)]
    out_size = op.attr("output_size", []) or default_out
    pads = [(ke[i] - 1 - pads_f[i][0],
             ke[i] - 1 - pads_f[i][1]
             + int(out_size[i]) - default_out[i]) for i in range(2)]
    cin = jnp.shape(x)[ch_axis]
    wt = jnp.flip(w, axis=(2, 3))
    # IOHW -> OIHW with group-major output channels (paddle layout)
    wt = wt.reshape(g, cin // g, -1, kh, kw)
    wt = wt.transpose(0, 2, 1, 3, 4).reshape(-1, cin // g, kh, kw)
    dn = (("NCHW", "OIHW", "NCHW") if fmt == "NCHW"
          else ("NHWC", "OIHW", "NHWC"))
    out = lax.conv_general_dilated(
        x, wt.astype(x.dtype), window_strides=(1, 1), padding=pads,
        lhs_dilation=strides, rhs_dilation=dils,
        dimension_numbers=dn, feature_group_count=g,
        precision=_conv_precision(x.dtype))
    ctx.set_output(op, "Output", out.astype(x.dtype))


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

def _pool2d_infer(op: Operator, block: Block):
    x = in_var(op, block, "X")
    fmt = op.attr("data_format", "NCHW")
    n, c, h, w = x.shape if fmt == "NCHW" else (
        x.shape[0], x.shape[3], x.shape[1], x.shape[2])
    if op.attr("global_pooling", False):
        oh = ow = 1
    elif op.attr("adaptive", False):
        oh, ow = op.attr("ksize", [1, 1])
    else:
        ks = op.attr("ksize", [1, 1])
        strides = op.attr("strides", [1, 1])
        pads = _resolve_padding(op, [h, w], ks, strides, [1, 1])
        ceil = op.attr("ceil_mode", False)
        def _od(i, k, p0, p1, s):
            if i == -1:
                return -1
            num = i + p0 + p1 - k
            return (num + s - 1) // s + 1 if ceil else num // s + 1
        oh = _od(h, ks[0], pads[0][0], pads[0][1], strides[0])
        ow = _od(w, ks[1], pads[1][0], pads[1][1], strides[1])
    out = [n, c, oh, ow] if fmt == "NCHW" else [n, oh, ow, c]
    set_out(op, block, "Out", out, x.dtype)


@register_op("pool2d", infer=_pool2d_infer)
def _pool2d(ctx: LowerContext, op: Operator):
    lax = _lax()
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    fmt = op.attr("data_format", "NCHW")
    ptype = op.attr("pooling_type", "max")
    sdims = (2, 3) if fmt == "NCHW" else (1, 2)
    shape = jnp.shape(x)
    if op.attr("global_pooling", False) or (
            op.attr("adaptive", False) and op.attr("ksize") == [1, 1]):
        red = jnp.max if ptype == "max" else jnp.mean
        ctx.set_output(op, "Out", red(x, axis=sdims, keepdims=True))
        return
    if op.attr("adaptive", False):
        oh, ow = op.attr("ksize")
        h, w = shape[sdims[0]], shape[sdims[1]]
        assert h % oh == 0 and w % ow == 0, \
            "adaptive pool needs divisible sizes under static shapes"
        ks = [h // oh, w // ow]
        strides = ks
        pads = [(0, 0), (0, 0)]
    else:
        ks = op.attr("ksize", [1, 1])
        strides = op.attr("strides", [1, 1])
        pads = _resolve_padding(op, [shape[sdims[0]], shape[sdims[1]]],
                                ks, strides, [1, 1])
    window = [1] * len(shape)
    wstrides = [1] * len(shape)
    padding = [(0, 0)] * len(shape)
    for i, d in enumerate(sdims):
        window[d] = ks[i]
        wstrides[d] = strides[i]
        padding[d] = pads[i]
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, wstrides, padding)
    else:
        summed = lax.reduce_window(x, 0.0, lax.add,
                                   window, wstrides, padding)
        if op.attr("exclusive", True) and any(p != (0, 0) for p in padding):
            ones = jnp.ones_like(x)
            counts = lax.reduce_window(ones, 0.0, lax.add,
                                       window, wstrides, padding)
            out = summed / counts
        else:
            out = summed / float(np.prod(ks))
    ctx.set_output(op, "Out", out.astype(x.dtype))


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def _bn_infer(op: Operator, block: Block):
    x = in_var(op, block, "X")
    c_axis = 1 if op.attr("data_layout", "NCHW") == "NCHW" else len(x.shape) - 1
    c = x.shape[c_axis]
    set_out(op, block, "Y", x.shape, x.dtype)
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        if op.output(slot):
            set_out(op, block, slot, [c], "float32")


def _bn_lower(ctx: LowerContext, op: Operator):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    mean = ctx.get_input(op, "Mean")
    var = ctx.get_input(op, "Variance")
    eps = op.attr("epsilon", 1e-5)
    momentum = op.attr("momentum", 0.9)
    layout = op.attr("data_layout", "NCHW")
    is_test = op.attr("is_test", False) or ctx.is_test
    use_global = op.attr("use_global_stats", False) or is_test

    nd = jnp.ndim(x)
    c_axis = 1 if layout == "NCHW" else nd - 1
    red_axes = tuple(i for i in range(nd) if i != c_axis)
    bshape = [1] * nd
    bshape[c_axis] = jnp.shape(x)[c_axis]

    xf = x.astype("float32")
    if use_global:
        use_mean, use_var = mean, var
        new_mean, new_var = mean, var
        saved_mean = mean
        saved_var = 1.0 / jnp.sqrt(var + eps)
    else:
        bmean = jnp.mean(xf, axis=red_axes)
        bvar = jnp.mean((xf - bmean.reshape(bshape)) ** 2, axis=red_axes)
        use_mean, use_var = bmean, bvar
        new_mean = momentum * mean + (1 - momentum) * bmean
        new_var = momentum * var + (1 - momentum) * bvar
        saved_mean = bmean
        saved_var = 1.0 / jnp.sqrt(bvar + eps)

    inv = 1.0 / jnp.sqrt(use_var + eps)
    y = (xf - use_mean.reshape(bshape)) * inv.reshape(bshape)
    y = y * scale.reshape(bshape) + bias.reshape(bshape)
    ctx.set_output(op, "Y", y.astype(x.dtype))
    ctx.set_output(op, "MeanOut", new_mean)
    ctx.set_output(op, "VarianceOut", new_var)
    ctx.set_output(op, "SavedMean", saved_mean)
    ctx.set_output(op, "SavedVariance", saved_var)


def _bn_grad_maker(fwd_op, block, helper):
    """batch_norm Y depends on X/Scale/Bias only (stats are derived), so the
    auto-vjp grad is correct -- but MeanOut/VarianceOut alias their inputs
    and must be excluded from re-lowering state.  We keep auto grads and let
    the executor's SSA env ordering handle aliasing (grad ops are emitted
    before any later state write)."""
    from .registry import build_auto_grad_specs
    specs = build_auto_grad_specs(fwd_op, block, helper.no_grad_set)
    for s in specs:
        # Mean/Variance inputs are running stats: never differentiable.
        s["outputs"].pop("Mean@GRAD", None)
        s["outputs"].pop("Variance@GRAD", None)
    return specs


register_op("batch_norm", infer=_bn_infer, lower=_bn_lower,
            grad=_bn_grad_maker,
            stateful_outputs=("MeanOut", "VarianceOut"))


def _sync_bn_lower(ctx: LowerContext, op: Operator):
    """Cross-replica batch norm (reference sync_batch_norm_op.cu:31:
    NCCL allreduce of per-device sum/sum-of-squares). On TPU the stats
    ride one lax.pmean pair over the dp axis inside shard_map — cheap
    on ICI — and the grad falls out of the auto-vjp (pmean has a
    defined transpose). Without a bound axis it degrades to plain BN
    (single participant), matching the reference's 1-GPU behavior."""
    import jax.lax as lax
    jnp = _jnp()
    from .collective_ops import _axis_name
    axis = _axis_name(ctx, op)
    x = ctx.get_input(op, "X")
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    mean = ctx.get_input(op, "Mean")
    var = ctx.get_input(op, "Variance")
    eps = op.attr("epsilon", 1e-5)
    momentum = op.attr("momentum", 0.9)
    layout = op.attr("data_layout", "NCHW")
    is_test = op.attr("is_test", False) or ctx.is_test
    use_global = op.attr("use_global_stats", False) or is_test

    nd = jnp.ndim(x)
    c_axis = 1 if layout == "NCHW" else nd - 1
    red_axes = tuple(i for i in range(nd) if i != c_axis)
    bshape = [1] * nd
    bshape[c_axis] = jnp.shape(x)[c_axis]

    xf = x.astype("float32")
    if use_global:
        use_mean, use_var = mean, var
        new_mean, new_var = mean, var
        saved_mean = mean
        saved_var = 1.0 / jnp.sqrt(var + eps)
    else:
        m1 = jnp.mean(xf, axis=red_axes)
        m2 = jnp.mean(xf * xf, axis=red_axes)
        if axis is not None:
            m1 = lax.pmean(m1, axis)
            m2 = lax.pmean(m2, axis)
        bmean = m1
        bvar = jnp.maximum(m2 - m1 * m1, 0.0)
        use_mean, use_var = bmean, bvar
        new_mean = momentum * mean + (1 - momentum) * bmean
        new_var = momentum * var + (1 - momentum) * bvar
        saved_mean = bmean
        saved_var = 1.0 / jnp.sqrt(bvar + eps)

    inv = 1.0 / jnp.sqrt(use_var + eps)
    y = (xf - use_mean.reshape(bshape)) * inv.reshape(bshape)
    y = y * scale.reshape(bshape) + bias.reshape(bshape)
    ctx.set_output(op, "Y", y.astype(x.dtype))
    ctx.set_output(op, "MeanOut", new_mean)
    ctx.set_output(op, "VarianceOut", new_var)
    ctx.set_output(op, "SavedMean", saved_mean)
    ctx.set_output(op, "SavedVariance", saved_var)


register_op("sync_batch_norm", infer=_bn_infer, lower=_sync_bn_lower,
            grad=_bn_grad_maker,
            stateful_outputs=("MeanOut", "VarianceOut"))


def _ln_infer(op, block):
    x = in_var(op, block, "X")
    axis = op.attr("begin_norm_axis", 1)
    rows = int(np.prod([s for s in x.shape[:axis]])) \
        if -1 not in x.shape[:axis] else -1
    set_out(op, block, "Y", x.shape, x.dtype)
    if op.output("Mean"):
        set_out(op, block, "Mean", [rows], "float32")
    if op.output("Variance"):
        set_out(op, block, "Variance", [rows], "float32")


@register_op("layer_norm", infer=_ln_infer)
def _layer_norm(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    eps = op.attr("epsilon", 1e-5)
    axis = op.attr("begin_norm_axis", 1)
    shape = jnp.shape(x)
    red = tuple(range(axis, len(shape)))
    xf = x.astype("float32")
    mean = jnp.mean(xf, axis=red, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=red, keepdims=True)
    y = (xf - mean) / jnp.sqrt(var + eps)
    norm_shape = (1,) * axis + shape[axis:]
    if scale is not None:
        y = y * scale.reshape(norm_shape)
    if bias is not None:
        y = y + bias.reshape(norm_shape)
    ctx.set_output(op, "Y", y.astype(x.dtype))
    ctx.set_output(op, "Mean", mean.reshape(-1))
    ctx.set_output(op, "Variance", var.reshape(-1))


@register_op("rms_norm", infer=lambda op, block: set_out(
    op, block, "Y", in_var(op, block, "X").shape,
    in_var(op, block, "X").dtype))
def _rms_norm(ctx, op):
    """RMSNorm (new capability for the LLM configs; no reference analog)."""
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    scale = ctx.get_input(op, "Scale")
    eps = op.attr("epsilon", 1e-6)
    xf = x.astype("float32")
    group = op.attr("group_size", None)
    if group:
        # the mean over each run of ``group`` consecutive channels apart
        # (a state-space layer's gated norm with more than one group)
        xf = xf.reshape(xf.shape[:-1] + (xf.shape[-1] // group, group))
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf / jnp.sqrt(ms + eps)
    if group:
        y = y.reshape(x.shape)
    if scale is not None:
        y = y * scale
    ctx.set_output(op, "Y", y.astype(x.dtype))


def _gn_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Y", x.shape, x.dtype)
    g = op.attr("groups", 1)
    set_out(op, block, "Mean", [x.shape[0], g], "float32")
    set_out(op, block, "Variance", [x.shape[0], g], "float32")


@register_op("group_norm", infer=_gn_infer)
def _group_norm(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    scale, bias = ctx.get_input(op, "Scale"), ctx.get_input(op, "Bias")
    g = op.attr("groups", 1)
    eps = op.attr("epsilon", 1e-5)
    layout = op.attr("data_layout", "NCHW")
    if layout != "NCHW":
        x = jnp.moveaxis(x, -1, 1)
    n, c = jnp.shape(x)[:2]
    spatial = jnp.shape(x)[2:]
    xg = x.reshape((n, g, c // g) + spatial).astype("float32")
    red = tuple(range(2, jnp.ndim(xg)))
    mean = jnp.mean(xg, axis=red, keepdims=True)
    var = jnp.mean((xg - mean) ** 2, axis=red, keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(jnp.shape(x))
    cshape = (1, c) + (1,) * len(spatial)
    if scale is not None:
        y = y * scale.reshape(cshape)
    if bias is not None:
        y = y + bias.reshape(cshape)
    if layout != "NCHW":
        y = jnp.moveaxis(y, 1, -1)
    ctx.set_output(op, "Y", y.astype(ctx.get_input(op, "X").dtype))
    ctx.set_output(op, "Mean", mean.reshape(n, g))
    ctx.set_output(op, "Variance", var.reshape(n, g))


@register_op("instance_norm", infer=lambda op, block: (
    set_out(op, block, "Y", in_var(op, block, "X").shape,
            in_var(op, block, "X").dtype),
    set_out(op, block, "SavedMean",
            [in_var(op, block, "X").shape[0] *
             in_var(op, block, "X").shape[1]], "float32"),
    set_out(op, block, "SavedVariance",
            [in_var(op, block, "X").shape[0] *
             in_var(op, block, "X").shape[1]], "float32")))
def _instance_norm(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    scale, bias = ctx.get_input(op, "Scale"), ctx.get_input(op, "Bias")
    eps = op.attr("epsilon", 1e-5)
    red = tuple(range(2, jnp.ndim(x)))
    xf = x.astype("float32")
    mean = jnp.mean(xf, axis=red, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=red, keepdims=True)
    y = (xf - mean) / jnp.sqrt(var + eps)
    c = jnp.shape(x)[1]
    cshape = (1, c) + (1,) * (jnp.ndim(x) - 2)
    if scale is not None:
        y = y * scale.reshape(cshape)
    if bias is not None:
        y = y + bias.reshape(cshape)
    ctx.set_output(op, "Y", y.astype(x.dtype))
    ctx.set_output(op, "SavedMean", mean.reshape(-1))
    ctx.set_output(op, "SavedVariance",
                   (1.0 / jnp.sqrt(var + eps)).reshape(-1))


def _norm_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", x.shape, x.dtype)
    # Norm keeps a size-1 reduced axis (reference norm_op.cc InferShape:
    # xdim[axis] = 1) — caught by the round-5 infer-vs-runtime gate
    axis = op.attrs.get("axis", 1) % len(x.shape)
    nshape = list(x.shape)
    nshape[axis] = 1
    set_out(op, block, "Norm", nshape, x.dtype)


@register_op("norm", infer=_norm_infer)
def _l2norm(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    axis = op.attr("axis", 1)
    eps = op.attr("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    ctx.set_output(op, "Out", x / norm)
    ctx.set_output(op, "Norm", norm)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def _acc_infer(op, block):
    set_out(op, block, "Accuracy", [], "float32")
    if op.output("Correct"):
        set_out(op, block, "Correct", [], "int32")
    if op.output("Total"):
        set_out(op, block, "Total", [], "int32")


@register_op("accuracy", infer=_acc_infer, grad=None)
def _accuracy(ctx, op):
    jnp = _jnp()
    idx = ctx.get_input(op, "Indices")
    label = ctx.get_input(op, "Label")
    if jnp.ndim(label) == 2:
        label = jnp.squeeze(label, -1)
    correct = jnp.any(idx == label[:, None], axis=1)
    n = jnp.shape(idx)[0]
    num_correct = jnp.sum(correct.astype("int32"))
    ctx.set_output(op, "Accuracy",
                   num_correct.astype("float32") / float(n))
    ctx.set_output(op, "Correct", num_correct)
    ctx.set_output(op, "Total", jnp.asarray(n, dtype="int32"))


# ---------------------------------------------------------------------------
# misc nn
# ---------------------------------------------------------------------------

@register_op("label_smooth", infer=same_as_input())
def _label_smooth(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    eps = op.attr("epsilon", 0.1)
    dist = ctx.get_input(op, "PriorDist")
    k = jnp.shape(x)[-1]
    if dist is not None:
        out = (1 - eps) * x + eps * dist
    else:
        out = (1 - eps) * x + eps / k
    ctx.set_output(op, "Out", out)


@register_op("prelu", infer=same_as_input())
def _prelu(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    alpha = ctx.get_input(op, "Alpha")
    mode = op.attr("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (jnp.ndim(x) - 2))
    ctx.set_output(op, "Out", jnp.where(x >= 0, x, alpha * x))


@register_op("softshrink", infer=same_as_input())
def _softshrink(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    lam = op.attr("lambda", 0.5)
    ctx.set_output(op, "Out",
                   jnp.where(x > lam, x - lam,
                             jnp.where(x < -lam, x + lam, 0.0)))


@register_op("maxout", infer=lambda op, block: set_out(
    op, block, "Out",
    [in_var(op, block, "X").shape[0],
     in_var(op, block, "X").shape[1] // op.attr("groups", 1)] +
    list(in_var(op, block, "X").shape[2:]),
    in_var(op, block, "X").dtype))
def _maxout(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    g = op.attr("groups", 1)
    n, c = jnp.shape(x)[:2]
    rest = jnp.shape(x)[2:]
    ctx.set_output(op, "Out",
                   jnp.max(x.reshape((n, c // g, g) + rest), axis=2))
