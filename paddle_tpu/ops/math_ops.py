"""Math / elementwise / reduction / matmul op lowerings.

Replaces the reference's hand-written CPU/CUDA kernels for these ops
(operators/elementwise/*, operators/reduce_ops/*, operators/matmul_op.cc,
operators/activation_op.*, operators/scale_op.cc, operators/sum_op.cc,
operators/cast_op.cc, operators/clip_op.cc) with jax.numpy/lax lowerings
fused by XLA.  Broadcasting follows the reference's axis-aligned rule
(operators/elementwise/elementwise_op_function.h).
"""
from __future__ import annotations

import numpy as np

from ..errors import InvalidArgumentError
from ..framework.core import Block, Operator, convert_dtype, dtype_to_np
from .registry import (LowerContext, broadcast_shapes, in_var, register_op,
                       same_as_input, set_out)


def _jnp():
    import jax.numpy as jnp
    return jnp


# ---------------------------------------------------------------------------
# elementwise binary ops (broadcast with paddle `axis` semantics)
# ---------------------------------------------------------------------------

def _ew_infer(op: Operator, block: Block):
    x = in_var(op, block, "X")
    y = in_var(op, block, "Y")
    axis = op.attr("axis", -1)
    shape = broadcast_shapes(list(x.shape), list(y.shape), axis)
    set_out(op, block, "Out", shape, x.dtype)


def _align_y(x, y, axis):
    jnp = _jnp()
    xr, yr = jnp.ndim(x), jnp.ndim(y)
    if yr < xr:
        if axis == -1:
            axis = xr - yr
        y = jnp.reshape(y, (1,) * axis + tuple(jnp.shape(y)) +
                        (1,) * (xr - axis - yr))
    elif xr < yr:
        if axis == -1:
            axis = yr - xr
        x = jnp.reshape(x, (1,) * axis + tuple(jnp.shape(x)) +
                        (1,) * (yr - axis - xr))
    return x, y


def _make_ew(op_type, fn):
    def lower(ctx: LowerContext, op: Operator):
        from ..framework.selected_rows import densify

        # SELECTED_ROWS operands densify here (grad-clip pipelines
        # square/scale grads elementwise); sparsity-preserving consumers
        # are sum/scale/optimizer ops
        x = densify(ctx.get_input(op, "X"))
        y = densify(ctx.get_input(op, "Y"))
        x, y = _align_y(x, y, op.attr("axis", -1))
        ctx.set_output(op, "Out", fn(x, y))
    register_op(op_type, infer=_ew_infer, lower=lower)


_make_ew("elementwise_add", lambda x, y: x + y)
_make_ew("elementwise_sub", lambda x, y: x - y)
_make_ew("elementwise_mul", lambda x, y: x * y)
_make_ew("elementwise_div", lambda x, y: x / y)
_make_ew("elementwise_min", lambda x, y: _jnp().minimum(x, y))
_make_ew("elementwise_max", lambda x, y: _jnp().maximum(x, y))
_make_ew("elementwise_pow", lambda x, y: _jnp().power(x, y))
_make_ew("elementwise_mod", lambda x, y: _jnp().mod(x, y))
_make_ew("elementwise_floordiv", lambda x, y: _jnp().floor_divide(x, y))


# ---------------------------------------------------------------------------
# comparison / logical (non-differentiable)
# ---------------------------------------------------------------------------

def _cmp_infer(op: Operator, block: Block):
    x = in_var(op, block, "X")
    y = in_var(op, block, "Y")
    shape = broadcast_shapes(list(x.shape), list(y.shape), op.attr("axis", -1))
    set_out(op, block, "Out", shape, "bool")


def _make_cmp(op_type, fn):
    def lower(ctx, op):
        x, y = ctx.get_input(op, "X"), ctx.get_input(op, "Y")
        ctx.set_output(op, "Out", fn(x, y))
    register_op(op_type, infer=_cmp_infer, lower=lower, grad=None)


_make_cmp("less_than", lambda x, y: x < y)
_make_cmp("less_equal", lambda x, y: x <= y)
_make_cmp("greater_than", lambda x, y: x > y)
_make_cmp("greater_equal", lambda x, y: x >= y)
_make_cmp("equal", lambda x, y: x == y)
_make_cmp("not_equal", lambda x, y: x != y)
_make_cmp("logical_and", lambda x, y: _jnp().logical_and(x, y))
_make_cmp("logical_or", lambda x, y: _jnp().logical_or(x, y))
_make_cmp("logical_xor", lambda x, y: _jnp().logical_xor(x, y))


@register_op("logical_not", infer=same_as_input(), grad=None)
def _logical_not(ctx, op):
    ctx.set_output(op, "Out", _jnp().logical_not(ctx.get_input(op, "X")))


@register_op("isfinite_v2", infer=same_as_input(), grad=None)
def _isfinite(ctx, op):
    ctx.set_output(op, "Out", _jnp().isfinite(ctx.get_input(op, "X")))


def _isfinite_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", x.shape, "bool")


for _t in ("isfinite_v2", "isnan_v2", "isinf_v2"):
    pass  # shapes fixed below

register_op("isnan_v2", infer=_isfinite_infer, grad=None,
            lower=lambda ctx, op: ctx.set_output(
                op, "Out", _jnp().isnan(ctx.get_input(op, "X"))))
register_op("isinf_v2", infer=_isfinite_infer, grad=None,
            lower=lambda ctx, op: ctx.set_output(
                op, "Out", _jnp().isinf(ctx.get_input(op, "X"))))
_REG_FIX = True
# fix isfinite_v2 infer (bool output)
from .registry import _REGISTRY  # noqa: E402
_REGISTRY["isfinite_v2"].infer = _isfinite_infer


# ---------------------------------------------------------------------------
# unary activations & pointwise math
# ---------------------------------------------------------------------------

def _make_unary(op_type, fn, grad="auto"):
    def lower(ctx: LowerContext, op: Operator):
        ctx.set_output(op, "Out", fn(ctx.get_input(op, "X"), op))
    register_op(op_type, infer=same_as_input(), lower=lower, grad=grad)


def _jnn():
    import jax.nn
    return jax.nn


_make_unary("relu", lambda x, op: _jnp().maximum(x, 0))
_make_unary("relu6", lambda x, op: _jnp().clip(x, 0, op.attr("threshold", 6.0)))
_make_unary("sigmoid", lambda x, op: _jnn().sigmoid(x))
_make_unary("tanh", lambda x, op: _jnp().tanh(x))
_make_unary("exp", lambda x, op: _jnp().exp(x))
_make_unary("log", lambda x, op: _jnp().log(x))
_make_unary("log2", lambda x, op: _jnp().log2(x))
_make_unary("log10", lambda x, op: _jnp().log10(x))
_make_unary("log1p", lambda x, op: _jnp().log1p(x))
_make_unary("sqrt", lambda x, op: _jnp().sqrt(x))
_make_unary("rsqrt", lambda x, op: 1.0 / _jnp().sqrt(x))
_make_unary("square", lambda x, op: x * x)
_make_unary("abs", lambda x, op: _jnp().abs(x))
_make_unary("reciprocal", lambda x, op: 1.0 / x)
_make_unary("floor", lambda x, op: _jnp().floor(x))
_make_unary("ceil", lambda x, op: _jnp().ceil(x))
_make_unary("round", lambda x, op: _jnp().round(x))
_make_unary("sin", lambda x, op: _jnp().sin(x))
_make_unary("cos", lambda x, op: _jnp().cos(x))
_make_unary("tan", lambda x, op: _jnp().tan(x))
_make_unary("asin", lambda x, op: _jnp().arcsin(x))
_make_unary("acos", lambda x, op: _jnp().arccos(x))
_make_unary("atan", lambda x, op: _jnp().arctan(x))
_make_unary("sinh", lambda x, op: _jnp().sinh(x))
_make_unary("cosh", lambda x, op: _jnp().cosh(x))
_make_unary("erf", lambda x, op: __import__("jax").scipy.special.erf(x))
_make_unary("gelu", lambda x, op: _jnn().gelu(
    x, approximate=op.attr("approximate", False)))
_make_unary("softplus", lambda x, op: _jnn().softplus(x))
_make_unary("softsign", lambda x, op: _jnn().soft_sign(x))
_make_unary("silu", lambda x, op: _jnn().silu(x))
_make_unary("swish", lambda x, op: x * _jnn().sigmoid(
    op.attr("beta", 1.0) * x))
_make_unary("mish", lambda x, op: x * _jnp().tanh(_jnn().softplus(x)))
_make_unary("hard_sigmoid", lambda x, op: _jnp().clip(
    op.attr("slope", 0.2) * x + op.attr("offset", 0.5), 0, 1))
_make_unary("hard_swish", lambda x, op: x * _jnp().clip(
    x + op.attr("offset", 3.0), 0, op.attr("threshold", 6.0))
    / op.attr("scale", 6.0))
_make_unary("leaky_relu", lambda x, op: _jnn().leaky_relu(
    x, op.attr("alpha", 0.02)))
_make_unary("elu", lambda x, op: _jnn().elu(x, op.attr("alpha", 1.0)))
_make_unary("logsigmoid", lambda x, op: _jnn().log_sigmoid(x))
_make_unary("sign", lambda x, op: _jnp().sign(x), grad=None)


def _clip_value(x, op):
    """reference clip_op.h — the SelectedRows branch merges, then clips
    the values slab (untouched rows are implicitly 0, kept as-is)."""
    from ..framework.selected_rows import is_selected_rows

    lo = op.attr("min", float("-inf"))
    hi = op.attr("max", float("inf"))
    if is_selected_rows(x):
        m = x.merge()
        return type(m)(m.rows, _jnp().clip(m.values, lo, hi), m.height)
    return _jnp().clip(x, lo, hi)


_make_unary("clip", _clip_value)
_make_unary("assign", lambda x, op: x)
_make_unary("share_data", lambda x, op: x)


@register_op("scale", infer=same_as_input())
def _scale(ctx: LowerContext, op: Operator):
    from ..framework.selected_rows import is_selected_rows

    x = ctx.get_input(op, "X")
    scale = op.attr("scale", 1.0)
    if op.single_input("ScaleTensor"):
        scale = ctx.get_input(op, "ScaleTensor")
    bias = op.attr("bias", 0.0)
    if is_selected_rows(x):
        # sparsity-preserving (bias on a sparse grad would densify;
        # the framework only emits bias=0 scales on grads)
        if bias != 0.0:
            x = x.to_dense()
        else:
            ctx.set_output(op, "Out", x.scale(scale))
            return
    if op.attr("bias_after_scale", True):
        out = x * scale + bias
    else:
        out = (x + bias) * scale
    ctx.set_output(op, "Out", out)


@register_op("increment", infer=same_as_input())
def _increment(ctx: LowerContext, op: Operator):
    """Out = X + step, dtype-preserving (reference increment_op.cc) — used
    for int step counters, where a scale op would promote to float."""
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    step = op.attr("step", 1.0)
    ctx.set_output(op, "Out", x + jnp.asarray(step).astype(x.dtype))


@register_op("pow", infer=same_as_input())
def _pow(ctx, op):
    x = ctx.get_input(op, "X")
    factor = op.attr("factor", 1.0)
    if op.single_input("FactorTensor"):
        factor = ctx.get_input(op, "FactorTensor")
    ctx.set_output(op, "Out", _jnp().power(x, factor))


def _cast_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", x.shape, op.attr("out_dtype", "float32"))


@register_op("cast", infer=_cast_infer)
def _cast(ctx, op):
    x = ctx.get_input(op, "X")
    ctx.set_output(op, "Out",
                   x.astype(dtype_to_np(op.attr("out_dtype", "float32"))))


# ---------------------------------------------------------------------------
# matmul family
# ---------------------------------------------------------------------------

def _matmul_shape(xs, ys, tx, ty):
    xs, ys = list(xs), list(ys)
    x1 = len(xs) == 1
    y1 = len(ys) == 1
    if x1:
        xs = [1, xs[0]]
    if y1:
        ys = [ys[0], 1]
    # transpose flags are ignored for 1-D operands, matching the
    # lowering's `ndim >= 2` condition
    if tx and not x1:
        xs = xs[:-2] + [xs[-1], xs[-2]]
    if ty and not y1:
        ys = ys[:-2] + [ys[-1], ys[-2]]
    if not (int(xs[-1]) == int(ys[-2]) or -1 in (int(xs[-1]),
                                                 int(ys[-2]))):
        raise InvalidArgumentError(
            f"matmul contraction mismatch: X{tuple(xs)} @ Y{tuple(ys)} "
            f"(K={xs[-1]} vs {ys[-2]})")
    batch = xs[:-2] if len(xs) >= len(ys) else ys[:-2]
    out = list(batch) + [xs[-2], ys[-1]]
    if x1:
        out.pop(-2)
    if y1:
        out.pop(-1)
    if not out:
        out = [1]
    return tuple(out)


def _matmul_infer(op: Operator, block: Block):
    x, y = in_var(op, block, "X"), in_var(op, block, "Y")
    tx = op.attr("trans_x", op.attr("transpose_X", False))
    ty = op.attr("trans_y", op.attr("transpose_Y", False))
    set_out(op, block, "Out", _matmul_shape(x.shape, y.shape, tx, ty),
            op.attr("out_dtype", None) or x.dtype)


def _matmul_lower(ctx: LowerContext, op: Operator):
    jnp = _jnp()
    x, y = ctx.get_input(op, "X"), ctx.get_input(op, "Y")
    tx = op.attr("trans_x", op.attr("transpose_X", False))
    ty = op.attr("trans_y", op.attr("transpose_Y", False))
    if tx and jnp.ndim(x) >= 2:
        x = jnp.swapaxes(x, -1, -2)
    if ty and jnp.ndim(y) >= 2:
        y = jnp.swapaxes(y, -1, -2)
    # On the MXU, accumulate matmuls in f32 even for bf16 operands.
    out = jnp.matmul(x, y, preferred_element_type=_acc_dtype(x.dtype),
                     precision=_mm_precision(x.dtype))
    out = out.astype(_out_dtype(op, x))
    alpha = op.attr("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    ctx.set_output(op, "Out", out)


def _acc_dtype(dtype):
    jnp = _jnp()
    if dtype in (jnp.bfloat16, np.float16):
        return jnp.float32
    return dtype


def _out_dtype(op, x):
    """What ``mul`` / ``matmul`` hand on: X's dtype, or with the attr
    ``out_dtype`` (an LM head over two-byte rows: "float32") that one, the
    float32 sum not rounded on the way."""
    out = op.attr("out_dtype", None)
    return x.dtype if out is None else dtype_to_np(out)


def _mm_precision(dtype):
    """f32 operands compute at full precision (reference cuBLAS semantics);
    bf16/f16 operands ride the fast MXU path — speed is an explicit
    dtype/AMP choice, not a silent truncation.  On CPU, DEFAULT is already
    full f32 (and non-default precisions compile pathologically slowly)."""
    import jax
    jnp = _jnp()
    if dtype in (jnp.bfloat16, np.float16):
        return None
    if jax.default_backend() == "cpu":
        return None
    return jax.lax.Precision.HIGHEST


register_op("matmul_v2", infer=_matmul_infer, lower=_matmul_lower)
register_op("matmul", infer=_matmul_infer, lower=_matmul_lower)


def _mul_infer(op: Operator, block: Block):
    # reference `mul_op`: flatten x to 2-D at x_num_col_dims, y likewise.
    x, y = in_var(op, block, "X"), in_var(op, block, "Y")
    xd = op.attr("x_num_col_dims", 1)
    yd = op.attr("y_num_col_dims", 1)
    out = list(x.shape[:xd]) + list(y.shape[yd:])
    set_out(op, block, "Out", out, op.attr("out_dtype", None) or x.dtype)


@register_op("mul", infer=_mul_infer)
def _mul_lower(ctx: LowerContext, op: Operator):
    import jax
    jnp = _jnp()
    x, y = ctx.get_input(op, "X"), ctx.get_input(op, "Y")
    xd = op.attr("x_num_col_dims", 1)
    yd = op.attr("y_num_col_dims", 1)
    xs, ys = jnp.shape(x), jnp.shape(y)
    if list(xs[xd:]) == list(ys[:yd]):
        # contraction factorizations line up: contract directly with
        # dot_general, leading dims stay free. The reshape-to-2D-and-back
        # formulation costs real HBM copies when XLA's tiled layouts
        # differ across the reshape (profiled 3 GB/step of bf16
        # [B,S,I] copies on the seq-128 BERT flagship at batch 160 —
        # ~15% of device time as 'copy' ops)
        dn = ((tuple(range(xd, len(xs))), tuple(range(yd))), ((), ()))
        out = jax.lax.dot_general(
            x, y, dn, preferred_element_type=_acc_dtype(x.dtype),
            precision=_mm_precision(x.dtype))
        ctx.set_output(op, "Out", out.astype(_out_dtype(op, x)))
        return
    x2 = jnp.reshape(x, (int(np.prod(xs[:xd])), -1))
    y2 = jnp.reshape(y, (int(np.prod(ys[:yd])), -1))
    out = jnp.matmul(x2, y2, preferred_element_type=_acc_dtype(x2.dtype),
                     precision=_mm_precision(x2.dtype))
    out = out.astype(_out_dtype(op, x2))
    ctx.set_output(op, "Out", jnp.reshape(out, xs[:xd] + ys[yd:]))


# Rows a segment of the products that stop at the valid rows
# (``mul_valid_rows``, ``swiglu_valid_rows``).  Measured on a v5e at float32
# "highest" (tools/dense_rows_microbench.py and the two cells whose
# prefills the products set the pace of, 256 against 512: PERF.md §6 PR 64).
VALID_ROW_SEGMENT = 256


def _over_valid_rows(x, valid, segment, width, fn):
    """``fn`` (rows ``[1, segment, K]`` -> ``[1, segment, width]``, a row's
    result a function of that row alone) over the segments of x [1, S, K]
    that hold one of its first ``valid`` (a traced int32 scalar) rows, zeros
    behind them: a loop of dynamic trip count, so one body is compiled
    whatever S.  Where S is no multiple of the segment the last one starts
    early, at S - segment, and works some rows again."""
    import jax
    jnp = _jnp()
    rows = x.shape[1]

    def one(i, out):
        start = jnp.minimum(i * segment, rows - segment)
        part = fn(jax.lax.dynamic_slice_in_dim(x, start, segment, axis=1))
        return jax.lax.dynamic_update_slice_in_dim(out, part, start, axis=1)

    # (zeros that wait for x: XLA:TPU fills a buffer of plain zeros at the
    # program's start, every loop's at once, and holds them all till their
    # loops run: 2.3 GB in solar-open2-250b's rung 4096, PERF.md §6 PR 64)
    zeros = jnp.broadcast_to(0 * x[0, 0, 0], (1, rows, width))
    return jax.lax.fori_loop(
        0, (jnp.clip(valid, 0, rows) + segment - 1) // segment, one, zeros)


def _rows_dot(x, y):
    """``mul``'s ``dot_general`` of rows x [1, R, K] by y [K, N]."""
    import jax
    return jax.lax.dot_general(
        x, y, (((2,), (0,)), ((), ())),
        preferred_element_type=_acc_dtype(x.dtype),
        precision=_mm_precision(x.dtype)).astype(x.dtype)


def valid_rows_product(x, y, valid, segment=VALID_ROW_SEGMENT):
    """x [1, S, K] @ y [K, N] over the segments that hold one of the first
    ``valid`` rows (:func:`_over_valid_rows`): the same ``dot_general`` as
    ``mul``'s a segment."""
    return _over_valid_rows(x, valid, segment, y.shape[1],
                            lambda rows: _rows_dot(rows, y))


def valid_rows_swiglu(x, gate_up, down, valid, segment=VALID_ROW_SEGMENT,
                      limit=None):
    """``(silu(x W_gate) * (x W_up)) W_down`` with gate | up fused, x
    [1, S, K], over the segments that hold one of the first ``valid`` rows
    (:func:`_over_valid_rows`): both products and what lies between them
    a segment, so no ``[S, 2I]`` is ever held.  ``limit`` L: the gate held
    under L and the up to [-L, L] first."""
    import jax
    jnp = _jnp()
    width = down.shape[0]

    def ffn(rows):
        gu = _rows_dot(rows, gate_up)
        gate, up = gu[..., :width], gu[..., width:]
        if limit is not None:
            gate = jnp.clip(gate, -3.0e38, limit)
            up = jnp.clip(up, -limit, limit)
        return _rows_dot(jax.nn.silu(gate) * up, down)

    return _over_valid_rows(x, valid, segment, down.shape[1], ffn)


def _valid_rows_infer(weights):
    """Shape inference of an op over X [1, S, K] that stops at
    ``ValidRows``: ``weights`` are its matrix slots in order, chained K ->
    ... -> N (a slot ``(name, 2)``: the next one reads half its columns)."""
    def infer(op: Operator, block: Block):
        x = in_var(op, block, "X")
        shapes = [tuple(int(d) for d in in_var(op, block, w).shape)
                  for w, _ in weights]
        ok = len(x.shape) == 3 and int(x.shape[0]) == 1 \
            and all(len(s) == 2 for s in shapes)
        k = int(x.shape[2]) if ok else None
        for (_w, split), shape in zip(weights, shapes):
            ok = ok and shape[0] == k and shape[1] % split == 0
            k = shape[1] // split if ok else None
        if not ok:
            raise InvalidArgumentError(
                f"{op.type}: X [1, S, K] through "
                f"{[w for w, _ in weights]}, got X{tuple(x.shape)} {shapes}")
        if not 0 < int(op.attr("segment")) <= int(x.shape[1]):
            raise InvalidArgumentError(
                f"{op.type}: a segment of {op.attr('segment')} rows of "
                f"{x.shape[1]}")
        set_out(op, block, "Out", [1, x.shape[1], k], x.dtype)
    return infer


@register_op("mul_valid_rows", infer=_valid_rows_infer([("Y", 1)]),
             grad=None)
def _mul_valid_rows_lower(ctx: LowerContext, op: Operator):
    """``mul`` of rows X [1, S, K] by Y [K, N] of which only the first
    ``ValidRows[0]`` hold anything (:func:`valid_rows_product`).
    Inference only."""
    ctx.set_output(op, "Out", valid_rows_product(
        ctx.get_input(op, "X"), ctx.get_input(op, "Y"),
        ctx.get_input(op, "ValidRows")[0].astype("int32"),
        int(op.attr("segment"))))


@register_op("swiglu_valid_rows",
             infer=_valid_rows_infer([("GateUp", 2), ("Down", 1)]),
             grad=None)
def _swiglu_valid_rows_lower(ctx: LowerContext, op: Operator):
    """A SwiGLU (GateUp [K, 2I], Down [I, N]) of rows X [1, S, K] of which
    only the first ``ValidRows[0]`` hold anything
    (:func:`valid_rows_swiglu`).  Inference only."""
    ctx.set_output(op, "Out", valid_rows_swiglu(
        ctx.get_input(op, "X"), ctx.get_input(op, "GateUp"),
        ctx.get_input(op, "Down"),
        ctx.get_input(op, "ValidRows")[0].astype("int32"),
        int(op.attr("segment")), op.attr("limit", None)))


@register_op("dot", infer=lambda op, block: set_out(
    op, block, "Out", list(in_var(op, block, "X").shape[:-1]) or [1],
    in_var(op, block, "X").dtype))
def _dot(ctx, op):
    jnp = _jnp()
    x, y = ctx.get_input(op, "X"), ctx.get_input(op, "Y")
    ctx.set_output(op, "Out", jnp.sum(x * y, axis=-1))


@register_op("bmm", infer=_matmul_infer)
def _bmm(ctx, op):
    jnp = _jnp()
    x, y = ctx.get_input(op, "X"), ctx.get_input(op, "Y")
    out = jnp.matmul(x, y, preferred_element_type=_acc_dtype(x.dtype),
                     precision=_mm_precision(x.dtype))
    ctx.set_output(op, "Out", out.astype(x.dtype))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def _reduce_infer(op: Operator, block: Block):
    x = in_var(op, block, "X")
    dims = op.attr("dim", [0])
    keep = op.attr("keep_dim", False)
    if op.attr("reduce_all", False) or dims is None or dims == []:
        shape = [1] * len(x.shape) if keep else []
    else:
        dims = [d % len(x.shape) for d in
                (dims if isinstance(dims, (list, tuple)) else [dims])]
        shape = [(1 if i in dims else s) if keep else s
                 for i, s in enumerate(x.shape) if keep or i not in dims]
    if not shape:
        shape = []
    dtype = op.attr("out_dtype") or x.dtype
    set_out(op, block, "Out", shape, dtype)


def _make_reduce(op_type, fn, grad="auto"):
    def lower(ctx: LowerContext, op: Operator):
        jnp = _jnp()
        x = ctx.get_input(op, "X")
        keep = op.attr("keep_dim", False)
        if op.attr("reduce_all", False) or not op.attr("dim", [0]):
            axis = None
        else:
            dims = op.attr("dim", [0])
            dims = dims if isinstance(dims, (list, tuple)) else [dims]
            axis = tuple(d % jnp.ndim(x) for d in dims)
        out = fn(x, axis, keep)
        if op.attr("out_dtype"):
            out = out.astype(dtype_to_np(op.attr("out_dtype")))
        ctx.set_output(op, "Out", out)
    register_op(op_type, infer=_reduce_infer, lower=lower, grad=grad)


_make_reduce("reduce_sum", lambda x, a, k: _jnp().sum(x, axis=a, keepdims=k))
_make_reduce("reduce_mean", lambda x, a, k: _jnp().mean(x, axis=a, keepdims=k))
_make_reduce("reduce_max", lambda x, a, k: _jnp().max(x, axis=a, keepdims=k))
_make_reduce("reduce_min", lambda x, a, k: _jnp().min(x, axis=a, keepdims=k))
_make_reduce("reduce_prod", lambda x, a, k: _jnp().prod(x, axis=a, keepdims=k))
_make_reduce("reduce_any",
             lambda x, a, k: _jnp().any(x, axis=a, keepdims=k), grad=None)
_make_reduce("reduce_all",
             lambda x, a, k: _jnp().all(x, axis=a, keepdims=k), grad=None)
_make_reduce("logsumexp", lambda x, a, k: __import__("jax").scipy.special
             .logsumexp(x, axis=a, keepdims=k))


def _mean_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", [], x.dtype)


@register_op("mean", infer=_mean_infer)
def _mean(ctx, op):
    ctx.set_output(op, "Out", _jnp().mean(ctx.get_input(op, "X")))


def _sum_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", x.shape, x.dtype)


@register_op("sum", infer=_sum_infer)
def _sum(ctx, op):
    """Add N tensors (reference sum_op, used for gradient accumulation).
    All-SelectedRows inputs concatenate (reference sum_op SelectedRows
    branch); mixed inputs densify."""
    from ..framework.selected_rows import (concat_selected_rows,
                                           is_selected_rows)

    xs = ctx.get_inputs(op, "X")
    if xs and all(is_selected_rows(x) for x in xs):
        out = xs[0] if len(xs) == 1 else concat_selected_rows(xs)
        ctx.set_output(op, "Out", out)
        return
    xs = [x.to_dense() if is_selected_rows(x) else x for x in xs]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    ctx.set_output(op, "Out", out)


@register_op("p_norm", infer=lambda op, block: _reduce_like_pnorm(op, block))
def _p_norm(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    porder = op.attr("porder", 2.0)
    axis = op.attr("axis", -1)
    keep = op.attr("keepdim", False)
    if op.attr("asvector", False):
        axis = None
    out = jnp.linalg.norm(x, ord=porder,
                          axis=axis if axis is None else int(axis),
                          keepdims=keep)
    ctx.set_output(op, "Out", out)


def _reduce_like_pnorm(op, block):
    x = in_var(op, block, "X")
    if op.attr("asvector", False):
        set_out(op, block, "Out", [], x.dtype)
        return
    axis = op.attr("axis", -1) % len(x.shape)
    keep = op.attr("keepdim", False)
    shape = [(1 if i == axis else s) for i, s in enumerate(x.shape)
             if keep or i != axis]
    set_out(op, block, "Out", shape, x.dtype)


# cumulative ops
@register_op("cumsum", infer=same_as_input())
def _cumsum(ctx, op):
    jnp = _jnp()
    x = ctx.get_input(op, "X")
    axis = op.attr("axis", -1)
    if op.attr("flatten", False):
        x = jnp.ravel(x)
        axis = 0
    out = jnp.cumsum(x, axis=axis)
    if op.attr("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    if op.attr("exclusive", False):
        out = out - x
    ctx.set_output(op, "Out", out)


@register_op("clip_by_norm", infer=same_as_input())
def _clip_by_norm(ctx, op):
    from ..framework.selected_rows import is_selected_rows

    jnp = _jnp()
    x = ctx.get_input(op, "X")
    max_norm = op.attr("max_norm", 1.0)
    if is_selected_rows(x):
        # reference clip_by_norm_op.h SelectedRows branch: MergeAdd,
        # then norm/scale the values slab (stays sparse)
        m = x.merge()
        norm = jnp.sqrt(jnp.sum(m.values * m.values))
        vals = jnp.where(norm > max_norm,
                         m.values * (max_norm / norm), m.values)
        ctx.set_output(op, "Out", type(m)(m.rows, vals, m.height))
        return
    norm = jnp.sqrt(jnp.sum(x * x))
    ctx.set_output(op, "Out",
                   jnp.where(norm > max_norm, x * (max_norm / norm), x))


@register_op("max", infer=_reduce_infer)
def _max(ctx, op):
    _REGISTRY["reduce_max"].lower(ctx, op)


@register_op("min", infer=_reduce_infer)
def _min(ctx, op):
    _REGISTRY["reduce_min"].lower(ctx, op)


def _global_norm_sq_infer(op, block):
    set_out(op, block, "Out", (), "float32")


@register_op("global_norm_sq", infer=_global_norm_sq_infer)
def _global_norm_sq(ctx, op):
    """sum_i ||x_i||^2 over ALL inputs in one concat+vdot fusion.

    Opt-in alternative (clip.py PT_FUSED_GLOBAL_CLIP=1) to the per-grad
    square+reduce chain — measured SLOWER on v5e BERT-base (the concat
    materializes the full gradient set), kept for param-count-heavy
    models where launch overhead dominates."""
    jnp = _jnp()
    from ..framework.selected_rows import densify
    xs = [densify(x) for x in ctx.get_inputs(op, "X")]
    flat = jnp.concatenate(
        [x.astype("float32").reshape(-1) for x in xs])
    ctx.set_output(op, "Out", jnp.vdot(flat, flat))
