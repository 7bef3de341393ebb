"""Per-op tests: forward vs numpy reference + grads vs central finite
differences, over the whole op registry.

Reference: the per-op OpTest suites under tests/unittests/test_*_op.py
(driven by op_test.py).  The coverage gate at the bottom guarantees every
registered op is either exercised here or skip-listed with the test file
that covers it.
"""
import math

import numpy as np
import pytest

import paddle_tpu as pt
from op_test import OpCase, check_forward, check_grad, run_case

R = np.random.RandomState


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


# ---------------------------------------------------------------------------
# unary elementwise
# ---------------------------------------------------------------------------
_POS = R(0).uniform(0.3, 2.0, (3, 4)).astype("float32")
_SYM = R(1).uniform(-2.0, 2.0, (3, 4)).astype("float32")
_UNIT = R(2).uniform(-0.9, 0.9, (3, 4)).astype("float32")
# keep points away from kinks (relu at 0, round at .5) for finite diffs
_OFF = (_SYM + np.where(np.abs(_SYM) < 0.15, 0.3, 0.0)).astype("float32")

UNARY = {
    "abs": (np.abs, _OFF, True),
    "acos": (np.arccos, _UNIT, True),
    "asin": (np.arcsin, _UNIT, True),
    "atan": (np.arctan, _SYM, True),
    "ceil": (np.ceil, _OFF, False),
    "cos": (np.cos, _SYM, True),
    "cosh": (np.cosh, _SYM, True),
    "erf": (np.vectorize(math.erf), _SYM, True),
    "exp": (np.exp, _SYM, True),
    "floor": (np.floor, _OFF, False),
    "log": (np.log, _POS, True),
    "log2": (np.log2, _POS, True),
    "log10": (np.log10, _POS, True),
    "log1p": (np.log1p, _POS, True),
    "logsigmoid": (lambda x: np.log(_sigmoid(x)), _SYM, True),
    "reciprocal": (lambda x: 1.0 / x, _POS, True),
    "relu": (lambda x: np.maximum(x, 0), _OFF, True),
    "relu6": (lambda x: np.clip(x, 0, 6), _OFF, True),
    "round": (np.round, _OFF, False),
    "rsqrt": (lambda x: 1.0 / np.sqrt(x), _POS, True),
    "sigmoid": (_sigmoid, _SYM, True),
    "sign": (np.sign, _OFF, False),
    "silu": (lambda x: x * _sigmoid(x), _SYM, True),
    "sin": (np.sin, _SYM, True),
    "sinh": (np.sinh, _SYM, True),
    "softplus": (lambda x: np.log1p(np.exp(x)), _SYM, True),
    "softsign": (lambda x: x / (1 + np.abs(x)), _OFF, True),
    "sqrt": (np.sqrt, _POS, True),
    "square": (np.square, _SYM, True),
    "tan": (np.tan, _UNIT, True),
    "tanh": (np.tanh, _SYM, True),
    "gelu": (lambda x: x * 0.5 * (1 + np.vectorize(math.erf)(
        x / np.sqrt(2))), _SYM, True),
    "elu": (lambda x: np.where(x > 0, x, np.expm1(x)), _OFF, True),
    "mish": (lambda x: x * np.tanh(np.log1p(np.exp(x))), _SYM, True),
    "swish": (lambda x: x * _sigmoid(x), _SYM, True),
    "hard_sigmoid": (lambda x: np.clip(0.2 * x + 0.5, 0, 1), _OFF, False),
    "hard_swish": (lambda x: x * np.clip(x + 3, 0, 6) / 6, _OFF, True),
    "softshrink": (lambda x: np.where(x > 0.5, x - 0.5,
                                      np.where(x < -0.5, x + 0.5, 0)),
                   _OFF, False),
}


@pytest.mark.parametrize("op", sorted(UNARY))
def test_unary(op):
    fn, data, do_grad = UNARY[op]
    run_case(OpCase(op, {"X": data}, ref=lambda X: fn(X),
                    grad=["X"] if do_grad else [], rtol=2e-5, atol=2e-6))


def test_leaky_relu_and_prelu():
    run_case(OpCase("leaky_relu", {"X": _OFF}, attrs={"alpha": 0.1},
                    ref=lambda X, alpha: np.where(X > 0, X, alpha * X),
                    grad=["X"]))
    alpha = np.full((1,), 0.25, "float32")
    run_case(OpCase("prelu", {"X": _OFF, "Alpha": alpha},
                    attrs={"mode": "all"},
                    ref=lambda X, Alpha, mode: np.where(X > 0, X,
                                                        Alpha * X),
                    grad=["X", "Alpha"]))


def test_scale_clip_increment_assign_cast():
    run_case(OpCase("scale", {"X": _SYM},
                    attrs={"scale": 2.0, "bias": 1.0},
                    ref=lambda X, scale, bias: scale * X + bias,
                    grad=["X"]))
    run_case(OpCase("clip", {"X": _SYM}, attrs={"min": -1.0, "max": 1.0},
                    ref=lambda X, min, max: np.clip(X, min, max)))
    run_case(OpCase("assign", {"X": _SYM}, ref=lambda X: X, grad=["X"]))
    run_case(OpCase("share_data", {"X": _SYM}, ref=lambda X: X))
    run_case(OpCase("cast", {"X": _SYM},
                    attrs={"out_dtype": "int32"},
                    ref=lambda X, out_dtype: X.astype("int32"),
                    check_dtype=False))
    run_case(OpCase("logsumexp", {"X": _SYM},
                    attrs={"dim": [-1], "keep_dim": False},
                    ref=lambda X, dim, keep_dim: np.log(
                        np.exp(X).sum(-1)), grad=["X"]))
    run_case(OpCase("pow", {"X": _POS}, attrs={"factor": 2.5},
                    ref=lambda X, factor: X ** 2.5, grad=["X"],
                    rtol=1e-4, atol=1e-5))
    run_case(OpCase("maxout", {"X": R(3).rand(2, 4, 3, 3).astype(
        "float32")}, attrs={"groups": 2, "axis": 1},
        ref=lambda X, groups, axis: X.reshape(2, 2, 2, 3, 3).max(2)))


def test_finite_checks():
    x = np.array([1.0, np.inf, -np.inf, np.nan, 3.0], "float32")
    run_case(OpCase("isfinite_v2", {"X": x}, ref=lambda X: np.isfinite(X),
                    check_dtype=False))
    run_case(OpCase("isinf_v2", {"X": x}, ref=lambda X: np.isinf(X),
                    check_dtype=False))
    run_case(OpCase("isnan_v2", {"X": x}, ref=lambda X: np.isnan(X),
                    check_dtype=False))


# ---------------------------------------------------------------------------
# binary elementwise + comparisons + logicals
# ---------------------------------------------------------------------------
_A = R(4).uniform(0.5, 2.0, (3, 4)).astype("float32")
_B = R(5).uniform(0.5, 2.0, (3, 4)).astype("float32")
_BCOL = R(6).uniform(0.5, 2.0, (4,)).astype("float32")

BINARY = {
    "elementwise_add": (np.add, True),
    "elementwise_sub": (np.subtract, True),
    "elementwise_mul": (np.multiply, True),
    "elementwise_div": (np.divide, True),
    "elementwise_max": (np.maximum, True),
    "elementwise_min": (np.minimum, True),
    "elementwise_pow": (np.power, True),
    "elementwise_mod": (np.mod, False),
    "elementwise_floordiv": (np.floor_divide, False),
}


@pytest.mark.parametrize("op", sorted(BINARY))
def test_binary(op):
    fn, do_grad = BINARY[op]
    run_case(OpCase(op, {"X": _A, "Y": _B}, ref=lambda X, Y: fn(X, Y),
                    grad=["X", "Y"] if do_grad else [], rtol=2e-5,
                    atol=2e-6))


def test_binary_broadcast_axis():
    run_case(OpCase("elementwise_add", {"X": _A, "Y": _BCOL},
                    attrs={"axis": -1},
                    ref=lambda X, Y, axis: X + Y, grad=["X", "Y"]))


COMPARE = {"equal": np.equal, "not_equal": np.not_equal,
           "less_than": np.less, "less_equal": np.less_equal,
           "greater_than": np.greater, "greater_equal": np.greater_equal}


@pytest.mark.parametrize("op", sorted(COMPARE))
def test_compare(op):
    a = np.array([[1, 2], [3, 4]], "float32")
    b = np.array([[1, 3], [2, 4]], "float32")
    run_case(OpCase(op, {"X": a, "Y": b},
                    ref=lambda X, Y: COMPARE[op](X, Y),
                    check_dtype=False))


LOGICAL = {"logical_and": np.logical_and, "logical_or": np.logical_or,
           "logical_xor": np.logical_xor}


@pytest.mark.parametrize("op", sorted(LOGICAL))
def test_logical(op):
    a = np.array([True, True, False, False])
    b = np.array([True, False, True, False])
    run_case(OpCase(op, {"X": a, "Y": b},
                    ref=lambda X, Y: LOGICAL[op](X, Y),
                    check_dtype=False))


def test_logical_not():
    run_case(OpCase("logical_not", {"X": np.array([True, False])},
                    ref=lambda X: ~X, check_dtype=False))


# ---------------------------------------------------------------------------
# matmul family
# ---------------------------------------------------------------------------
def test_matmul_family():
    x = R(7).rand(3, 4).astype("float32")
    y = R(8).rand(4, 5).astype("float32")
    run_case(OpCase("matmul", {"X": x, "Y": y},
                    ref=lambda X, Y: X @ Y, grad=["X", "Y"],
                    rtol=1e-4, atol=1e-5))
    run_case(OpCase("matmul_v2", {"X": x, "Y": y},
                    ref=lambda X, Y: X @ Y, grad=["X", "Y"],
                    rtol=1e-4, atol=1e-5))
    run_case(OpCase("matmul", {"X": x.T.copy(), "Y": y},
                    attrs={"transpose_X": True},
                    ref=lambda X, Y, transpose_X: X.T @ Y,
                    rtol=1e-4, atol=1e-5))
    run_case(OpCase("mul", {"X": x, "Y": y},
                    attrs={"x_num_col_dims": 1, "y_num_col_dims": 1},
                    ref=lambda X, Y, **kw: X @ Y, grad=["X", "Y"],
                    rtol=1e-4, atol=1e-5))
    bx = R(9).rand(2, 3, 4).astype("float32")
    by = R(10).rand(2, 4, 5).astype("float32")
    run_case(OpCase("bmm", {"X": bx, "Y": by},
                    ref=lambda X, Y: X @ Y, grad=["X", "Y"],
                    rtol=1e-4, atol=1e-5))
    run_case(OpCase("dot", {"X": x[0], "Y": x[1]},
                    ref=lambda X, Y: np.array(np.dot(X, Y)),
                    grad=["X", "Y"], rtol=1e-4, atol=1e-5))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def test_reductions():
    x = R(11).rand(2, 3, 4).astype("float32") + 0.1
    for op, fn in [("reduce_sum", np.sum), ("reduce_mean", np.mean),
                   ("reduce_max", np.max), ("reduce_min", np.min),
                   ("reduce_prod", np.prod)]:
        grad = ["X"] if op in ("reduce_sum", "reduce_mean") else []
        run_case(OpCase(op, {"X": x}, attrs={"dim": [1],
                                             "keep_dim": False},
                        ref=lambda X, dim, keep_dim, fn=fn: fn(X, axis=1),
                        grad=grad, rtol=1e-4, atol=1e-5))
    run_case(OpCase("reduce_sum", {"X": x},
                    attrs={"dim": [0], "keep_dim": True},
                    ref=lambda X, dim, keep_dim: X.sum(0, keepdims=True),
                    rtol=1e-4, atol=1e-5))
    run_case(OpCase("mean", {"X": x},
                    ref=lambda X: np.array(X.mean(), "float32"),
                    grad=["X"]))
    run_case(OpCase("max", {"X": x}, attrs={"dim": [-1]},
                    ref=lambda X, dim: X.max(-1)))
    run_case(OpCase("min", {"X": x}, attrs={"dim": [-1]},
                    ref=lambda X, dim: X.min(-1)))
    run_case(OpCase("sum", {"X": [_A, _B, _A]},
                    ref=lambda X: X[0] + X[1] + X[2], grad=["X"]))
    b = np.array([[True, False], [True, True]])
    run_case(OpCase("reduce_all", {"X": b}, attrs={"dim": [1]},
                    ref=lambda X, dim: X.all(1), check_dtype=False))
    run_case(OpCase("reduce_any", {"X": b}, attrs={"dim": [1]},
                    ref=lambda X, dim: X.any(1), check_dtype=False))
    run_case(OpCase("squared_l2_norm", {"X": _A},
                    ref=lambda X: np.array((X ** 2).sum(), "float32"),
                    grad=["X"], rtol=1e-4, atol=1e-5))
    run_case(OpCase("cumsum", {"X": x}, attrs={"axis": 1},
                    ref=lambda X, axis: X.cumsum(1), grad=["X"],
                    rtol=1e-4, atol=1e-5))


def test_norms():
    x = _A
    run_case(OpCase("norm", {"X": x}, outputs={"Out": 1, "Norm": 1},
                    attrs={"axis": 1, "epsilon": 1e-10},
                    ref=lambda X, axis, epsilon: {
                        "Out": X / np.sqrt((X ** 2).sum(1, keepdims=True)
                                           + epsilon)},
                    grad=["X"], rtol=1e-4, atol=1e-5))
    run_case(OpCase("p_norm", {"X": x},
                    attrs={"porder": 2.0, "axis": 1, "keepdim": False,
                           "epsilon": 1e-12},
                    ref=lambda X, porder, axis, keepdim, epsilon:
                    np.sqrt((X ** 2).sum(1)), grad=["X"],
                    rtol=1e-4, atol=1e-5))
    run_case(OpCase("clip_by_norm", {"X": x}, attrs={"max_norm": 1.0},
                    ref=lambda X, max_norm: X * min(
                        1.0, max_norm / np.sqrt((X ** 2).sum()))))


# ---------------------------------------------------------------------------
# shape / indexing ops
# ---------------------------------------------------------------------------
def test_shape_ops():
    x = R(12).rand(2, 3, 4).astype("float32")
    run_case(OpCase("reshape2", {"X": x},
                    outputs={"Out": 1, "XShape": 1},
                    attrs={"shape": [6, 4]},
                    ref=lambda X, shape: {"Out": X.reshape(6, 4)},
                    grad=["X"]))
    run_case(OpCase("transpose2", {"X": x},
                    outputs={"Out": 1, "XShape": 1},
                    attrs={"axis": [2, 0, 1]},
                    ref=lambda X, axis: {"Out": X.transpose(2, 0, 1)},
                    grad=["X"]))
    run_case(OpCase("concat", {"X": [_A, _B]}, attrs={"axis": 1},
                    ref=lambda X, axis: np.concatenate(X, 1),
                    grad=["X"]))
    run_case(OpCase("split", {"X": _A}, outputs={"Out": 2},
                    attrs={"num": 2, "axis": 1},
                    ref=lambda X, num, axis: {"Out": [X[:, :2], X[:, 2:]]},
                    grad=["X"]))
    run_case(OpCase("stack", {"X": [_A, _B]}, outputs={"Y": 1},
                    attrs={"axis": 0},
                    ref=lambda X, axis: {"Y": np.stack(X)}, grad=["X"]))
    run_case(OpCase("unstack", {"X": np.stack([_A, _B])},
                    outputs={"Y": 2}, attrs={"axis": 0, "num": 2},
                    ref=lambda X, axis, num: {"Y": [X[0], X[1]]},
                    grad=["X"]))
    run_case(OpCase("squeeze2", {"X": x[:, :1]},
                    outputs={"Out": 1, "XShape": 1},
                    attrs={"axes": [1]},
                    ref=lambda X, axes: {"Out": X[:, 0]}, grad=["X"]))
    run_case(OpCase("unsqueeze2", {"X": _A},
                    outputs={"Out": 1, "XShape": 1},
                    attrs={"axes": [1]},
                    ref=lambda X, axes: {"Out": X[:, None]}, grad=["X"]))
    run_case(OpCase("squeeze", {"X": x[:, :1]}, attrs={"axes": [1]},
                    ref=lambda X, axes: X[:, 0]))
    run_case(OpCase("unsqueeze", {"X": _A}, attrs={"axes": [0]},
                    ref=lambda X, axes: X[None]))
    run_case(OpCase("reshape", {"X": x}, attrs={"shape": [4, 6]},
                    ref=lambda X, shape: X.reshape(4, 6)))
    run_case(OpCase("transpose", {"X": _A}, attrs={"axis": [1, 0]},
                    ref=lambda X, axis: X.T))
    run_case(OpCase("flatten2", {"X": x},
                    outputs={"Out": 1, "XShape": 1}, attrs={"axis": 1},
                    ref=lambda X, axis: {"Out": X.reshape(2, 12)}))
    run_case(OpCase("flatten", {"X": x}, attrs={"axis": 2},
                    ref=lambda X, axis: X.reshape(6, 4)))
    run_case(OpCase("flatten_contiguous_range", {"X": x},
                    outputs={"Out": 1, "XShape": 1},
                    attrs={"start_axis": 1, "stop_axis": 2},
                    ref=lambda X, start_axis, stop_axis:
                    {"Out": X.reshape(2, 12)}))
    run_case(OpCase("slice", {"Input": x},
                    attrs={"axes": [1], "starts": [1], "ends": [3]},
                    ref=lambda Input, axes, starts, ends: Input[:, 1:3],
                    grad=["Input"]))
    run_case(OpCase("strided_slice", {"Input": x},
                    attrs={"axes": [2], "starts": [0], "ends": [4],
                           "strides": [2]},
                    ref=lambda Input, **kw: Input[:, :, 0:4:2]))
    run_case(OpCase("pad", {"X": _A},
                    attrs={"paddings": [1, 0, 0, 2], "pad_value": 0.5},
                    ref=lambda X, paddings, pad_value: np.pad(
                        X, [(1, 0), (0, 2)], constant_values=0.5),
                    grad=["X"]))
    run_case(OpCase("tile", {"X": _A},
                    attrs={"repeat_times": [2, 1]},
                    ref=lambda X, repeat_times: np.tile(X, (2, 1))))
    run_case(OpCase("expand", {"X": _A[:1]},
                    attrs={"expand_times": [3, 1]},
                    ref=lambda X, expand_times: np.tile(X, (3, 1))))
    run_case(OpCase("expand_v2", {"X": _A[:1]},
                    attrs={"shape": [3, 4]},
                    ref=lambda X, shape: np.broadcast_to(X, (3, 4))))
    run_case(OpCase("flip", {"X": _A}, attrs={"axis": [1]},
                    ref=lambda X, axis: X[:, ::-1]))
    run_case(OpCase("roll", {"X": _A}, attrs={"shifts": [1],
                                              "axis": [0]},
                    ref=lambda X, shifts, axis: np.roll(X, 1, 0)))
    run_case(OpCase("shape", {"Input": x},
                    ref=lambda Input: np.array(Input.shape),
                    check_dtype=False))


def test_gather_scatter():
    x = R(13).rand(5, 3).astype("float32")
    idx = np.array([0, 3, 1], "int64")
    run_case(OpCase("gather", {"X": x, "Index": idx},
                    ref=lambda X, Index: X[Index], grad=["X"]))
    run_case(OpCase("index_select", {"X": x, "Index": idx},
                    attrs={"dim": 0},
                    ref=lambda X, Index, dim: X[Index]))
    nd_idx = np.array([[0, 1], [3, 2]], "int64")
    run_case(OpCase("gather_nd", {"X": x, "Index": nd_idx},
                    ref=lambda X, Index: X[Index[:, 0], Index[:, 1]],
                    grad=["X"]))
    upd = np.ones((3, 3), "float32")
    run_case(OpCase("scatter", {"X": x, "Ids": idx, "Updates": upd},
                    attrs={"overwrite": True},
                    ref=lambda X, Ids, Updates, overwrite: _scatter_ref(
                        X, Ids, Updates)))
    nd_upd = np.ones((2,), "float32")
    run_case(OpCase("scatter_nd_add",
                    {"X": x, "Index": nd_idx, "Updates": nd_upd},
                    ref=lambda X, Index, Updates: _scatter_nd_ref(
                        X, Index, Updates)))
    ta_idx = np.array([[0, 1, 0], [2, 0, 1]], "int64")
    run_case(OpCase("take_along_axis",
                    {"Input": x[:2], "Index": ta_idx},
                    outputs={"Result": 1}, attrs={"Axis": 1},
                    ref=lambda Input, Index, Axis: {
                        "Result": np.take_along_axis(Input, Index, 1)}))
    cond = np.array([[True, False], [False, True]])
    a2, b2 = _A[:2, :2], _B[:2, :2]
    run_case(OpCase("where", {"Condition": cond, "X": a2, "Y": b2},
                    ref=lambda Condition, X, Y: np.where(Condition, X, Y),
                    grad=["X", "Y"]))
    run_case(OpCase("lookup_table_v2",
                    {"W": x, "Ids": np.array([[1, 4], [0, 2]], "int64")},
                    ref=lambda W, Ids: W[Ids], grad=["W"]))
    run_case(OpCase("lookup_table",
                    {"W": x, "Ids": np.array([[1], [4]], "int64")},
                    ref=lambda W, Ids: W[Ids[:, 0]]))
    run_case(OpCase("embedding",
                    {"W": x, "Ids": np.array([2, 0], "int64")},
                    ref=lambda W, Ids: W[Ids]))


def _scatter_ref(x, ids, upd):
    out = x.copy()
    out[ids] = upd
    return out


def _scatter_nd_ref(x, index, upd):
    out = x.copy()
    for k in range(index.shape[0]):
        out[tuple(index[k])] += upd[k]
    return out


def test_structural_ops():
    x = R(31).rand(2, 4, 4).astype("float32")
    run_case(OpCase("tril_triu", {"X": x},
                    attrs={"diagonal": 0, "lower": True},
                    ref=lambda X, diagonal, lower: np.tril(X),
                    grad=["X"]))
    run_case(OpCase("tril_triu", {"X": x},
                    attrs={"diagonal": 1, "lower": False},
                    ref=lambda X, diagonal, lower: np.triu(X, 1),
                    name="triu"))
    a = np.arange(3, dtype="float32")
    b = np.arange(4, dtype="float32")
    run_case(OpCase("meshgrid", {"X": [a, b]}, outputs={"Out": 2},
                    ref=lambda X: {"Out": list(np.meshgrid(
                        X[0], X[1], indexing="ij"))}))
    run_case(OpCase("cumprod", {"X": _POS}, attrs={"dim": 1},
                    ref=lambda X, dim: np.cumprod(X, 1), grad=["X"],
                    rtol=1e-4, atol=1e-5))
    img = R(32).rand(1, 2, 4, 4).astype("float32")
    run_case(OpCase("nearest_interp", {"X": img},
                    attrs={"out_h": 8, "out_w": 8,
                           "align_corners": False},
                    ref=lambda X, out_h, out_w, align_corners: np.repeat(
                        np.repeat(X, 2, 2), 2, 3)))
    def bilinear_ref(X, out_h, out_w, align_corners):
        n, c, h, w = X.shape
        ys = np.linspace(0, h - 1, out_h) if align_corners else \
            np.clip((np.arange(out_h) + 0.5) * h / out_h - 0.5, 0, h - 1)
        xs = np.linspace(0, w - 1, out_w) if align_corners else \
            np.clip((np.arange(out_w) + 0.5) * w / out_w - 0.5, 0, w - 1)
        y0 = np.floor(ys).astype(int); x0 = np.floor(xs).astype(int)
        y1 = np.minimum(y0 + 1, h - 1); x1 = np.minimum(x0 + 1, w - 1)
        wy = (ys - y0)[None, None, :, None]
        wx = (xs - x0)[None, None, None, :]
        g = lambda yi, xi: X[:, :, yi, :][:, :, :, xi]
        return (g(y0, x0) * (1 - wy) * (1 - wx) + g(y1, x0) * wy * (1 - wx)
                + g(y0, x1) * (1 - wy) * wx + g(y1, x1) * wy * wx
                ).astype("float32")

    for align in (True, False):
        run_case(OpCase("bilinear_interp", {"X": img},
                        attrs={"out_h": 8, "out_w": 8,
                               "align_corners": align},
                        ref=bilinear_ref, grad=["X"], rtol=1e-4,
                        atol=1e-5, name=f"bilinear_align{align}"))
    ps = R(33).rand(1, 8, 2, 2).astype("float32")

    def ps_ref(X, upscale_factor):
        n, c, h, w = X.shape
        r = upscale_factor
        o = X.reshape(n, c // (r * r), r, r, h, w)
        return o.transpose(0, 1, 4, 2, 5, 3).reshape(
            n, c // (r * r), h * r, w * r)

    run_case(OpCase("pixel_shuffle", {"X": ps},
                    attrs={"upscale_factor": 2}, ref=ps_ref, grad=["X"]))


def test_argsort_topk_onehot():
    x = R(14).rand(3, 5).astype("float32")
    run_case(OpCase("arg_max", {"X": x}, attrs={"axis": 1},
                    ref=lambda X, axis: X.argmax(1), check_dtype=False))
    run_case(OpCase("arg_min", {"X": x}, attrs={"axis": 1},
                    ref=lambda X, axis: X.argmin(1), check_dtype=False))
    run_case(OpCase("argsort", {"X": x},
                    outputs={"Out": 1, "Indices": 1}, attrs={"axis": 1},
                    ref=lambda X, axis: {"Out": np.sort(X, 1),
                                         "Indices": np.argsort(X, 1)},
                    check_dtype=False))
    run_case(OpCase("top_k_v2", {"X": x},
                    outputs={"Out": 1, "Indices": 1}, attrs={"k": 2},
                    ref=lambda X, k: {
                        "Out": np.sort(X, 1)[:, ::-1][:, :2],
                        "Indices": np.argsort(-X, 1)[:, :2]},
                    check_dtype=False))
    run_case(OpCase("top_k", {"X": x},
                    outputs={"Out": 1, "Indices": 1}, attrs={"k": 1},
                    ref=lambda X, k: {"Out": X.max(1, keepdims=True)},
                    check_dtype=False))
    ids = np.array([[1], [3]], "int64")
    run_case(OpCase("one_hot", {"X": ids}, attrs={"depth": 4},
                    ref=lambda X, depth: np.eye(4, dtype="float32")[
                        X[:, 0]], check_dtype=False))
    run_case(OpCase("one_hot_v2", {"X": ids[:, 0]}, attrs={"depth": 4},
                    ref=lambda X, depth: np.eye(4, dtype="float32")[X],
                    check_dtype=False))
    run_case(OpCase("label_smooth", {"X": np.eye(3, dtype="float32")},
                    attrs={"epsilon": 0.1},
                    ref=lambda X, epsilon: X * 0.9 + 0.1 / 3))


# ---------------------------------------------------------------------------
# creation ops (forward-only, exact)
# ---------------------------------------------------------------------------
def test_creation_ops():
    run_case(OpCase("fill_constant", {}, attrs={"shape": [2, 3],
                                                "dtype": "float32",
                                                "value": 2.5},
                    ref=lambda shape, dtype, value: np.full((2, 3), 2.5,
                                                            "float32")))
    run_case(OpCase("fill_any_like", {"X": _A}, attrs={"value": 3.0},
                    ref=lambda X, value: np.full_like(X, 3.0)))
    run_case(OpCase("fill_zeros_like", {"X": _A},
                    ref=lambda X: np.zeros_like(X)))
    run_case(OpCase("assign_value", {}, attrs={
        "shape": [2, 2], "dtype": "float32",
        "values": np.arange(4, dtype="float32")},
        ref=lambda **kw: np.arange(4, dtype="float32").reshape(2, 2)))
    run_case(OpCase("eye", {}, attrs={"num_rows": 3, "num_columns": 4,
                                      "dtype": "float32"},
                    ref=lambda **kw: np.eye(3, 4, dtype="float32")))
    run_case(OpCase("linspace", {}, attrs={"start": 0.0, "stop": 1.0,
                                           "num": 5, "dtype": "float32"},
                    ref=lambda **kw: np.linspace(0, 1, 5,
                                                 dtype="float32")))
    run_case(OpCase("range", {}, attrs={"start": 1.0, "end": 7.0,
                                        "step": 2.0, "dtype": "float32"},
                    ref=lambda **kw: np.arange(1, 7, 2, dtype="float32")))


def test_random_ops_statistics():
    got = check_forward(OpCase("gaussian_random", {}, attrs={
        "shape": [2000], "mean": 1.0, "std": 2.0, "dtype": "float32"}))
    a = np.asarray(got[0])
    assert abs(a.mean() - 1.0) < 0.2 and abs(a.std() - 2.0) < 0.2
    got = check_forward(OpCase("uniform_random", {}, attrs={
        "shape": [2000], "min": -1.0, "max": 1.0, "dtype": "float32"}))
    a = np.asarray(got[0])
    assert a.min() >= -1 and a.max() <= 1 and abs(a.mean()) < 0.1
    got = check_forward(OpCase("randint", {}, attrs={
        "shape": [1000], "low": 0, "high": 5, "dtype": "int64"}))
    a = np.asarray(got[0])
    assert a.min() >= 0 and a.max() < 5
    got = check_forward(OpCase("randperm", {}, attrs={"n": 64,
                                                      "dtype": "int64"}))
    a = np.asarray(got[0])
    assert sorted(a.tolist()) == list(range(64))
    got = check_forward(OpCase("bernoulli",
                               {"X": np.full((2000,), 0.3, "float32")}))
    a = np.asarray(got[0])
    assert set(np.unique(a)) <= {0.0, 1.0} and abs(a.mean() - 0.3) < 0.1
    got = check_forward(OpCase("truncated_gaussian_random", {}, attrs={
        "shape": [2000], "mean": 0.0, "std": 1.0, "dtype": "float32"}))
    a = np.asarray(got[0])
    assert np.abs(a).max() <= 2.0 + 1e-5


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def test_losses():
    logits = R(15).rand(4, 5).astype("float32")
    label = np.array([[1], [0], [4], [2]], "int64")
    onehot = np.eye(5, dtype="float32")[label[:, 0]]

    run_case(OpCase("softmax", {"X": logits},
                    ref=lambda X: _softmax(X), grad=["X"],
                    rtol=1e-4, atol=1e-5))
    run_case(OpCase("log_softmax", {"X": logits},
                    ref=lambda X: np.log(_softmax(X)), grad=["X"],
                    rtol=1e-4, atol=1e-5))
    run_case(OpCase("cross_entropy", {"X": _softmax(logits),
                                      "Label": label},
                    outputs={"Y": 1},
                    ref=lambda X, Label: {
                        "Y": -np.log(X[np.arange(4), Label[:, 0]]
                                     )[:, None]},
                    grad=["X"], rtol=1e-4, atol=1e-5))
    run_case(OpCase("softmax_with_cross_entropy",
                    {"Logits": logits, "Label": label},
                    outputs={"Softmax": 1, "Loss": 1},
                    ref=lambda Logits, Label: {
                        "Softmax": _softmax(Logits),
                        "Loss": -np.log(_softmax(Logits)[
                            np.arange(4), Label[:, 0]])[:, None]},
                    grad=["Logits"], rtol=1e-4, atol=1e-5))
    p = R(16).uniform(0.1, 0.9, (4, 1)).astype("float32")
    y = np.array([[1.0], [0.0], [1.0], [0.0]], "float32")
    run_case(OpCase("bce_loss", {"X": p, "Label": y},
                    ref=lambda X, Label: -(Label * np.log(X) + (
                        1 - Label) * np.log(1 - X)),
                    grad=["X"], rtol=1e-4, atol=1e-5))
    run_case(OpCase("sigmoid_cross_entropy_with_logits",
                    {"X": logits[:, :1], "Label": y},
                    ref=lambda X, Label: np.maximum(X, 0) - X * Label +
                    np.log1p(np.exp(-np.abs(X))),
                    grad=["X"], rtol=1e-4, atol=1e-5))
    run_case(OpCase("mse_loss", {"X": _A, "Y": _B},
                    ref=lambda X, Y: (X - Y) ** 2, grad=["X"]))
    run_case(OpCase("huber_loss", {"X": _A[:, :1], "Y": _B[:, :1]},
                    outputs={"Out": 1, "Residual": 1},
                    attrs={"delta": 0.3},
                    ref=lambda X, Y, delta: {
                        "Out": _huber_ref(Y - X, 0.3),
                        "Residual": Y - X}, grad=["X"]))
    run_case(OpCase("smooth_l1_loss", {"X": _A, "Y": _B},
                    outputs={"Out": 1, "Diff": 1}, attrs={"sigma": 1.0},
                    ref=lambda X, Y, sigma: {
                        "Out": _smooth_l1_ref(X - Y).sum(
                            1, keepdims=True)},
                    grad=["X"]))
    t = _softmax(R(17).rand(3, 4).astype("float32"))
    xlog = np.log(_softmax(R(18).rand(3, 4).astype("float32")))
    run_case(OpCase("kldiv_loss", {"X": xlog, "Target": t},
                    outputs={"Loss": 1}, attrs={"reduction": "none"},
                    ref=lambda X, Target, reduction: {
                        "Loss": Target * (np.log(Target) - X)},
                    grad=["X"], rtol=1e-4, atol=1e-5))


def _huber_ref(r, d):
    return np.where(np.abs(r) <= d, 0.5 * r * r,
                    d * (np.abs(r) - 0.5 * d))


def _smooth_l1_ref(d):
    a = np.abs(d)
    return np.where(a < 1, 0.5 * d * d, a - 0.5)


def test_accuracy_op():
    # accuracy(Out from topk, Indices, Label)
    pred = np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]], "float32")
    idx = pred.argmax(1)[:, None].astype("int64")
    label = np.array([[1], [1], [1]], "int64")
    run_case(OpCase("accuracy",
                    {"Out": pred, "Indices": idx, "Label": label},
                    outputs={"Accuracy": 1, "Correct": 1, "Total": 1},
                    ref=lambda Out, Indices, Label: {
                        "Accuracy": np.array(2 / 3, "float32")},
                    check_dtype=False))


# ---------------------------------------------------------------------------
# nn ops
# ---------------------------------------------------------------------------
def _conv2d_ref(x, w, stride=1, pad=0):
    n, ci, h, ww = x.shape
    co, _, kh, kw = w.shape
    xp = np.pad(x, [(0, 0), (0, 0), (pad, pad), (pad, pad)])
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (ww + 2 * pad - kw) // stride + 1
    out = np.zeros((n, co, oh, ow), "float64")
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, :, i * stride:i * stride + kh,
                       j * stride:j * stride + kw]
            out[:, :, i, j] = np.einsum("nchw,ochw->no", patch, w)
    return out.astype("float32")


def test_conv_pool():
    x = (R(19).permutation(2 * 3 * 5 * 5).reshape(2, 3, 5, 5)
         * 0.02).astype("float32")
    w = R(20).rand(4, 3, 3, 3).astype("float32")
    run_case(OpCase("conv2d", {"Input": x, "Filter": w},
                    outputs={"Output": 1},
                    attrs={"strides": [1, 1], "paddings": [1, 1],
                           "dilations": [1, 1], "groups": 1},
                    ref=lambda Input, Filter, **kw: {
                        "Output": _conv2d_ref(Input, Filter, 1, 1)},
                    grad=["Input", "Filter"], rtol=1e-3, atol=1e-4,
                    grad_rtol=8e-2))
    dw = R(21).rand(3, 1, 3, 3).astype("float32")
    run_case(OpCase("depthwise_conv2d", {"Input": x, "Filter": dw},
                    outputs={"Output": 1},
                    attrs={"strides": [1, 1], "paddings": [1, 1],
                           "dilations": [1, 1], "groups": 3},
                    ref=None, grad=["Input"], grad_rtol=8e-2))
    run_case(OpCase("pool2d", {"X": x},
                    attrs={"pooling_type": "max", "ksize": [2, 2],
                           "strides": [2, 2], "paddings": [0, 0]},
                    ref=lambda X, **kw: X.reshape(
                        2, 3, 2, 2, 2, 2).max(5).max(3)[:, :, :2, :2]
                    if False else _pool_ref(X, "max"),
                    grad=["X"], grad_rtol=8e-2))
    run_case(OpCase("pool2d", {"X": x},
                    attrs={"pooling_type": "avg", "ksize": [2, 2],
                           "strides": [2, 2], "paddings": [0, 0]},
                    ref=lambda X, **kw: _pool_ref(X, "avg"),
                    grad=["X"], name="pool2d_avg"))
    # conv2d_transpose: verify via adjointness on tiny shapes
    run_case(OpCase("conv2d_transpose",
                    {"Input": R(22).rand(1, 2, 3, 3).astype("float32"),
                     "Filter": R(23).rand(2, 2, 3, 3).astype("float32")},
                    outputs={"Output": 1},
                    attrs={"strides": [1, 1], "paddings": [0, 0],
                           "dilations": [1, 1], "groups": 1},
                    ref=None, grad=["Input"], grad_rtol=8e-2))


def _pool_ref(x, kind):
    n, c, h, w = x.shape
    oh, ow = h // 2, w // 2
    out = np.zeros((n, c, oh, ow), "float32")
    for i in range(oh):
        for j in range(ow):
            win = x[:, :, 2 * i:2 * i + 2, 2 * j:2 * j + 2]
            out[:, :, i, j] = win.max((2, 3)) if kind == "max" \
                else win.mean((2, 3))
    return out


def test_normalization_ops():
    x = R(24).rand(2, 6, 4).astype("float32")
    scale = R(25).rand(4).astype("float32")
    bias = R(26).rand(4).astype("float32")

    def ln_ref(X, Scale, Bias, epsilon, begin_norm_axis):
        m = X.mean(-1, keepdims=True)
        v = X.var(-1, keepdims=True)
        y = (X - m) / np.sqrt(v + epsilon) * Scale + Bias
        return {"Y": y}

    run_case(OpCase("layer_norm",
                    {"X": x.reshape(12, 4), "Scale": scale,
                     "Bias": bias},
                    outputs={"Y": 1, "Mean": 1, "Variance": 1},
                    attrs={"epsilon": 1e-5, "begin_norm_axis": 1},
                    ref=ln_ref, grad=["X", "Scale", "Bias"],
                    rtol=1e-4, atol=1e-5))

    def rms_ref(X, Scale, epsilon):
        return X / np.sqrt((X ** 2).mean(-1, keepdims=True)
                           + epsilon) * Scale

    run_case(OpCase("rms_norm", {"X": x.reshape(12, 4), "Scale": scale},
                    outputs={"Y": 1}, attrs={"epsilon": 1e-6},
                    ref=lambda **kw: {"Y": rms_ref(**kw)},
                    grad=["X", "Scale"], rtol=1e-4, atol=1e-5))

    xc = R(27).rand(2, 4, 3, 3).astype("float32")

    def bn_test_ref(X, Scale, Bias, Mean, Variance, epsilon, momentum,
                    is_test):
        y = (X - Mean[None, :, None, None]) / np.sqrt(
            Variance[None, :, None, None] + epsilon) \
            * Scale[None, :, None, None] + Bias[None, :, None, None]
        return {"Y": y}

    mean = R(28).rand(4).astype("float32")
    var = R(29).uniform(0.5, 1.5, 4).astype("float32")
    run_case(OpCase("batch_norm",
                    {"X": xc, "Scale": scale, "Bias": bias,
                     "Mean": mean, "Variance": var},
                    outputs={"Y": 1, "MeanOut": 1, "VarianceOut": 1,
                             "SavedMean": 1, "SavedVariance": 1},
                    attrs={"epsilon": 1e-5, "momentum": 0.9,
                           "is_test": True},
                    ref=bn_test_ref, rtol=1e-4, atol=1e-5))

    def gn_ref(X, Scale, Bias, epsilon, groups):
        n, c, h, w = X.shape
        g = X.reshape(n, groups, c // groups, h, w)
        m = g.mean((2, 3, 4), keepdims=True)
        v = g.var((2, 3, 4), keepdims=True)
        y = ((g - m) / np.sqrt(v + epsilon)).reshape(n, c, h, w)
        return {"Y": y * Scale[None, :, None, None]
                + Bias[None, :, None, None]}

    run_case(OpCase("group_norm",
                    {"X": xc, "Scale": scale, "Bias": bias},
                    outputs={"Y": 1, "Mean": 1, "Variance": 1},
                    attrs={"epsilon": 1e-5, "groups": 2},
                    ref=gn_ref, grad=["X"], rtol=1e-4, atol=1e-5))

    def in_ref(X, Scale, Bias, epsilon):
        m = X.mean((2, 3), keepdims=True)
        v = X.var((2, 3), keepdims=True)
        y = (X - m) / np.sqrt(v + epsilon)
        return {"Y": y * Scale[None, :, None, None]
                + Bias[None, :, None, None]}

    run_case(OpCase("instance_norm",
                    {"X": xc, "Scale": scale, "Bias": bias},
                    outputs={"Y": 1, "SavedMean": 1, "SavedVariance": 1},
                    attrs={"epsilon": 1e-5},
                    ref=in_ref, grad=["X"], rtol=1e-4, atol=1e-5))


def test_dropout_modes():
    x = np.ones((50, 50), "float32")
    got = check_forward(OpCase(
        "dropout", {"X": x}, outputs={"Out": 1, "Mask": 1},
        attrs={"dropout_prob": 0.3, "is_test": True,
               "dropout_implementation": "upscale_in_train"}))
    np.testing.assert_allclose(np.asarray(got[0]), x)  # test mode: identity
    got = check_forward(OpCase(
        "dropout", {"X": x}, outputs={"Out": 1, "Mask": 1},
        attrs={"dropout_prob": 0.3, "is_test": False,
               "dropout_implementation": "upscale_in_train"}))
    out = np.asarray(got[0])
    kept = out != 0
    assert abs(kept.mean() - 0.7) < 0.08
    np.testing.assert_allclose(out[kept], 1 / 0.7, rtol=1e-5)


def test_rope_op():
    x = R(30).rand(1, 2, 4, 8).astype("float32")  # [B,H,S,D]

    def rope_ref(X, base, position_offset):
        b, h, s, d = X.shape
        half = d // 2
        inv = 1.0 / (base ** (np.arange(half) / half))
        t = np.arange(s)[:, None] * inv[None, :]
        cos, sin = np.cos(t), np.sin(t)
        x1, x2 = X[..., :half], X[..., half:]
        return np.concatenate([x1 * cos - x2 * sin,
                               x2 * cos + x1 * sin], axis=-1)

    run_case(OpCase("rope", {"X": x},
                    attrs={"base": 10000.0, "position_offset": 0},
                    ref=rope_ref, grad=["X"], rtol=1e-4, atol=1e-5))


# ---------------------------------------------------------------------------
# optimizer ops (single step vs numpy)
# ---------------------------------------------------------------------------
def test_sgd_op():
    p = _A.copy()
    g = _B.copy()
    lr = np.array([0.1], "float32")
    run_case(OpCase("sgd", {"Param": p, "Grad": g, "LearningRate": lr},
                    outputs={"ParamOut": 1},
                    ref=lambda Param, Grad, LearningRate: {
                        "ParamOut": Param - 0.1 * Grad}))


def test_momentum_op():
    p, g = _A.copy(), _B.copy()
    v = np.zeros_like(p)
    lr = np.array([0.1], "float32")
    run_case(OpCase("momentum",
                    {"Param": p, "Grad": g, "Velocity": v,
                     "LearningRate": lr},
                    outputs={"ParamOut": 1, "VelocityOut": 1},
                    attrs={"mu": 0.9},
                    ref=lambda Param, Grad, Velocity, LearningRate, mu: {
                        "VelocityOut": mu * Velocity + Grad,
                        "ParamOut": Param - 0.1 * (mu * Velocity + Grad)}))


def test_adam_op():
    p, g = _A.copy(), _B.copy()
    m = np.full_like(p, 0.1)
    v = np.full_like(p, 0.2)
    lr = np.array([0.01], "float32")
    b1p = np.array([0.9], "float32")
    b2p = np.array([0.999], "float32")

    def ref(Param, Grad, Moment1, Moment2, LearningRate, Beta1Pow,
            Beta2Pow, beta1, beta2, epsilon):
        # reference adam_op.h: beta pows hold beta^t for the current step
        m2 = beta1 * Moment1 + (1 - beta1) * Grad
        v2 = beta2 * Moment2 + (1 - beta2) * Grad * Grad
        lr_t = 0.01 * np.sqrt(1 - Beta2Pow) / (1 - Beta1Pow)
        return {"ParamOut": Param - lr_t * m2 / (
                    np.sqrt(v2) + epsilon * np.sqrt(1 - Beta2Pow)),
                "Moment1Out": m2, "Moment2Out": v2}

    run_case(OpCase("adam",
                    {"Param": p, "Grad": g, "Moment1": m, "Moment2": v,
                     "LearningRate": lr, "Beta1Pow": b1p,
                     "Beta2Pow": b2p},
                    outputs={"ParamOut": 1, "Moment1Out": 1,
                             "Moment2Out": 1, "Beta1PowOut": 1,
                             "Beta2PowOut": 1},
                    attrs={"beta1": 0.9, "beta2": 0.999,
                           "epsilon": 1e-8},
                    ref=ref, rtol=1e-4, atol=1e-5))


def test_adagrad_op():
    p, g = _A.copy(), _B.copy()
    mom = np.full_like(p, 0.3)
    lr = np.array([0.1], "float32")
    run_case(OpCase("adagrad",
                    {"Param": p, "Grad": g, "Moment": mom,
                     "LearningRate": lr},
                    outputs={"ParamOut": 1, "MomentOut": 1},
                    attrs={"epsilon": 1e-6},
                    ref=lambda Param, Grad, Moment, LearningRate,
                    epsilon: {
                        "MomentOut": Moment + Grad * Grad,
                        "ParamOut": Param - 0.1 * Grad / (np.sqrt(
                            Moment + Grad * Grad) + epsilon)},
                    rtol=1e-4, atol=1e-5))


# ---------------------------------------------------------------------------
# coverage gate
# ---------------------------------------------------------------------------
# ops exercised by this file (directly above)
COVERED = (set(UNARY) | set(BINARY) | set(COMPARE) | set(LOGICAL) | {
    "leaky_relu", "prelu", "scale", "clip", "assign", "pow",
    "logical_not",
    "share_data", "cast", "logsumexp", "maxout",
    "isfinite_v2", "isinf_v2", "isnan_v2",
    "matmul", "matmul_v2", "mul", "bmm", "dot",
    "reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
    "reduce_prod", "reduce_all", "reduce_any", "mean", "max", "min",
    "sum", "squared_l2_norm", "cumsum", "norm", "p_norm", "clip_by_norm",
    "reshape", "reshape2", "transpose", "transpose2", "concat", "split",
    "stack", "unstack", "squeeze", "squeeze2", "unsqueeze", "unsqueeze2",
    "tril_triu", "meshgrid", "cumprod", "nearest_interp",
    "bilinear_interp", "pixel_shuffle",
    "flatten", "flatten2", "flatten_contiguous_range", "slice",
    "strided_slice", "pad", "tile", "expand", "expand_v2", "flip",
    "roll", "shape", "gather", "gather_nd", "index_select", "scatter",
    "scatter_nd_add", "take_along_axis", "where", "lookup_table",
    "lookup_table_v2", "embedding", "arg_max", "arg_min", "argsort",
    "top_k", "top_k_v2", "one_hot", "one_hot_v2", "label_smooth",
    "fill_constant", "fill_any_like", "fill_zeros_like", "assign_value",
    "eye", "linspace", "range", "gaussian_random", "uniform_random",
    "randint", "randperm", "bernoulli", "truncated_gaussian_random",
    "softmax", "log_softmax", "cross_entropy",
    "softmax_with_cross_entropy", "bce_loss",
    "sigmoid_cross_entropy_with_logits", "mse_loss", "huber_loss",
    "smooth_l1_loss", "kldiv_loss", "accuracy",
    "conv2d", "depthwise_conv2d", "conv2d_transpose", "pool2d",
    "layer_norm", "rms_norm", "batch_norm", "group_norm",
    "instance_norm", "dropout", "rope",
    "sgd", "momentum", "adam", "adagrad",
})

# every other registered op must appear here, with the test that covers it
SKIP = {
    # collectives: numerically tested on the virtual 8-device mesh
    **{op: "tests/test_fleet_collective.py" for op in [
        "c_allgather", "c_allreduce_max", "c_allreduce_min",
        "c_allreduce_prod", "c_allreduce_sum", "c_broadcast", "c_concat",
        "c_identity", "c_reduce_max", "c_reduce_min", "c_reduce_sum",
        "c_reducescatter", "c_split", "barrier"]},
    **{op: "no-op stream/init stubs (XLA owns ordering); asserted "
       "harmless in tests/test_fleet_collective.py" for op in [
           "c_comm_init", "c_comm_init_all", "c_gen_nccl_id",
           "c_sync_calc_stream", "c_sync_comm_stream", "c_wait_comm",
           "c_wait_compute"]},
    "send_v2": "tests/test_pipeline_pp.py (p2p pairing inside shard_map)",
    "recv_v2": "tests/test_pipeline_pp.py",
    # io: roundtrip-tested
    "save": "tests/test_io.py", "load": "tests/test_io.py",
    "save_combine": "tests/test_io.py",
    "load_combine": "tests/test_io.py",
    # control flow: trajectory-tested
    "while": "tests/test_backward_training.py (while_loop training)",
    "increment": "in-place loop-counter op; exercised by while-loop "
                 "tests (tests/test_backward_training.py)",
    "cond2": "tests/test_backward_training.py",
    "conditional_block": "tests/test_backward_training.py",
    # fused attention: parity + grad vs unfused in test_attention
    "flash_attention": "tests/test_attention.py (fwd+grad vs unfused)",
    "flash_attention_qkv": "tests/test_attention.py (packed vs unfused)",
    "beam_search": "tests/test_beam_search.py (finished semantics)",
    "beam_search_decode": "tests/test_beam_search.py (padding/lengths)",
    "gather_tree": "tests/test_beam_search.py (vs reference loop)",
    "linear_chain_crf": "tests/test_crf_ctc.py (brute-force + finite diff)",
    "crf_decoding": "tests/test_crf_ctc.py (viterbi vs brute force)",
    "warpctc": "tests/test_crf_ctc.py (alignment enum + finite diff)",
    "nce": "tests/test_crf_ctc.py (word2vec training smoke)",
    "hierarchical_sigmoid": "tests/test_crf_ctc.py (manual tree ref)",
    "addmm": "tests/test_longtail_ops.py",
    "mv": "tests/test_longtail_ops.py",
    "minus": "tests/test_longtail_ops.py",
    "allclose": "tests/test_longtail_ops.py",
    "l1_norm": "tests/test_longtail_ops.py",
    "squared_l2_distance": "tests/test_longtail_ops.py",
    "size": "tests/test_longtail_ops.py",
    "shard_index": "tests/test_longtail_ops.py",
    "multiplex": "tests/test_longtail_ops.py",
    "unbind": "tests/test_longtail_ops.py",
    "reverse": "tests/test_longtail_ops.py",
    "cos_sim": "tests/test_longtail_ops.py",
    "log_loss": "tests/test_longtail_ops.py",
    "selu": "tests/test_longtail_ops.py",
    "conv_shift": "tests/test_longtail_ops.py",
    # round-5 catalog batches
    **{op: "tests/test_interp_pool_ops.py (loop numpy refs + FD grads)"
       for op in [
           "linear_interp", "linear_interp_v2", "bicubic_interp",
           "bicubic_interp_v2", "trilinear_interp", "trilinear_interp_v2",
           "max_pool2d_with_index", "max_pool3d_with_index", "unpool"]},
    **{op: "tests/test_misc2_ops.py" for op in [
        "space_to_depth", "crop", "crop_tensor", "pad_constant_like",
        "expand_as", "expand_as_v2", "frobenius_norm", "cross_entropy2",
        "where_index", "coalesce_tensor", "inplace_abn",
        "sigmoid_focal_loss", "shuffle_batch", "sample_logits",
        "positive_negative_pair", "hash"]},
    **{op: "tests/test_rnn_fused_ops.py (step-loop refs + FD grads)"
       for op in ["lstm", "lstmp", "gru", "rnn", "cudnn_lstm"]},
    **{op: "tests/test_catalog_ops.py" for op in [
        "sequence_reshape", "sequence_scatter", "lod_reset",
        "lod_tensor_to_array", "array_to_lod_tensor",
        "split_lod_tensor", "merge_lod_tensor", "shrink_rnn_memory",
        "merge_selected_rows", "get_tensor_from_selected_rows",
        "split_ids", "merge_ids", "select_input", "select_output",
        "batch_fc", "rank_attention", "tree_conv", "var_conv_2d",
        "pyramid_hash", "filter_by_instag", "prroi_pool",
        "correlation", "chunk_eval", "attention_lstm", "bilateral_slice",
        "depthwise_conv2d_transpose", "quantize",
        "dequantize",
        "requantize", "proximal_adagrad", "dgc", "dgc_clip_by_norm",
        "multihead_matmul", "skip_layernorm",
        "fused_embedding_eltwise_layernorm"]},
    "split_selected_rows": "tests/test_selected_rows.py "
                           "(lowering-level shard test)",
    "sync_batch_norm": "tests/test_sync_batch_norm.py (8-mesh parity "
                       "vs full-batch BN + training)",
    **{op: "tests/test_jit_save.py" for op in [
        "py_func", "run_program", "distributed_lookup_table"]},
    "moe_ffn": "tests/test_moe.py (numpy Switch ref, ep8 all_to_all "
               "parity, capacity drop, training)",
    "global_norm_sq": "tests/test_lr_clip_ema.py (fused-clip parity "
                      "vs the per-grad default)",
    **{op: "tests/test_fleet_collective.py (8-mesh numeric)" for op in [
        "allreduce", "broadcast", "c_reduce_prod", "c_scatter"]},
    "add_position_encoding": "tests/test_longtail_ops.py",
    "cvm": "tests/test_longtail_ops.py",
    "hinge_loss": "tests/test_longtail_ops.py",
    "modified_huber_loss": "tests/test_longtail_ops.py",
    "margin_rank_loss": "tests/test_longtail_ops.py",
    "rank_loss": "tests/test_longtail_ops.py",
    "bpr_loss": "tests/test_longtail_ops.py",
    "nll_loss": "tests/test_longtail_ops.py",
    "teacher_student_sigmoid_loss": "tests/test_longtail_ops.py",
    "center_loss": "tests/test_longtail_ops.py",
    "fill_constant_batch_size_like": "tests/test_longtail_ops.py",
    "uniform_random_batch_size_like": "tests/test_longtail_ops.py",
    "gaussian_random_batch_size_like": "tests/test_longtail_ops.py",
    "empty": "tests/test_longtail_ops.py",
    "fill": "tests/test_longtail_ops.py",
    "is_empty": "tests/test_longtail_ops.py",
    "sampling_id": "tests/test_longtail_ops.py",
    "mean_iou": "tests/test_longtail_ops.py",
    "edit_distance": "tests/test_longtail_ops.py",
    "unique_with_counts": "tests/test_longtail_ops.py",
    "conv3d": "tests/test_longtail_ops.py",
    "conv3d_transpose": "tests/test_longtail_ops.py",
    "pool3d": "tests/test_longtail_ops.py",
    "pad2d": "tests/test_longtail_ops.py",
    "pad3d": "tests/test_longtail_ops.py",
    "lrn": "tests/test_longtail_ops.py",
    "data_norm": "tests/test_longtail_ops.py",
    "spectral_norm": "tests/test_longtail_ops.py",
    "shuffle_channel": "tests/test_longtail_ops.py",
    "temporal_shift": "tests/test_longtail_ops.py",
    "row_conv": "tests/test_longtail_ops.py",
    "im2sequence": "tests/test_longtail_ops.py",
    "bilinear_tensor_product": "tests/test_longtail_ops.py",
    "fsp": "tests/test_longtail_ops.py",
    "partial_concat": "tests/test_longtail_ops.py",
    "partial_sum": "tests/test_longtail_ops.py",
    "psroi_pool": "tests/test_longtail_ops.py",
    "deformable_conv": "tests/test_longtail_ops.py",
    "deformable_conv_v1": "tests/test_longtail_ops.py",
    "segment_pool": "tests/test_longtail_ops.py",
    "gru_unit": "tests/test_longtail_ops.py",
    "lstm_unit": "tests/test_longtail_ops.py",
    "auc": "tests/test_longtail_ops.py",
    "sequence_conv": "tests/test_longtail_ops.py",
    "sequence_expand": "tests/test_longtail_ops.py",
    "sequence_pad": "tests/test_longtail_ops.py",
    "sequence_unpad": "tests/test_longtail_ops.py",
    "sequence_concat": "tests/test_longtail_ops.py",
    "sequence_slice": "tests/test_longtail_ops.py",
    "sequence_erase": "tests/test_longtail_ops.py",
    "sequence_enumerate": "tests/test_longtail_ops.py",
    # amp machinery: inf-recovery trajectories
    "check_finite_and_unscale": "tests/test_round2_fixes.py (amp)",
    "update_loss_scaling": "tests/test_round2_fixes.py (amp)",
    # optimizer long tail: convergence-tested end to end
    **{op: "tests/test_backward_training.py (optimizer trajectories)"
       for op in ["adamax", "adadelta", "adamw", "rmsprop",
                  "decayed_adagrad", "ftrl", "dpsgd", "lamb",
                  "lars_momentum", "proximal_gd"]},
    "dgc_momentum": "tests/test_meta_optimizers.py (DGC trajectory)",
    "average_accumulates": "tests/test_lr_clip_ema.py (ModelAverage)",
    # dynamic output shapes: cannot run under a static-shape jit; the
    # lowering pads/masks — exercised via layers tests
    "print": "tests/test_observability.py (passthrough, grad, output)",
    "bilinear_interp_v2": "same lowering as bilinear_interp (tested)",
    "nearest_interp_v2": "same lowering as nearest_interp (tested)",
    **{op: "tests/test_quant.py (fake-quant semantics + STE grads)"
       for op in ["fake_quantize_dequantize_abs_max",
                  "fake_quantize_dequantize_moving_average_abs_max",
                  "fake_channel_wise_quantize_dequantize_abs_max"]},
    **{op: "tests/test_sequence.py (masked refs vs numpy, training)"
       for op in ["sequence_mask", "sequence_pool", "sequence_softmax",
                  "sequence_reverse", "sequence_expand_as",
                  "write_to_array", "read_from_array", "lstm_rnn",
                  "gru_rnn"]},
    "cached_attention":
        "tests/test_generation.py (cached decode vs the uncached "
        "forward: conftest.assert_logits_match)",
    **{op: "tests/test_paged_generation.py (scatter/gather round trip "
       "+ trash-page redirect unit; both via paged decode vs the "
       "uncached forward: conftest.assert_logits_match)" for op in [
           "kv_pool_write", "kv_pool_gather"]},
    "paged_decode_attention":
        "tests/test_paged_decode_attention.py (op == the gather + "
        "cached_attention triple bit for bit off the TPU; the Pallas "
        "kernel vs a float32 'highest' reference under interpret mode)",
    "chunk_attention":
        "tests/test_command_a_plus.py (op == cached_attention bit for bit "
        "off the TPU; the Pallas kernel vs the einsum under interpret mode "
        "at base 0, mid-prompt and past the window; through the chunked "
        "engine vs the benchmark's reference)",
    **{op: "tests/test_gigachat35.py (absorbed against expanded on the "
       "same latent rows; both Pallas kernels vs einsums under interpret "
       "mode; through the paged engine vs the benchmark's reference)"
       for op in ["latent_prefill_attention", "latent_decode_attention"]},
    "latent_chunk_attention":
        "tests/test_deepseek_v2.py (the op's einsum lowering vs the Pallas "
        "kernel under interpret mode at base 0, mid-prompt and at the "
        "view's end, NaN behind the chunk; chunks vs the single-shot "
        "prefill and vs the benchmark's reference through the engine)",
    "block_begin":
        "tests/test_block_diffusion.py (with block_unmask against the "
        "benchmark reference's host loop: fresh slots, quotas, ties)",
    "block_unmask":
        "tests/test_block_diffusion.py (against the benchmark reference's "
        "host loop, and inside the engine against reference.generate)",
    **{op: "tests/test_slot_state.py (a float64 sum over three shifted "
       "copies: prompts of 1, 2, 3 tokens and a padded rung, the step "
       "after them, a dead row, the trash row; inside the engine against "
       "the benchmark reference's full forward)" for op in [
           "short_conv", "short_conv_tail", "slot_state_write",
           "short_conv_step"]},
    **{op: "tests/test_gated_delta.py (the recurrence token by token in "
       "float64: whole and broken chunks, an initial state, NaN behind "
       "valid, live rows only and the trash row; the Pallas kernels in "
       "interpret mode; inside the engine against the benchmark "
       "reference's full forward)" for op in [
           "gated_delta_chunk", "gated_delta_step"]},
    **{op: "tests/test_granite_hybrid.py (the recurrence token by token "
       "in float64: valid inside a chunk, at its edge and behind a whole "
       "chunk, an initial state, NaN behind valid, live rows only and the "
       "trash row; the Pallas kernels in interpret mode; inside the "
       "engine against the benchmark reference's full forward)" for op in [
           "ssd_chunk", "ssd_step"]},
    **{op: "tests/test_dense_rows.py (against the plain mul and the plain "
       "SwiGLU: valid at, beside and inside a segment's edge, a rung that "
       "is no multiple of the segment, zeros behind; inside "
       "build_llama_prefill against the same program without it)" for op in [
           "mul_valid_rows", "swiglu_valid_rows"]},
    "moe_routed_ffn":
        "tests/test_window_moe.py (routing, dropless counts and the "
        "grouped matmul vs a plain float64 loop; the op inside the "
        "engine vs the uncached forward and the benchmark's reference); "
        "its attribute zero_experts (identity experts under a softmax "
        "router with a selection bias): tests/test_longcat_flash.py (a "
        "written-out loop, identity-only and real-only routers, rows "
        "behind valid, the shares of a router of E + Z)",
    "masked_select": "dynamic shape; covered via layers.masked_select "
                     "usage in tests/test_models.py",
    "unique": "dynamic shape; lowering returns padded/size pair",
    **{op: "tests/test_linalg_misc.py (forward vs numpy refs + "
       "finite-difference grads)" for op in [
           "cholesky", "inverse", "kron", "trace", "cross", "dist",
           "diag", "diag_v2", "diag_embed", "index_sample",
           "affine_channel", "affine_grid", "grid_sampler", "unfold",
           "histogram", "multinomial"]},
    **{op: "tests/test_detection.py (forward vs numpy refs; "
       "iou_similarity/roi_align grad-checked there)" for op in [
           "iou_similarity", "box_coder", "prior_box",
           "anchor_generator", "yolo_box", "box_clip",
           "bipartite_match", "roi_align", "roi_pool",
           "multiclass_nms", "density_prior_box", "target_assign",
           "mine_hard_examples", "generate_proposals", "matrix_nms",
           "distribute_fpn_proposals", "collect_fpn_proposals",
           "yolov3_loss"]},
}


def test_registry_coverage_complete():
    """Every registered op is either tested above or skip-listed with a
    pointer to the test that covers it (reference op_test coverage
    policy: tools/check_op_test_coverage)."""
    from paddle_tpu.ops.registry import all_registered_ops
    # auto-derived <type>_grad entries register lazily while other test
    # modules build backwards; the gate governs forward ops
    ops = {o for o in all_registered_ops() if not o.endswith("_grad")}
    untracked = ops - COVERED - set(SKIP)
    assert not untracked, f"ops with no test or skip reason: " \
                          f"{sorted(untracked)}"
    stale = (COVERED | set(SKIP)) - ops
    assert not stale, f"stale coverage entries: {sorted(stale)}"
    overlap = COVERED & set(SKIP)
    assert not overlap, f"both covered and skipped: {sorted(overlap)}"
