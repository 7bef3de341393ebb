"""Operator registry: shape inference + JAX lowering + grad derivation.

TPU-native replacement for the reference's op registry / kernel-dispatch
machinery (framework/op_registry.h:101,256; framework/operator.cc:1017,1141).

Architectural inversion: the reference keeps a global (op, place, dtype,
layout) -> kernel map consulted at *every step* per op.  Here each op type
registers:

  * ``infer``  -- compile-time shape/dtype inference (reference InferShape),
                  run at op-append time so graphs carry static shapes.
  * ``lower``  -- a pure function from a LowerContext (name->traced jax value
                  environment) to output values.  The Executor composes the
                  lowerings of a whole block into ONE function traced by JAX
                  and compiled by XLA; kernel selection / data transfer /
                  per-op dispatch all disappear into the compiler.
  * ``grad``   -- how to build the backward ops for framework.backward:
                  'auto' (default) emits a generic ``<type>_grad`` op whose
                  lowering computes jax.vjp of the forward lowering (XLA CSE
                  removes the recomputation, except of custom calls: two
                  ``tpu_custom_call``s on the same operands both run, which
                  is why flash_attention / flash_attention_qkv register a
                  ``<type>_grad`` lowering of their own, in
                  ops/attention_ops.py, that reads the forward's saved
                  output and softmax statistic); a callable builds custom
                  grad op descs (used where semantics demand it, e.g. ops
                  whose grad must reuse a saved random mask).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..errors import (NotFoundError, UnimplementedError,
                      op_error_context)
from ..framework.core import (Block, Operator, Variable, convert_dtype,
                              dtype_to_np, grad_var_name)

__all__ = [
    "OpDef", "register_op", "get_op_def", "infer_op_shape", "LowerContext",
    "lower_op", "all_registered_ops",
]


class LowerContext:
    """Environment for lowering a block: var name -> traced JAX value.

    Also carries the PRNG base key (TPU-first randomness: stateless
    counter-based keys folded per-op, replacing the reference's cuRAND
    stateful generators) and the mesh/test-mode flags.
    """

    def __init__(self, block: Block, env: Dict[str, Any], base_key=None,
                 is_test: bool = False, mesh=None, amp=None):
        self.block = block
        self.env = env
        self.base_key = base_key
        self.is_test = is_test
        self.mesh = mesh
        # amp: None or {"dtype": "bfloat16", "white": set, "black": set} —
        # lowering-level autocast (see _lower_with_amp). The reference
        # rewrites the ProgramDesc to fp16 (contrib/mixed_precision/
        # fp16_utils.py:193 rewrite_program); casting at lowering time is
        # equivalent under XLA (casts fuse into the matmul/conv kernels)
        # and keeps fp32 master params in the scope for free.
        self.amp = amp

    def get(self, name: str):
        if name not in self.env:
            raise KeyError(
                f"variable {name!r} has no value during lowering; "
                f"known: {sorted(self.env)[:20]}...")
        return self.env[name]

    def get_input(self, op: Operator, slot: str):
        name = op.single_input(slot)
        return None if name is None else self.get(name)

    def get_inputs(self, op: Operator, slot: str) -> List[Any]:
        return [self.get(n) for n in op.input(slot)]

    def set(self, name: str, value):
        self.env[name] = value

    def set_output(self, op: Operator, slot: str, value):
        name = op.single_output(slot)
        if name is not None:
            self.env[name] = value

    def set_outputs(self, op: Operator, slot: str, values: Sequence[Any]):
        for n, v in zip(op.output(slot), values):
            self.env[n] = v

    def rng(self, op: Operator):
        """Deterministic per-op PRNG key.

        Folds the op's build-time seed id into the step key so that
        re-lowering the same op (e.g. inside its auto-derived grad's vjp
        recomputation) yields the *same* randomness -- this is what makes
        'auto' grads of stochastic ops (dropout) correct.
        """
        import jax
        if self.base_key is None:
            raise RuntimeError("no PRNG key available in this context")
        return jax.random.fold_in(self.base_key, op.attr("__op_seed__", 0))

    def var_shape(self, name: str):
        return self.block.var(name).shape

    def var_dtype(self, name: str):
        return self.block.var(name).dtype


class OpDef:
    def __init__(self, type: str,
                 infer: Optional[Callable[[Operator, Block], None]] = None,
                 lower: Optional[Callable[[LowerContext, Operator], None]] = None,
                 grad=None,
                 stateful_outputs: Sequence[str] = ()):
        self.type = type
        self.infer = infer
        self.lower = lower
        # grad: None = non-differentiable; 'auto' = vjp of forward lowering;
        # callable(fwd_op, block, helper) -> list of grad op specs.
        self.grad = grad
        # output slots aliasing an input (in-place update semantics, e.g.
        # optimizer ParamOut); informs executors which vars are state.
        self.stateful_outputs = tuple(stateful_outputs)


_REGISTRY: Dict[str, OpDef] = {}


def register_op(type: str, *, infer=None, lower=None, grad="auto",
                stateful_outputs=()):
    """Register an op type.  Usable directly or as a decorator on `lower`."""
    if lower is None:
        def deco(fn):
            register_op(type, infer=infer, lower=fn, grad=grad,
                        stateful_outputs=stateful_outputs)
            return fn
        return deco
    _REGISTRY[type] = OpDef(type, infer=infer, lower=lower, grad=grad,
                            stateful_outputs=stateful_outputs)
    return _REGISTRY[type]


def get_op_def(type: str) -> OpDef:
    if type not in _REGISTRY:
        raise KeyError(f"op type {type!r} is not registered "
                       f"({len(_REGISTRY)} ops known)")
    return _REGISTRY[type]


def has_op(type: str) -> bool:
    return type in _REGISTRY


def all_registered_ops() -> List[str]:
    return sorted(_REGISTRY)


# global monotonically increasing op seed for stateless per-op randomness
_OP_SEED = [0]


def reset_op_seed(value: int = 0):
    """Reset the per-op randomness counter (test isolation / building two
    programs that must draw identical init randomness)."""
    _OP_SEED[0] = value


def infer_op_shape(op: Operator, block: Block):
    _OP_SEED[0] += 1
    op.attrs.setdefault("__op_seed__", _OP_SEED[0])
    opdef = _REGISTRY.get(op.type)
    if opdef is None:
        raise NotFoundError(f"cannot append unregistered op {op.type!r}")
    if opdef.infer is not None:
        with op_error_context(op, block, phase="shape inference"):
            opdef.infer(op, block)


_AMP_CASTABLE = ("float16", "bfloat16", "float32")


def _lower_with_amp(ctx: LowerContext, opdef: "OpDef", op: Operator):
    """Autocast wrapper: white-list ops see low-precision float inputs,
    black-list ops see float32; env bindings are restored afterwards so
    other consumers keep the original precision.

    A grad op (one whose desc carries ``__fwd_outputs__``) autocasts like
    its forward, with one rule of its own: of its inputs, the forward's
    inputs and the cotangents are cast, and what the forward saved (its
    outputs) is NOT: it reaches the grad lowering in the precision the
    forward emitted it in.  The auto-grad lowering never reads those; an
    explicit grad lowering that does (flash_attention[_qkv]_grad: a bf16
    ``Out`` beside a float32 softmax statistic) sees them as saved, and
    casts for itself what it wants cast."""
    amp = ctx.amp
    target = None
    if amp is not None:
        # grad ops autocast like their forward: without this the whole
        # backward (2/3 of training FLOPs) runs f32 matmuls off the f32
        # master weights — measured 0.21 -> 0.35+ MFU on the bf16 BERT
        # bench when the backward joined the white list
        base = op.type[:-5] if op.type.endswith("_grad") else op.type
        if base in amp["white"]:
            target = amp["dtype"]
        elif base in amp["black"]:
            target = "float32"
    if target is None:
        opdef.lower(ctx, op)
        return
    # a var that is forward input and output at once (in place) is cast
    kept = {n for slot in op.attr("__fwd_outputs__", ())
            for n in op.input(slot)}
    kept.difference_update(
        n for ns in op.attr("__fwd_inputs__", {}).values() for n in ns)
    saved = {}
    for name in op.input_arg_names():
        if name in kept:
            continue
        v = ctx.env.get(name)
        dt = str(getattr(v, "dtype", ""))
        if v is not None and dt in _AMP_CASTABLE and dt != target:
            saved[name] = v
            ctx.env[name] = v.astype(target)
    opdef.lower(ctx, op)
    for n, v in saved.items():
        ctx.env[n] = v


def lower_op(ctx: LowerContext, op: Operator):
    opdef = _REGISTRY.get(op.type)
    if opdef is None or opdef.lower is None:
        raise UnimplementedError(f"no lowering for op {op.type!r}")
    with op_error_context(op, getattr(ctx, "block", None),
                          phase="lowering"):
        _lower_with_amp(ctx, opdef, op)


# ---------------------------------------------------------------------------
# Shared infer-shape helpers
# ---------------------------------------------------------------------------

def set_out(op: Operator, block: Block, slot: str, shape, dtype,
            **var_kwargs):
    """Create/refresh the output var's shape+dtype in the block."""
    for name in op.output(slot):
        v = block._find_var_recursive(name)
        if v is None:
            v = block.create_var(name=name)
        v.shape = tuple(int(s) for s in shape) if shape is not None else None
        v.dtype = convert_dtype(dtype)
        for k, val in var_kwargs.items():
            setattr(v, k, val)


def in_var(op: Operator, block: Block, slot: str) -> Variable:
    return block.var(op.single_input(slot))


def same_as_input(input_slot="X", output_slot="Out"):
    def infer(op: Operator, block: Block):
        x = in_var(op, block, input_slot)
        set_out(op, block, output_slot, x.shape, x.dtype)
    return infer


def broadcast_shapes(s1, s2, axis=-1):
    """Paddle-style broadcast: y's dims align to x starting at `axis`
    (reference operators/elementwise/elementwise_op_function.h); -1 means
    trailing alignment (numpy rule)."""
    s1, s2 = list(s1), list(s2)
    if len(s2) > len(s1):
        s1, s2 = s2, s1
    if axis == -1:
        axis = len(s1) - len(s2)
    padded = [1] * axis + s2 + [1] * (len(s1) - axis - len(s2))
    out = []
    for a, b in zip(s1, padded):
        if a == -1 or b == -1:
            out.append(-1)
        elif a == 1:
            out.append(b)
        elif b == 1 or a == b:
            out.append(a)
        else:
            raise ValueError(f"cannot broadcast shapes {s1} vs {s2}")
    return tuple(out)


# ---------------------------------------------------------------------------
# Auto-grad ("vjp of the forward lowering") machinery
# ---------------------------------------------------------------------------

def build_auto_grad_specs(fwd_op: Operator, block: Block,
                          no_grad_set: set) -> List[dict]:
    """Emit the generic ``<type>_grad`` op desc for `fwd_op`.

    Inputs: every forward input slot and output slot under its own name,
    plus ``<slot>@GRAD`` for each forward output.  Outputs: ``<slot>@GRAD``
    for each differentiable forward input.  Mirrors the reference's
    DefaultGradOpMaker (framework/grad_op_desc_maker.h).
    """
    inputs: Dict[str, List[str]] = {}
    for slot, names in fwd_op.inputs.items():
        inputs[slot] = list(names)
    for slot, names in fwd_op.outputs.items():
        inputs[slot] = list(names)
        inputs[slot + "@GRAD"] = [grad_var_name(n) for n in names]
    outputs: Dict[str, List[str]] = {}
    for slot, names in fwd_op.inputs.items():
        grads = []
        for n in names:
            v = block._find_var_recursive(n)
            differentiable = (
                v is not None and not v.stop_gradient and n not in no_grad_set
                and convert_dtype(v.dtype).startswith(("float", "bfloat")))
            grads.append(grad_var_name(n) if differentiable else "")
        if any(grads):
            outputs[slot + "@GRAD"] = grads
    if not outputs:
        return []
    attrs = dict(fwd_op.attrs)
    attrs["__fwd_type__"] = fwd_op.type
    attrs["__fwd_inputs__"] = {k: list(v) for k, v in fwd_op.inputs.items()}
    attrs["__fwd_outputs__"] = {k: list(v) for k, v in fwd_op.outputs.items()}
    # full-fidelity nested desc, ONLY when differentiating a grad op
    # (double backward): its own __fwd_* attrs would be clobbered by the
    # flat keys above.  Plain first-order grads skip the duplication.
    if fwd_op.type.endswith("_grad"):
        attrs["__fwd_desc__"] = dict(
            type=fwd_op.type,
            inputs={k: list(v) for k, v in fwd_op.inputs.items()},
            outputs={k: list(v) for k, v in fwd_op.outputs.items()},
            attrs=dict(fwd_op.attrs))
    return [dict(type=fwd_op.type + "_grad", inputs=inputs, outputs=outputs,
                 attrs=attrs)]


def _lower_auto_grad(ctx: LowerContext, gop: Operator):
    """Lowering for auto-derived ``<type>_grad`` ops: jax.vjp of fwd lower."""
    import jax
    import jax.numpy as jnp

    fwd_type = gop.attr("__fwd_type__")
    fwd_inputs: Dict[str, List[str]] = gop.attr("__fwd_inputs__")
    fwd_outputs: Dict[str, List[str]] = gop.attr("__fwd_outputs__")
    opdef = get_op_def(fwd_type)

    # Which (slot, idx) need grads, in a stable order.
    wanted: List[tuple] = []
    for gslot, gnames in gop.outputs.items():
        slot = gslot[:-len("@GRAD")]
        for i, gname in enumerate(gnames):
            if gname:
                wanted.append((slot, i, gname))

    diff_names: List[str] = []
    seen = set()
    for slot, i, _ in wanted:
        n = fwd_inputs[slot][i]
        if n not in seen:
            seen.add(n)
            diff_names.append(n)

    # Forward output order for cotangents.
    out_order: List[str] = []
    for slot, names in fwd_outputs.items():
        for n in names:
            if n not in out_order:
                out_order.append(n)

    const_env = {n: ctx.get(n)
                 for ns in fwd_inputs.values() for n in ns
                 if n not in seen}

    # Reconstruct a forward op object for re-lowering (pure; attrs carry the
    # original __op_seed__ so stochastic ops replay identically).  The
    # nested desc preserves a grad op's own __fwd_* attrs, which double
    # backward needs (grad-of-grad re-lowers the inner grad op).
    desc = gop.attr("__fwd_desc__")
    if desc is not None:
        fwd_attrs = dict(desc["attrs"])
    else:
        fwd_attrs = {k: v for k, v in gop.attrs.items()
                     if not k.startswith("__fwd_")}
    fwd_op = Operator(ctx.block, fwd_type, fwd_inputs, fwd_outputs, fwd_attrs)

    def fwd_fn(*diff_vals):
        env = dict(const_env)
        env.update(zip(diff_names, diff_vals))
        sub = LowerContext(ctx.block, env, base_key=ctx.base_key,
                           is_test=ctx.is_test, mesh=ctx.mesh, amp=ctx.amp)
        sub.axis_names = getattr(ctx, "axis_names", ())
        sub.ring_table = getattr(ctx, "ring_table", {})
        # lowerings that book what they lowered to, once a site, read this
        sub.relowered = True
        _lower_with_amp(sub, opdef, fwd_op)
        return tuple(env[n] for n in out_order)

    primals = tuple(ctx.get(n) for n in diff_names)
    out_vals, vjp_fn = jax.vjp(fwd_fn, *primals)

    # cotangent names were recorded in the op's <slot>@GRAD inputs at
    # build time — use them, not grad_var_name(), which reads the
    # *current* grad suffix (higher-order passes build under @GRAD2, ...)
    cot_name = {}
    for slot, names in fwd_outputs.items():
        for i, n in enumerate(names):
            gnames = gop.inputs.get(slot + "@GRAD", [])
            if i < len(gnames) and gnames[i]:
                cot_name[n] = gnames[i]
    cotangents = []
    for n, ov in zip(out_order, out_vals):
        g = ctx.env.get(cot_name.get(n, grad_var_name(n)))
        if g is None:
            g = jnp.zeros_like(ov)
        else:
            g = jnp.asarray(g, dtype=ov.dtype).reshape(jnp.shape(ov))
        cotangents.append(g)
    in_grads = vjp_fn(tuple(cotangents))
    grad_by_name = dict(zip(diff_names, in_grads))

    written = set()
    for slot, i, gname in wanted:
        src = fwd_inputs[slot][i]
        val = grad_by_name[src]
        if gname in written:
            # same fwd var feeds multiple slots of THIS op (e.g. x*x):
            # jax.vjp already summed all paths into grad_by_name[src] —
            # writing again would double-count
            continue
        # accumulate across DIFFERENT consumers of the fwd var
        if gname in ctx.env and gop.attr("__accumulate__", False):
            val = ctx.env[gname] + val
        ctx.env[gname] = val
        written.add(gname)


def infer_auto_grad(gop: Operator, block: Block):
    """Grad vars mirror the shape/dtype of their forward vars."""
    fwd_inputs: Dict[str, List[str]] = gop.attr("__fwd_inputs__")
    for gslot, gnames in gop.outputs.items():
        slot = gslot[:-len("@GRAD")]
        for i, gname in enumerate(gnames):
            if not gname:
                continue
            src = block.var(fwd_inputs[slot][i])
            v = block._find_var_recursive(gname)
            if v is None:
                v = block.create_var(name=gname)
            v.shape, v.dtype = src.shape, src.dtype


class _AutoGradDef(OpDef):
    pass


def ensure_grad_op_registered(fwd_type: str):
    gtype = fwd_type + "_grad"
    if gtype not in _REGISTRY:
        # grad='auto': a grad op is itself differentiable (vjp of its
        # vjp), which is what double backward walks through
        _REGISTRY[gtype] = _AutoGradDef(
            gtype, infer=infer_auto_grad, lower=_lower_auto_grad,
            grad="auto")
    return gtype
