"""Pallas TPU grouped matmul: ``rows`` [M, K], sorted by group, times
``weights`` [G, K, N], float32 at "highest" (``parallel/moe.py``
``grouped_matmul`` routes to it by shape and has the XLA formulation it is
held to).

The grid is (column block, visit).  A visit is one (row block, group) pair
that holds a row, in row order; the pairs come from the group offsets,
handed over by scalar prefetch (the scheme of JAX's ``megablox.gmm``): a
row block that straddles two groups is visited once a group and stores
under a row mask, a group without rows is never visited, so its weights
are never read, and the number of visits is the grid's own (dynamic)
extent.  A block of weights is a group's whole ``[K, tn]``: consecutive
visits of one group keep its block index, so the pipeline fetches it once
a column block however many row blocks the group spans, and rows are read
once a column block.  Rows past ``group_sizes.sum()`` are in no visit and
are left as they lie.

A visit multiplies a whole row block whatever part of it is the group's,
so a block far over a group's rows is mostly wasted passes, and one far
under them latches each 128 x 128 tile of the weights for too few rows.
On a v5e ``ROW_BLOCK`` = 64 rows won at every shape measured, from 3 rows
a group (a decode step: several groups share a block, a visit each) to
768, and the widest column block won with it (:func:`tiles`; PERF.md
section 6, PR 50, has the sweep: 32 rows lose up to 22%, 128 win nowhere
and lose 3-46%, 256 and 512 lose everywhere).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PRECISION = jax.lax.Precision.HIGHEST
LANES = 128                   # a column block is whole lane tiles, or all of N
ROW_BLOCK = 64                # rows a block: eight float32 sublane tiles
VMEM_LIMIT = 100 << 20        # of a v5e core's 128 MiB
WEIGHT_BLOCK_BYTES = 26 << 20  # a group's [K, tn], held twice by the pipeline


def tiles(m, k, n, scoped=False):
    """``(tm, tn)``, rows and columns a block, for ``[m, k]`` rows over
    groups of ``[k, n]``: ``ROW_BLOCK`` rows and the widest column block
    whose weights fit ``block_bytes(scoped)``; None for fewer rows than one
    block, or where no whole-lane-tile divisor of ``n`` fits."""
    if m < ROW_BLOCK:
        return None
    tn = next((t for t in (n, *range(n - n % LANES, 0, -LANES))
               if n % t == 0 and block_fits(k, t, scoped)), None)
    return tn and (ROW_BLOCK, tn)


def visits(group_sizes, m, tm):
    """``(offsets [G + 1], group [V], row block [V], visits)``: the (row
    block, group) pairs that hold a row, in row order, V = row blocks + G
    - 1 the most there can be; entries past ``visits`` repeat the last."""
    groups = group_sizes.shape[0]
    blocks_m = -(-m // tm)
    bound = blocks_m + groups - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    before = jnp.cumsum(spans) - spans
    group = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), spans,
                       total_repeat_length=bound)
    block = first[group] + jnp.arange(bound, dtype=jnp.int32) - before[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets, group, jnp.clip(block, 0, blocks_m - 1),
            spans.sum().astype(jnp.int32))


def _kernel(offsets_ref, group_ref, block_ref, rows_ref, w_ref, out_ref,
            *, tm):
    v = pl.program_id(1)
    g = group_ref[v]
    lo, hi = offsets_ref[g], offsets_ref[g + 1]
    acc = jax.lax.dot_general(
        rows_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        precision=PRECISION, preferred_element_type=jnp.float32)
    row = block_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, acc.shape, 0)
    out_ref[...] = jnp.where((row >= lo) & (row < hi), acc, out_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("tm", "tn", "scoped", "interpret"))
def grouped_matmul(rows, weights, group_sizes, *, tm, tn, scoped=False,
                   interpret=False):
    """``rows`` [M, K] sorted by group x ``weights`` [G, K, N] -> [M, N],
    float32; ``tm``, ``tn``, ``scoped``: :func:`tiles`, :func:`block_bytes`."""
    m, k = rows.shape
    groups, _, n = weights.shape
    offsets, group, block, n_visits = visits(group_sizes, m, tm)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, n_visits),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, o, g, b: (b[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, o, g, b: (g[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, o, g, b: (b[v], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=None if scoped else VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=4 * (m * k * (n // tn) + groups * k * n + m * n)),
        name="grouped_matmul_ragged-dot",
        interpret=interpret,
    )(offsets, group, block, rows, weights)


# A group's [K, tn] under XLA:TPU's default 16 MiB of scoped VMEM, held twice
SCOPED_BLOCK_BYTES = 6 << 20


def block_bytes(scoped):
    """The most a block of weights may take.  ``scoped``: the call stays
    inside the scoped VMEM every XLA:TPU operation gets by default and
    sets no ``vmem_limit_bytes``.  A limit over the default on ONE call
    of a program makes XLA lay out the scoped VMEM of every other
    operation again: a decode program of 64 slots whose four expert
    layers held such calls took a window for its 24,576-wide vocabulary
    product that XLA's own estimate puts 18 times slower, 2.5 ms a step
    where the parent's took under 1 (my chip run, PR 52); ``tiles``
    without ``scoped`` keeps the wide blocks PR 50 measured."""
    return SCOPED_BLOCK_BYTES if scoped else WEIGHT_BLOCK_BYTES


# ---------------------------------------------------------------------------
# The same product with an epilogue on its accumulator (PR 57), for a
# routed layer that gives no [N k, .] array an HBM pass of its own: the
# gate of the layer's first product (``act(gate) * up`` of the accumulator's
# two halves, stored half as wide, so ``h`` [M, 2I] is never written) and
# the routing weight of its second (a row's scale, a ``(tm, 1)`` block
# beside the rows).  ``_kernel`` and ``grouped_matmul`` above stay on
# their lines for the calls without one: a Mosaic call's serialised body
# carries file and line, and the held experts' programs are the parent's.
# ---------------------------------------------------------------------------

def gate_fits(n, tn):
    """Whether a product of ``n`` columns in blocks of ``tn`` can gate in
    its epilogue: one block holds a row's gate and up halves whole, and
    each half is whole lane tiles."""
    return tn == n and n % (2 * LANES) == 0


def _epilogue_kernel(offsets_ref, group_ref, block_ref, rows_ref, w_ref,
                     *rest, tm, gate, scaled):
    scale_ref, out_ref = rest if scaled else (None, *rest)
    v = pl.program_id(1)
    g = group_ref[v]
    lo, hi = offsets_ref[g], offsets_ref[g + 1]
    acc = jax.lax.dot_general(
        rows_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        precision=PRECISION, preferred_element_type=jnp.float32)
    if gate is not None:
        acc = gate(acc)
    if scaled:
        acc = acc * scale_ref[...]
    row = block_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, acc.shape, 0)
    out_ref[...] = jnp.where((row >= lo) & (row < hi), acc, out_ref[...])


def grouped_matmul_epilogue(rows, weights, group_sizes, row_scale=None, *,
                            tm, tn, gate=None, act=None, interpret=False):
    """:func:`grouped_matmul` whose accumulator goes through an epilogue
    before the masked store.  ``gate`` (needs :func:`gate_fits`): a
    function of the ``[tm, N]`` float32 accumulator that yields ``[tm, N
    // 2]`` (``parallel/moe.py`` ``_gated`` with its arguments bound), and
    the result is [M, N // 2].  ``act``, in its place: a function of a
    ``[tm, tn]`` block that keeps its shape (an activation alone, for
    experts of two matrices: ``_activation``), under any column block, and
    the result is [M, N].  ``row_scale`` [M] float32: row ``r`` of the
    result is multiplied by ``row_scale[r]``, after the gate.  Rows past
    ``group_sizes.sum()`` are left as they lie, unscaled."""
    m, k = rows.shape
    groups, _, n = weights.shape
    if gate is not None and act is not None:
        raise ValueError("an epilogue gates or activates, not both")
    halve = 2 if gate is not None else 1
    if halve == 2 and not gate_fits(n, tn):
        raise ValueError(f"a gate epilogue needs a row's {n} columns in one "
                         f"block of whole lane tiles a half, not {tn}")
    offsets, group, block, n_visits = visits(group_sizes, m, tm)
    operands = [rows, weights]
    in_specs = [
        pl.BlockSpec((tm, k), lambda j, v, o, g, b: (b[v], 0)),
        pl.BlockSpec((None, k, tn), lambda j, v, o, g, b: (g[v], 0, j)),
    ]
    if row_scale is not None:
        operands.append(row_scale.astype(jnp.float32).reshape(m, 1))
        in_specs.append(pl.BlockSpec((tm, 1), lambda j, v, o, g, b: (b[v], 0)))
    return pl.pallas_call(
        functools.partial(_epilogue_kernel, tm=tm,
                          gate=gate if act is None else act,
                          scaled=row_scale is not None),
        out_shape=jax.ShapeDtypeStruct((m, n // halve), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, n_visits),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, tn // halve),
                                   lambda j, v, o, g, b: (b[v], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n,
            transcendentals=m * n // 2 if halve == 2 else 0,
            bytes_accessed=4 * (m * k * (n // tn) + groups * k * n
                                + m * n // halve)),
        name="grouped_matmul_ragged-dot",
        interpret=interpret,
    )(offsets, group, block, *operands)


# ---------------------------------------------------------------------------
# PR 66: what a block of weights takes of the scoped VMEM beside itself.
# Down here so that every call above stays on its lines.
# ---------------------------------------------------------------------------

SCOPED_VMEM_BYTES = 16 << 20   # what XLA:TPU gives an operation by default
COMPILER_TEMP_COLUMNS = 256


def block_fits(k, tn, scoped):
    """Whether a group's ``[k, tn]`` block of weights may be a call's:
    under :func:`block_bytes`, and for a ``scoped`` call inside the default
    scoped VMEM with what Mosaic lays beside the two copies the pipeline
    holds: from :data:`COMPILER_TEMP_COLUMNS` columns a block up, a ``[k,
    256]`` float32 temporary of its own (compile-only readings for a v5e,
    PR 66: inside a decode program ``[6144, 256]`` blocks asked for 18.14
    MiB, 12 of blocks and 6.14 beside them, and were refused; ``[5120,
    256]``, the widest an accepted cell runs, 15.14; ``[6144, 128]`` and
    ``[12288, 128]`` nothing beside the blocks), and a quarter MiB of
    slack.  Every block the accepted cells run fits as it did."""
    block = k * tn * 4
    if block > block_bytes(scoped):
        return False
    if not scoped:
        return True
    temp = k * COMPILER_TEMP_COLUMNS * 4 if tn >= COMPILER_TEMP_COLUMNS else 0
    return 2 * block + temp + (1 << 18) <= SCOPED_VMEM_BYTES

