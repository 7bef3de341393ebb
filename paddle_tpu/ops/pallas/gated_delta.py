"""Pallas TPU kernels of the gated delta rule (``ops/gated_delta_ops.py``
has the mathematics and the XLA formulations these are held to).

``gated_delta_step`` is the decode step: a grid of (head group, slot)
blocks of as many heads as ``HEADS_BLOCK_BYTES`` of state hold (fewer, larger DMAs than a head a
block), each reads its heads' ``S`` [Dk, Dv] once, moves them on and
writes them once to where they lay (the state is aliased in and out).  A slot that is not
``live`` is redirected to the trash row, which it hands through
unchanged: its own state is neither read nor written.  The three
contractions run on the VPU over ``S`` as it lies (key rows on sublanes,
value columns on lanes): the slot's k and q ride in as rows of one
sublane tile and are turned into columns by one small product with the
identity (``I kq^T``: the tile is the stationary operand), and then
``S^T k`` and ``S^T q`` are a lane-broadcast multiply and a sum over
sublanes, the correction ``k (beta r)^T`` a broadcast multiply-add.  (The
same contractions as MXU products of an 8-row tile load ``S`` as the
stationary operand twice a head for 8 rows of work: 3.3 times slower on
a v5e, PERF.md section 6.)  A decay a key channel (``g`` [n, H, Dk])
rides in as a third row of that tile and comes out of the same product
as the column that scales ``S``'s rows.

``gated_delta_chunk`` is the prefill's chunk-to-chunk pass: grid (batch,
head, chunk), the chunk axis sequential, ``S`` in VMEM scratch from a
head's first chunk to its last.  What the tokens of a chunk need of each
other (``chunk_terms``) is computed for all chunks at once by XLA before
it; the kernel does the three products that need the carried state and
writes the outputs and, once, the last state: no per-chunk state goes to
HBM.  Under a decay a key channel the chunk's scaling of the state is a
row of a sublane tile, turned into a column as the step's is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PRECISION = jax.lax.Precision.HIGHEST
ROWS = 8                      # one float32 sublane tile
HEADS_BLOCK_BYTES = 1 << 20   # a step block's states, at most: 10 heads of
#                               96 x 192, 16 of 128 x 128 (a divisor is taken)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=PRECISION,
                               preferred_element_type=jnp.float32)


def step_supported(state_shape) -> bool:
    return state_shape[2] % ROWS == 0


def chunk_supported(q_shape, chunk) -> bool:
    return chunk % ROWS == 0 and q_shape[3] % ROWS == 0


def _eye(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
            ).astype(jnp.float32)


def _step_kernel(live_ref, alpha_ref, beta_ref, kq_ref, v_ref, s_ref,
                 o_ref, s_out_ref, *, heads, block, channel=False):
    g, n = pl.program_id(0), pl.program_id(1)

    @pl.when(live_ref[n] != 0)
    def _():
        dk = s_ref.shape[2]
        eye = _eye(dk)
        for j in range(block):
            at = n * heads + g * block + j
            cols = _dot(eye, kq_ref[0, j], (((1,), (1,)), ((), ())))  # [Dk, 8]
            kc, qc = cols[:, 0:1], cols[:, 1:2]
            # (the decay: one number a head, or row 2 of the tile)
            s = (cols[:, 2:3] if channel else alpha_ref[at]) \
                * s_ref[0, j]                                 # [Dk, Dv]
            r = v_ref[0, j][0:1] - jnp.sum(kc * s, axis=0, keepdims=True)
            s = s + kc * (beta_ref[at] * r)
            o = jnp.sum(qc * s, axis=0, keepdims=True)        # [1, Dv]
            o_ref[0, j] = jnp.broadcast_to(o, o_ref.shape[2:])
            s_out_ref[0, j] = s

    @pl.when(live_ref[n] == 0)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)
        s_out_ref[0] = s_ref[0]


@functools.partial(jax.jit, static_argnames=("interpret", "heads_block"))
def step(q, k, v, g, beta, state, live, interpret=False, heads_block=None):
    """q, k [n, H, Dk], v [n, H, Dv], g [n, H] or [n, H, Dk], beta
    [n, H] float32, ``state`` [n + 1, H, Dk, Dv] (row n the trash row),
    ``live`` [n] int32 -> (out [n, H, Dv], the state, live rows moved on
    in place).  ``heads_block``: heads a block, at most (default: as many
    as hold ``HEADS_BLOCK_BYTES`` of state; on a v5e 64 heads of 128 x 128
    over 64 slots take 2.23 / 1.11 / 0.97 / 0.85 / 0.84 ms at 1 / 4 / 8 /
    16 / 32 heads a block, 30 heads of 96 x 192 were measured at PR 41)."""
    n, H, Dk = q.shape
    Dv = v.shape[-1]
    channel = g.ndim == 3
    if heads_block is None:
        heads_block = max(HEADS_BLOCK_BYTES // (Dk * Dv * 4), 1)
    hb = max(d for d in range(1, min(heads_block, H) + 1) if H % d == 0)
    def tile(*rows):
        """[n, H, D] rows -> one sublane tile a head, zero below them."""
        t = jnp.stack(rows, axis=2).astype(jnp.float32)
        return jnp.pad(t, ((0, 0), (0, 0), (0, ROWS - len(rows)), (0, 0)))

    # a decay a channel rides in the tile; the scalar operand is then not
    # read
    kq, v8 = tile(k, q, jnp.exp(g)) if channel else tile(k, q), tile(v)

    def row(h, s, live, *_):
        return (s, h, 0, 0)

    def state_row(h, s, live, *_):
        return (jnp.where(live[s] != 0, s, n), h, 0, 0)

    state_blk = pl.BlockSpec((1, hb, Dk, Dv), state_row)
    out, new = pl.pallas_call(
        functools.partial(_step_kernel, heads=H, block=hb,
                          **({"channel": True} if channel else {})),
        out_shape=(jax.ShapeDtypeStruct((n, H, ROWS, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(H // hb, n),
            in_specs=[pl.BlockSpec((1, hb, ROWS, Dk), row),
                      pl.BlockSpec((1, hb, ROWS, Dv), row),
                      state_blk],
            out_specs=[pl.BlockSpec((1, hb, ROWS, Dv), row), state_blk]),
        # operand 5 (after the three prefetched scalars, kq and v) is the
        # state: updated where it lies
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gated_delta_step",
    )(live.astype(jnp.int32), (beta if channel else jnp.exp(g)).reshape(-1),
      beta.reshape(-1), kq, v8, state)
    return out[:, :, 0], new


def _chunk_kernel(qg_ref, w_ref, u0_ref, p_ref, kdt_ref, gc_ref, s0_ref,
                  o_ref, s_out_ref, s_scr, *, channel=False):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[0, 0]

    s = s_scr[...]
    u = u0_ref[0, 0, 0] - _dot(w_ref[0, 0, 0], s)             # [C, Dv]
    o_ref[0, 0, 0] = _dot(qg_ref[0, 0, 0], s) + _dot(p_ref[0, 0, 0], u)
    gc = gc_ref[0, 0, 0]                   # [1, Dv], or a tile [8, Dk]
    if channel:
        gc = _dot(_eye(gc.shape[1]), gc, (((1,), (1,)), ((), ())))[:, 0:1]
    s = gc * s + _dot(kdt_ref[0, 0, 0], u)
    s_scr[...] = s

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0, 0] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def carry_chunks(terms, s0, interpret=False):
    """``gated_delta_ops.scan_chunks`` as the kernel: ``terms`` of
    ``chunk_terms`` ([B, H, N, C, ...]), ``s0`` [B, H, Dk, Dv] -> (out
    [B, H, N, C, Dv], the last state)."""
    qg, w, u0, p, kd, gc = terms
    B, H, N, C, Dk = qg.shape
    Dv = u0.shape[-1]
    kdt = jnp.swapaxes(kd, -1, -2)                            # [.., Dk, C]
    channel = gc.ndim == 4
    if channel:
        gcb = jnp.pad(gc[..., None, :], [(0, 0)] * 3 + [(0, ROWS - 1), (0, 0)])
    else:
        gcb = jnp.broadcast_to(gc[..., None, None], (B, H, N, 1, Dv))

    def per_chunk(*shape):
        return pl.BlockSpec((1, 1, 1) + shape,
                            lambda b, h, c: (b, h, c, 0, 0))

    per_head = pl.BlockSpec((1, 1, Dk, Dv), lambda b, h, c: (b, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, channel=True) if channel
        else _chunk_kernel,
        out_shape=(jax.ShapeDtypeStruct((B, H, N, C, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, Dk, Dv), jnp.float32)),
        grid=(B, H, N),
        in_specs=[per_chunk(C, Dk), per_chunk(C, Dk), per_chunk(C, Dv),
                  per_chunk(C, C), per_chunk(Dk, C),
                  per_chunk(ROWS, Dk) if channel else per_chunk(1, Dv),
                  per_head],
        out_specs=[per_chunk(C, Dv), per_head],
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_chunk",
    )(qg, w, u0, p, kdt, gcb, s0.astype(jnp.float32))
