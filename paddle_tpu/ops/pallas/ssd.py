"""Pallas TPU kernels of the state-space duality recurrence
(``ops/ssd_ops.py`` has the mathematics, how the state lies and the XLA
formulations these are held to).

The state of a slot a layer is ``[N, H P]``: N state rows on sublanes,
every head's P channels side by side on lanes.  ``x`` and ``y`` are then
rows as they lie in HBM, ``B`` and ``C`` columns that every lane shares
(one group), the decay a row, and nothing in either kernel is a head's
own but the decay's value.  With G groups of ``B`` and ``C`` (``[.., G,
N]``) a group's heads are H P / G consecutive lanes and a lane block lies
inside one group (``_lane_block`` of a group's lanes): the block fetches
its group's ``B`` and ``C`` by its index, and the kernels' bodies are the
one group's.

``ssd_step`` is the decode step: grid (lane block, slot); a block reads
its ``[N, lanes]`` of ``S`` once, moves it on, ``S <- a * S + B x``, reads
``y = sum_n C[n] S[n, :]`` off it (a sum over sublanes) and writes it once
to where it lay (the state is aliased in and out).  The slot's ``B`` and
``C`` ride in as rows of one sublane tile and are turned into columns by
one small product with the identity.  A slot that is not ``live`` is
redirected to the trash row, which it hands through unchanged: its own
state is neither read nor written.  All of it is VPU work over whole
tiles; the kernel's floor is the state's two passes over HBM.

``ssd_chunk`` is the prefill's whole chunked scan: grid (batch, lane
block, chunk), the chunk axis sequential, ``S`` [N, lanes] in VMEM scratch
from a block's first chunk to its last.  A chunk makes ``C B^T`` once for
all the block's heads, reads the carried state by one product ``C S`` and
writes it by one ``B^T (w x)`` over all the block's lanes, and per head
only the decay mask ``exp(cum_t - cum_s)`` (every exponent non-positive)
and the product of the masked ``C B^T`` with the head's ``dt x``.  Heads
narrower than a lane tile share one: a head's product is taken over the
whole tile with the other heads' lanes zeroed (the MXU is as wide as the
tile either way).  The running log decay comes in twice, as columns
``[L, heads]`` and as rows ``[heads, L]`` (made in XLA: a cumulative sum
of ``[T, H]``), so that no transposition is made in the kernel.  A chunk
wholly behind ``valid`` is neither fetched nor worked: zeros out, the
state handed through.  The kernel is traced and lowered again for every
program that holds it (``gated_delta.py`` says what that costs), so what
is unrolled is a lane block's few heads and nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ssd_ops import CHUNK, masked

PRECISION = jax.lax.Precision.HIGHEST
ROWS = 8                      # one float32 sublane tile
LANES = 128                   # ... and its lanes
STEP_LANES = 1024             # lanes a step block: [128, 1024] is 512 KB
CHUNK_LANES = 512             # lanes a chunk block: 8 heads of 64


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=PRECISION,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _eye(n):
    return (_iota((n, n), 0) == _iota((n, n), 1)).astype(jnp.float32)


def _lane_block(limit, lanes):
    """The largest whole-tile divisor of ``lanes`` that is at most
    ``limit`` lanes."""
    tiles = lanes // LANES
    return LANES * max(d for d in range(1, tiles + 1)
                       if tiles % d == 0 and d * LANES <= max(limit, LANES))


def step_supported(state_shape, groups=1) -> bool:
    return (state_shape[1] % ROWS == 0
            and state_shape[2] % (groups * LANES) == 0
            and (groups == 1 or state_shape[1] % LANES == 0))


def chunk_supported(x_shape, n_state, chunk, groups=1) -> bool:
    heads, p = x_shape[2], x_shape[3]
    return (LANES % p == 0 and (heads * p) % (groups * LANES) == 0
            and n_state % ROWS == 0 and chunk % ROWS == 0
            and (groups == 1 or n_state % LANES == 0))


def _step_kernel(live_ref, bc_ref, xa_ref, s_ref, y_ref, s_out_ref):
    n = pl.program_id(1)

    @pl.when(live_ref[n] != 0)
    def _():
        rows = s_ref.shape[1]
        cols = _dot(_eye(rows), bc_ref[0], (((1,), (1,)), ((), ())))  # [N, 8]
        # B and C along the lanes once, then a lane tile at a time: the
        # tile's S, both columns and both rows stay in registers
        bcol = jnp.broadcast_to(cols[:, 0:1], (rows, LANES))
        ccol = jnp.broadcast_to(cols[:, 1:2], (rows, LANES))
        for t in range(s_ref.shape[2] // LANES):
            at = slice(t * LANES, (t + 1) * LANES)
            s = xa_ref[0, 1:2, at] * s_ref[0, :, at] \
                + bcol * xa_ref[0, 0:1, at]
            s_out_ref[0, :, at] = s
            y_ref[0, :, at] = jnp.sum(ccol * s, axis=0, keepdims=True)

    @pl.when(live_ref[n] == 0)
    def _():
        y_ref[0] = jnp.zeros(y_ref.shape[1:], y_ref.dtype)
        s_out_ref[0] = s_ref[0]


@functools.partial(jax.jit, static_argnames=("interpret", "lanes_block"))
def step(x, dt, a, bm, cm, d, state, live, interpret=False,
         lanes_block=None):
    """x [n, H, P], dt [n, H], a, d [H], bm, cm [n, N] (or [n, G, N])
    float32, ``state`` [n + 1, N, H P] (row n the trash row), ``live`` [n]
    int32 -> (out [n, H, P], the state, live rows moved on in place).
    ``lanes_block``: lanes a block, at most (default ``STEP_LANES``)."""
    n, H, P = x.shape
    N, HP = state.shape[1:]
    G = 1 if bm.ndim == 2 else bm.shape[1]
    lb = _lane_block(lanes_block or STEP_LANES, HP // G)
    per = HP // G // lb                # lane blocks a group
    if bm.ndim == 3:   # a group's [N] the block's lanes of a row of [G N]
        bm, cm = bm.reshape(n, G * N), cm.reshape(n, G * N)
    # rows as the kernel takes them: dt x, and the decay a lane
    xa = jnp.stack([(dt[..., None] * x).reshape(n, HP),
                    jnp.repeat(jnp.exp(dt * a), P, axis=1)], axis=1)
    bc = jnp.pad(jnp.stack([bm, cm], axis=1), ((0, 0), (0, ROWS - 2), (0, 0)))

    # (slots inside lane blocks: slots that are not live follow each other
    # to the one trash block, which is then fetched and written once)
    def row(j, s, live):
        return (s, 0, j)

    def state_row(j, s, live):
        return (jnp.where(live[s] != 0, s, n), 0, j)

    state_blk = pl.BlockSpec((1, N, lb), state_row)
    y, new = pl.pallas_call(
        _step_kernel,
        out_shape=(jax.ShapeDtypeStruct((n, 1, HP), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(HP // lb, n),
            in_specs=[pl.BlockSpec((1, ROWS, N), lambda j, s, live:
                                   (s, 0, j // per if G > 1 else 0)),
                      pl.BlockSpec((1, 2, lb), row),
                      state_blk],
            out_specs=[pl.BlockSpec((1, 1, lb), row), state_blk]),
        # operand 3 (after the prefetched scalars, bc and xa) is the state:
        # updated where it lies
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_step",
    )(live.astype(jnp.int32), bc, xa, state)
    return y.reshape(n, H, P) + d[:, None] * x, new


def _chunk_kernel(valid_ref, live_ref, x_ref, b_ref, c_ref, cc_ref, cr_ref,
                  s0_ref, y_ref, s_out_ref, s_scr, *, head):
    b, c = pl.program_id(0), pl.program_id(2)
    L = x_ref.shape[1]

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[0]

    @pl.when(c < live_ref[b])
    def _():
        bm, cm = b_ref[0], c_ref[0]                            # [L, N]
        g = _dot(cm, bm, (((1,), (1,)), ((), ())))             # C B^T
        tri = _iota((L, L), 0) >= _iota((L, L), 1)
        lane = _iota((L, LANES), 1)
        cols, rows = cc_ref[0, 0], cr_ref[0, 0, 0]      # [L, heads], [heads, L]
        for t in range(x_ref.shape[2] // LANES):
            at = slice(t * LANES, (t + 1) * LANES)
            x, s = x_ref[0, :, at], s_scr[:, at]               # dt x; the state
            inside = jnp.zeros((L, LANES), jnp.float32)
            read = jnp.zeros((L, LANES), jnp.float32)
            wrote = jnp.zeros((L, LANES), jnp.float32)
            for i in range(LANES // head):
                h = t * (LANES // head) + i
                own = (lane >= i * head) & (lane < (i + 1) * head)
                col, row = cols[:, h:h + 1], rows[h:h + 1, :]
                decay = jnp.where(tri, jnp.exp(jnp.where(tri, col - row,
                                                         0.0)), 0.0)
                inside = inside + _dot(g * decay, jnp.where(own, x, 0.0))
                read = jnp.where(own, jnp.exp(col), read)
                wrote = jnp.where(own, jnp.exp(col[L - 1:L] - col), wrote)
            y_ref[0, :, at] = inside + read * _dot(cm, s)
            # (``read``'s last row is the chunk's whole decay, a lane)
            s_scr[:, at] = read[L - 1:L] * s \
                + _dot(bm, wrote * x, (((0,), (0,)), ((), ())))

    @pl.when(c >= live_ref[b])
    def _():
        # wholly behind ``valid``: nothing read, the state handed through
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0] = s_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("interpret", "lanes_block", "chunk"))
def chunk(x, dt, a, bm, cm, d, s0=None, valid=None, interpret=False,
          lanes_block=None, chunk=CHUNK):
    """The whole chunked scan as one kernel: x [B, T, H, P], dt [B, T, H],
    a, d [H], bm, cm [B, T, N] (or [B, T, G, N]) float32, ``s0`` [B, N,
    H P], ``valid`` [B]
    -> (out [B, T, H, P], the state after the last real token [B, N,
    H P]), as ``ssd_ops.chunked``.  ``lanes_block``: lanes a grid step, at
    most (default ``CHUNK_LANES``); ``chunk``: tokens a chunk."""
    B, T, H, P = x.shape
    N, HP, L = bm.shape[-1], H * P, chunk
    n = -(-T // L)
    if valid is None:
        valid = jnp.full((B,), T, jnp.int32)
    valid = jnp.minimum(valid.astype(jnp.int32), T)
    if s0 is None:
        s0 = jnp.zeros((B, N, HP), jnp.float32)
    G = 1 if bm.ndim == 3 else bm.shape[2]
    lb = _lane_block(lanes_block or CHUNK_LANES, HP // G)
    hb, J = lb // P, HP // lb                  # heads a block, blocks
    per = J // G                               # blocks a group
    xm, dtm, bmm, cmm = (
        jnp.pad(t, ((0, 0), (0, n * L - T)) + ((0, 0),) * (t.ndim - 2))
        for t in masked(x, dt, bm, cm, valid))
    cum = jnp.cumsum((dtm * a).reshape(B, n, L, J, hb), axis=2)
    cols = jnp.moveaxis(cum, 3, 1).reshape(B, J, n * L, hb)
    rows = jnp.transpose(cum, (0, 3, 1, 4, 2))               # [B, J, n, hb, L]
    xd = (dtm[..., None] * xm).reshape(B, n * L, HP)
    if bm.ndim == 4:   # a group's [L, N] the block's lanes of [L, G N]
        bmm, cmm = bmm.reshape(B, n * L, G * N), cmm.reshape(B, n * L, G * N)

    live = (valid + L - 1) // L           # chunks that hold a real row

    def real(c, b, live):
        """A chunk wholly behind ``valid`` is not fetched: the block index
        stays at the last real chunk's."""
        return jnp.minimum(c, jnp.maximum(live[b] - 1, 0))

    shared = pl.BlockSpec((1, L, N), lambda b, j, c, valid, live:
                          (b, real(c, b, live), j // per if G > 1 else 0))
    state_blk = pl.BlockSpec((1, N, lb), lambda b, j, c, valid, live:
                             (b, 0, j))
    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, head=P),
        out_shape=(jax.ShapeDtypeStruct((B, n * L, HP), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, HP), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, J, n),
            in_specs=[pl.BlockSpec((1, L, lb), lambda b, j, c, valid, live:
                                   (b, real(c, b, live), j)),
                      shared, shared,
                      pl.BlockSpec((1, 1, L, hb), lambda b, j, c, valid, live:
                                   (b, j, real(c, b, live), 0)),
                      pl.BlockSpec((1, 1, 1, hb, L),
                                   lambda b, j, c, valid, live:
                                   (b, j, real(c, b, live), 0, 0)),
                      state_blk],
            out_specs=[pl.BlockSpec((1, L, lb), lambda b, j, c, valid, live:
                                    (b, c, j)),
                       state_blk],
            scratch_shapes=[pltpu.VMEM((N, lb), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk",
    )(valid, live, xd, bmm, cmm, cols, rows, s0.astype(jnp.float32))
    return y[:, :T].reshape(B, T, H, P) + d[:, None] * xm[:, :T], state
