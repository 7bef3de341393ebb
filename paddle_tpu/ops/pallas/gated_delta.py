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

``gated_delta_chunk`` is the prefill's whole chunked scan: grid (batch,
head group, chunk), the chunk axis sequential, ``S`` in VMEM scratch from
a head's first chunk to its last.  It is handed q, k, v, the log decay,
beta and ``valid`` (prefetched scalars).  Heads of whole lane tiles (Dk
and Dv multiples of 128) are read where they lie, a head's lanes of [B,
T, H D], and written so; other head sizes are first laid out by chunks
(``gated_delta_ops.lay``, a copy each in XLA) and the outputs copied
back.  For each chunk of a block's heads, all of them side by side (each
head is one chain of dependent products, and they run in each other's
shadow), it makes in VMEM what ``chunk_terms`` / ``chunk_terms_channel``
make in XLA: the running log decay (a product with a triangle of ones),
the decayed ``k k^T`` and ``q k^T`` with every exponent non-positive ([C,
C] differences under a decay a head; blocks of ``BLOCK`` under a decay a
channel, ``_decayed_products``), ``(I + A)^-1`` of the chunk's
unit-triangular system (``_unit_lower_inverse``: the diagonal blocks by
substitution on the VPU, the blocks below them by small products), ``w``
and ``u0``; then applies them, ``U = u0 - w S``, ``O = qg S + p U``, ``S
<- gc S + kd^T U``, and writes the outputs and, once, the last state: no
term and no per-chunk state goes to HBM.  A chunk wholly behind ``valid``
is neither fetched nor worked: zeros out, the state handed through.  Rows
and columns trade places by a product with the identity, as the step's
do.  The kernel's size is a cost of its own: it is traced and lowered
again for every program that holds it, in every process (a warm compile
cache saves the compile, not that), so its loops are loops and its index
arithmetic is bit operations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..gated_delta_ops import BLOCK, CHUNK, lay

PRECISION = jax.lax.Precision.HIGHEST
ROWS = 8                      # one float32 sublane tile
LANES = 128                   # ... and its lanes
COLUMNS = 4                   # columns of a 16-row block a trip of their loop
HEADS_BLOCK_BYTES = 1 << 20   # a step block's states, at most: 10 heads of
#                               96 x 192, 16 of 128 x 128 (a divisor is taken)
CHUNK_BLOCK_BYTES = 1 << 19   # a chunk block's q, k, v and outputs, at most: 4
#                               heads of 128 x 128, 3 of 96 x 192 (a divisor)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=PRECISION,
                               preferred_element_type=jnp.float32)


def step_supported(state_shape) -> bool:
    return state_shape[2] % ROWS == 0


def chunk_supported(q_shape, chunk) -> bool:
    return chunk % ROWS == 0 and q_shape[3] % ROWS == 0


def _heads_a_block(limit, heads):
    """The largest divisor of ``heads`` that is at most ``limit``."""
    return max(d for d in range(1, min(max(limit, 1), heads) + 1)
               if heads % d == 0)


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
    hb = _heads_a_block(heads_block or HEADS_BLOCK_BYTES // (Dk * Dv * 4), H)
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


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _same_block(i, j, size):
    """``i // size == j // size`` for indices >= 0 and ``size`` a power of
    two, in bit operations (an integer division costs the kernel's
    lowering 15 ms apiece, every time a program is built)."""
    return (i ^ j) < size


# The chunk kernel's values carry the block's heads as their first axis,
# [n, ...]: every stage is taken for all of them at once, so that one
# head's chain of dependent products runs in the shadow of another's.

def _bdot(a, b, ca=2, cb=1):
    """A product a head: [n, .., ..] x [n, .., ..], axis ``ca`` of ``a``
    against axis ``cb`` of ``b``."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((0,), (0,))),
                               precision=PRECISION,
                               preferred_element_type=jnp.float32)


def _dot_const(a, m):
    """``a[h] @ m`` for a constant ``m`` [k, j]: one product over all the
    heads' rows."""
    n, r, k = a.shape
    return _dot(a.reshape(n * r, k), m).reshape(n, r, m.shape[1])


def _transposed(x):
    """``x[h]^T`` of small [n, r, c] as a product with the identity (exact:
    every sum has one term that is not zero)."""
    n, _, c = x.shape
    return _bdot(jnp.broadcast_to(_eye(c), (n, c, c)), x, 2, 2)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` [n, C, C], all of
    it in registers and VMEM.  The diagonal blocks of ``BLOCK`` rows by
    forward substitution, all of them at once and with no reduction in
    the chain: once row j of a block's inverse is final, every later row
    i of that block takes ``- a[i, j] row j`` (a lane-broadcast column
    times a sublane-broadcast row).  Then the blocks below the diagonal,
    two sizes: ``[[X1, 0], [-X2 A21 X1, X2]]`` as ``X - X F X`` with ``F``
    the ``A21`` of every pair."""
    n, C, _ = a.shape
    nb = C // BLOCK
    ri, ci = _iota((C, C), 0), _iota((C, C), 1)
    # every block's own columns side by side: [t, j] = a[t, 16 b(t) + j]
    own = (_iota((C, BLOCK), 0) & (BLOCK - 1)
           == _iota((C, BLOCK), 1)).astype(jnp.float32)
    d = _dot_const(jnp.where(_same_block(ri, ci, BLOCK), a, 0.0), own)
    d = d.reshape(n, nb, BLOCK, BLOCK)
    x = jnp.broadcast_to(_eye(C).reshape(nb, BLOCK, C), (n, nb, BLOCK, C))
    for j in range(BLOCK - 1):
        x = x - d[:, :, :, j:j + 1] * x[:, :, j:j + 1, :]
    x = x.reshape(n, C, C)
    size = BLOCK
    while size < C:
        pair = 2 * size
        f = jnp.where(_same_block(ri, ci, pair) & (ri & size != 0)
                      & (ci & size == 0), a, 0.0)
        x = x - _bdot(x, _bdot(f, x))
        size = pair
    return x


def _decayed_products(q, k, cum):
    """``gated_delta_ops._decayed_products`` on one chunk a head in VMEM:
    q, k, cum [n, C, Dk] -> the decayed ``k k^T`` and ``q k^T`` [n, C, C],
    zero above the diagonal, every exponent non-positive."""
    n, C, Dk = k.shape
    nb = C // BLOCK
    c4, k4, q4 = (x.reshape(n, nb, BLOCK, Dk) for x in (cum, k, q))
    ti = _iota((nb, BLOCK, 1), 1)
    lane = _iota((nb, BLOCK, C), 2) & (BLOCK - 1)

    def columns(c_ref, k_ref):
        """Column i of every block at once, the differences to token i
        outright; row i of each block is read back from VMEM scratch, so
        that this is a loop and not 16 copies of its body (the kernel is
        traced and lowered again for every program that holds it, in
        every process, and its size is that time)."""
        c_ref[...], k_ref[...] = c4, k4

        def trip(t, acc):
            dk, dq = acc
            for i in (t * COLUMNS + j for j in range(COLUMNS)):
                keep, at = ti >= i, lane == i
                e = jnp.where(keep, jnp.exp(jnp.where(
                    keep, c4 - c_ref[:, :, pl.ds(i, 1), :], 0.0)), 0.0) \
                    * k_ref[:, :, pl.ds(i, 1), :]
                dk = jnp.where(at, jnp.sum(k4 * e, axis=3, keepdims=True), dk)
                dq = jnp.where(at, jnp.sum(q4 * e, axis=3, keepdims=True), dq)
            return dk, dq

        zeros = jnp.zeros((n, nb, BLOCK, C), jnp.float32)
        return jax.lax.fori_loop(0, BLOCK // COLUMNS, trip, (zeros, zeros))

    dk, dq = pl.run_scoped(columns, pltpu.VMEM(c4.shape, jnp.float32),
                           pltpu.VMEM(k4.shape, jnp.float32))
    ri, ci = _iota((C, C), 0), _iota((C, C), 1)
    same = _same_block(ri, ci, BLOCK)
    kk = [jnp.zeros((n, BLOCK, C), jnp.float32)]
    qk = [jnp.zeros((n, BLOCK, C), jnp.float32)]
    # across blocks: block b's rows scaled back to its first token, the
    # columns before that token scaled on to it
    old = _iota((C, 1), 0)
    for b in range(1, nb):
        first = cum[:, b * BLOCK:b * BLOCK + 1]
        back = jnp.exp(c4[:, b] - first)
        before = old < b * BLOCK
        cols = jnp.where(before, jnp.exp(jnp.where(before, first - cum, 0.0)),
                         0.0) * k
        far = _bdot(jnp.concatenate([k4[:, b] * back, q4[:, b] * back],
                                    axis=1), cols, 2, 2)      # [n, 2 BLOCK, C]
        kk.append(far[:, :BLOCK])
        qk.append(far[:, BLOCK:])
    return (jnp.concatenate(kk, axis=1)
            + jnp.where(same, dk.reshape(n, C, C), 0.0),
            jnp.concatenate(qk, axis=1)
            + jnp.where(same, dq.reshape(n, C, C), 0.0))


def _chunk_of_heads(q, k, v, g, gb, s):
    """One chunk of a block's ``n`` heads, values in VMEM: q, k [n, C, Dk],
    v [n, C, Dv], ``gb`` a sublane tile a head [n, ROWS, C] whose row 0 is
    the log decay a token (where it is a number a head: ``g`` is then
    None) and row 1 beta, ``g`` [n, C, Dk] the log decay a channel, ``s``
    [n, Dk, Dv] -> (the chunk's outputs [n, C, Dv], the states behind
    it)."""
    n, C, _ = q.shape
    ri, ci = _iota((C, C), 0), _iota((C, C), 1)
    low, strict = ri >= ci, ri > ci
    if g is None:
        # the running sum as a row, and (with beta) as a column: the same
        # numbers both ways, so the diagonal's difference is 0 exactly
        gb = jnp.where(_iota(gb.shape, 1) == 0,
                       _dot_const(gb, (ri <= ci).astype(jnp.float32)), gb)
        cols = _transposed(gb)                                 # [n, C, ROWS]
        cum, beta = cols[:, :, 0:1], cols[:, :, 1:2]
        decay = jnp.where(low, jnp.exp(jnp.where(low, cum - gb[:, 0:1], 0.0)),
                          0.0)
        both = _bdot(jnp.concatenate([k, q], axis=1), k, 2, 2)  # [n, 2 C, C]
        kk, p = decay * both[:, :C], decay * both[:, C:]
    else:
        beta = _transposed(gb)[:, :, 1:2]
        cum = _bdot(jnp.broadcast_to(low.astype(jnp.float32), (n, C, C)), g)
        kk, p = _decayed_products(q, k, cum)
    t = _unit_lower_inverse(jnp.where(strict, beta * kk, 0.0))
    gam, last = jnp.exp(cum), cum[:, C - 1:C]
    w = _bdot(t, beta * gam * k)
    u = _bdot(t, beta * v) - _bdot(w, s)                       # [n, C, Dv]
    o = _bdot(q * gam, s) + _bdot(p, u)
    if g is None:                       # one number, along the lanes first
        gc = jnp.exp(jnp.broadcast_to(last, (n, 1, s.shape[2])))
    else:                               # a row [1, Dk], turned into the
        gc = _transposed(jnp.broadcast_to(                # rows' scaling
            jnp.exp(last), (n, ROWS, last.shape[2])))[:, :, 0:1]
    kd = k * jnp.exp(last - cum)
    return o, gc * s + _bdot(kd, u, 1, 1)


def _chunk_kernel(valid_ref, live_ref, *refs, heads, channel, flat):
    q_ref, k_ref, v_ref = refs[:3]
    g_ref = refs[3] if channel else None
    gb_ref, s0_ref, o_ref, s_out_ref, s_scr = refs[3 + channel:]
    b, c = pl.program_id(0), pl.program_id(2)
    real = _iota((CHUNK, 1), 0) < valid_ref[b] - c * CHUNK

    def lanes(ref, j):
        """Head j of a block's ``heads``: its lanes of [1, C, heads D] (the
        operand as it lies), or its [C, D] of [1, heads, 1, C, D]."""
        if not flat:
            return (0, j, 0)
        d = ref.shape[2] // heads
        return (0, slice(None), slice(j * d, (j + 1) * d))

    def rows(ref):
        """[heads, C, D], the rows behind ``valid`` zero, whatever they
        hold."""
        return jnp.where(real, jnp.stack([ref[lanes(ref, j)]
                                          for j in range(heads)]), 0.0)

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[0]

    @pl.when(c < live_ref[b])
    def _():
        o, s = _chunk_of_heads(
            rows(q_ref), rows(k_ref), rows(v_ref),
            rows(g_ref) if channel else None, gb_ref[0, :, 0], s_scr[...])
        for j in range(heads):
            o_ref[lanes(o_ref, j)] = o[j]
        s_scr[...] = s

    @pl.when(c >= live_ref[b])
    def _():
        # wholly behind ``valid``: nothing read, the state handed through
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret", "heads_block"))
def chunk(q, k, v, g, beta, s0=None, valid=None, interpret=False,
          heads_block=None):
    """The whole chunked scan as one kernel: q, k [B, T, H, Dk], v [B, T,
    H, Dv], g [B, T, H] or [B, T, H, Dk], beta [B, T, H] float32, ``s0``
    [B, H, Dk, Dv], ``valid`` [B] -> (out [B, T, H, Dv], the state after
    the last real token), as ``gated_delta_ops.chunked``.  Heads of whole
    lane tiles (Dk and Dv multiples of 128) are read where they lie, a
    head's lanes of [B, T, H D], and the outputs written so; other sizes
    are laid out by chunks first (``lay``: one copy each, in XLA) and the
    outputs copied back.  ``heads_block``: heads a grid step, at most
    (default: as many as hold ``CHUNK_BLOCK_BYTES`` of a chunk's
    operands)."""
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    C = CHUNK
    N = -(-T // C)
    channel = g.ndim == 4
    flat = Dk % LANES == 0 and Dv % LANES == 0
    if valid is None:
        valid = jnp.full((B,), T, jnp.int32)
    valid = jnp.minimum(valid.astype(jnp.int32), T)
    if s0 is None:
        s0 = jnp.zeros((B, H, Dk, Dv), jnp.float32)
    hb = _heads_a_block(
        heads_block or CHUNK_BLOCK_BYTES // (C * 2 * (Dk + Dv) * 4), H)
    # the log decay a head and beta: a sublane tile a chunk a head
    tile = [lay(x, valid, C) for x in
            ((jnp.zeros_like(beta) if channel else g), beta)]
    gb = jnp.pad(jnp.stack(tile, axis=3),
                 [(0, 0)] * 3 + [(0, ROWS - 2), (0, 0)])
    wide = (q, k, v) + ((g,) if channel else ())
    if flat:
        wide = [jnp.pad(x.reshape(B, T, -1), ((0, 0), (0, N * C - T), (0, 0)))
                for x in wide]
    else:
        wide = [lay(x, None, C) for x in wide]

    live = (valid + C - 1) // C           # chunks that hold a real row

    def real(c, b, live):
        """A chunk wholly behind ``valid`` is not fetched: the block index
        stays at the last real chunk's."""
        return jnp.minimum(c, jnp.maximum(live[b] - 1, 0))

    def laid(*shape, fetch=real):
        return pl.BlockSpec((1, hb, 1) + shape, lambda b, h, c, valid, live:
                            (b, h, fetch(c, b, live), 0, 0))

    def per_chunk(d, fetch=real):
        if flat:
            return pl.BlockSpec((1, C, hb * d), lambda b, h, c, valid, live:
                                (b, fetch(c, b, live), h))
        return laid(C, d, fetch=fetch)

    per_head = pl.BlockSpec((1, hb, Dk, Dv), lambda b, h, c, valid, live:
                            (b, h, 0, 0))
    out, state = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, channel=channel,
                          flat=flat),
        out_shape=(jax.ShapeDtypeStruct(
            (B, N * C, H * Dv) if flat else (B, H, N, C, Dv), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Dk, Dv), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb, N),
            in_specs=[per_chunk(x.shape[-1] // (H if flat else 1))
                      for x in wide]
            + [laid(ROWS, C), per_head],
            out_specs=[per_chunk(Dv, fetch=lambda c, b, live: c), per_head],
            scratch_shapes=[pltpu.VMEM((hb, Dk, Dv), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_chunk",
    )(valid, live, *wide, gb, s0.astype(jnp.float32))
    if not flat:
        out = jnp.moveaxis(out, 1, 3)
    return out.reshape(B, N * C, H, Dv)[:, :T], state
