"""Paged decode attention for TPU — one query token per slot, or a block
of query rows that all see the same columns, attends its live KV pages in
place.

The paged cache is a per-layer pool ``[P, Hkv, pt, D]`` with a block
table ``[B, NP]`` (logical page -> physical page) per slot
(``ops/decode_ops.py``).  The reference formulation gathers every slot's
whole block-table row into a dense ``[B, Hkv, NP*pt, D]`` view, expands
it to the query heads and contracts over all of ``max_seq``; its cost
follows ``slots x max_seq`` whatever is live.  This kernel reads, per
step, each **live** page of K and of V once, straight from the pool in
HBM through the block table, and nothing else but Q and the output:

* grid ``(B,)``, one slot per cell; the block table and the positions are
  scalar-prefetch operands in SMEM, the pools stay in HBM;
* a slot's live pages come in granules of ``G`` pages (``G * pt``
  positions, 128 by default): one async copy per page (a page is
  ``Hkv * pt * D`` contiguous elements) into a double-buffered VMEM
  scratch laid out ``[Hkv, G*pt, D]``, so the next granule — or the next
  slot's first one — is in flight while this one is contracted.  The
  loop runs ``ceil(live_pages / G)`` times: nothing beyond the live
  length is fetched, an idle slot (position 0) costs one page;
* the ``rep`` query heads of a KV head share that head's K/V (query head
  ``g`` reads KV head ``g // rep``, the ``cached_attention`` convention):
  one batched ``[Hkv, rep, D] x [Hkv, T, D]`` contraction, no expansion;
* online softmax with float32 state and accumulators, the same ``-1e30``
  mask constant and ``j <= positions[b]`` validity rule as
  ``cached_attention``.  Rows of the V buffer beyond the live length are
  zeroed in a slot's last granule, so stale VMEM or a recycled page's
  garbage (NaN included) cannot reach the output through ``0 * x``.

Shapes the compiled kernel takes: ``D`` a multiple of 128 (lanes) and
``pt`` whole sublane tiles (8 rows of float32, 16 of bfloat16: a page is
copied into rows ``i * pt ..`` of the buffer); ``supported()`` says so and
the op lowers anything else to the reference formulation.

**Two-byte pools.**  The buffers are the pools' dtype and go into both
contractions as they lie, with q (the op hands it over in the pools'
dtype) and the probabilities rounded where they enter ``p @ v``: one MXU
pass each at the default precision, float32 sums, and the softmax state
float32 as ever.

**Heads of 64.**  A TPU pads a minor dim of 64 to the 128 lanes, in HBM
too, so such a model's pool is kept ``[P, Hkv / 2, pt, 128]``: KV heads
``2p`` and ``2p + 1`` side by side in one row (``ops/decode_ops.py``
``pool_shape``).  The kernel reads those pages as they lie, as ``Hkv /
2`` heads of 128: the query rows of head ``2p`` go in with zeros in lanes
64-127 and those of head ``2p + 1`` with zeros in lanes 0-63, so ``q .
k`` over the 128 lanes is each row's own head's score; ``p @ v`` then
holds the row's own head's output in its own half of the lanes (the
other half, the neighbour's V under this row's weights, is cut off).
The same compiled kernel, twice the rows a pair and twice the MXU work
of a step that HBM bounds; every live K and V byte is read once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF  # the -1e30 mask constant
from .flash_attention import sublane_rows

# positions fetched and contracted per loop turn.  On a TPU v5e at Hkv 8,
# D 128, float32, page 16 (PERF.md §6, PR 25): 128 / 256 / 512 take 137 /
# 150 / 186 us a layer over 32 short chat contexts and 243 / 235 / 239 us
# over 8 long ones; a smaller granule wastes less on a slot's masked tail.
# Over bfloat16 pages (tools/attn_dtype_microbench.py, PERF.md §6, PR 68: 32
# chat contexts of 100-1,400 positions | 8 long ones of 1,000-3,700) a page
# copy is half the bytes and a turn's fixed cost what it was: 313 / 275 /
# 257 | 247 / 247 / 270 us a layer (float32 in the same run: 370 / 385 /
# 391 | 296 / 285 / 290), so two-byte pools take 256
GRANULE_POSITIONS = 128
GRANULE_POSITIONS_TWO_BYTE = 256


def granule_positions(dtype):
    """Positions a loop turn over pools of ``dtype`` (the table above)."""
    return GRANULE_POSITIONS if jnp.dtype(dtype).itemsize >= 4 \
        else GRANULE_POSITIONS_TWO_BYTE
# float32 operands go through the MXU whole (Mosaic's fp32 contraction),
# not rounded to bf16 as at default precision: 6e-7 of the range against
# a "highest" reference where default reads 4e-3 to 7e-3 (the einsum
# formulation 2e-3 to 5e-3), for 2% (long contexts) to 12% (short) of
# the kernel's time, which the HBM bounds either way (same runs)
PRECISION = jax.lax.Precision.HIGHEST


def _precision(dtype):
    """Of the kernel's two contractions: :data:`PRECISION` on float32
    operands, the default (None) on two-byte ones, which are what the MXU
    multiplies."""
    return PRECISION if dtype == jnp.float32 else None


# query rows of one KV head the kernel holds at once: ``rep`` query heads
# times the rows of a block
MAX_GROUP_ROWS = 256


def supported(q_shape, pool_shape, window=None, itemsize=4):
    """Whether the compiled kernel takes these shapes: whole lane tiles
    of ``D``, whole sublane tiles of ``pt`` (of the pools' ``itemsize``),
    and the query rows of a slot all admitting the same columns (one
    token, or a block of rows without a sliding window) with a KV head's
    group of them in ``MAX_GROUP_ROWS``."""
    _, H, T, D = q_shape
    _, Hkv, pt, pool_d = pool_shape
    pack = pool_d // D          # KV heads a pool row (2: heads of 64)
    return (pool_d % 128 == 0 and pack in (1, 2) and pack * D == pool_d
            and pt % sublane_rows(itemsize) == 0 and H % (Hkv * pack) == 0
            and (T == 1 or window is None)
            and (H // Hkv) * T <= MAX_GROUP_ROWS)


def _kernel(bt_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            turn_ref, *, scale, pt, G, NP, window=None):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    T = G * pt

    def live_pages(bb):
        return pos_ref[bb] // pt + 1

    def granules(bb):
        return (live_pages(bb) + G - 1) // G

    def first_page(bb):
        """The page holding the window's first column ``pos - window +
        1``; 0 without a window.  Pages left of it are neither fetched
        nor contracted (their block-table entries may be the trash
        page)."""
        if window is None:
            return 0
        return jnp.maximum(pos_ref[bb] - window + 1, 0) // pt

    def first_granule(bb):
        return first_page(bb) // G

    def copies(bb, g, buf):
        """The granule's page copies, each under the condition it is
        live: started and waited for under the same rule."""
        n = live_pages(bb)
        p0 = first_page(bb)
        for i in range(G):
            page = g * G + i
            phys = bt_ref[bb * NP + jnp.minimum(page, NP - 1)]
            dst = (buf, slice(None), pl.ds(i * pt, pt), slice(None))
            live = page < n if window is None \
                else jnp.logical_and(page < n, page >= p0)
            yield live, (
                pltpu.make_async_copy(k_hbm.at[phys], kbuf.at[dst],
                                      sem.at[buf, 0]),
                pltpu.make_async_copy(v_hbm.at[phys], vbuf.at[dst],
                                      sem.at[buf, 1]))

    def each_live(act, bb, g, buf):
        for live, pair in copies(bb, g, buf):
            @pl.when(live)
            def _(pair=pair):
                for c in pair:
                    act(c)

    def start(bb, g, buf):
        each_live(lambda c: c.start(), bb, g, buf)

    def wait(bb, g, buf):
        each_live(lambda c: c.wait(), bb, g, buf)

    @pl.when(b == 0)
    def _():
        turn_ref[0] = 0
        start(0, first_granule(0), 0)

    pos = pos_ref[b]
    n_g = granules(b)
    q = q_ref[0]                                     # [Hkv, R, D]
    Hkv, R, D = q.shape

    def body(g, carry):
        m, l, acc = carry
        buf = turn_ref[0] % 2
        turn_ref[0] = turn_ref[0] + 1
        # next in flight: this slot's next granule, else the next
        # slot's first
        more = g + 1 < n_g
        nxt_b = jnp.where(more, b, b + 1)
        # (the clamp only keeps the scalar read in bounds on the last
        # slot, where nothing is started)
        nxt_g = jnp.where(more, g + 1,
                          first_granule(jnp.minimum(b + 1, nb - 1)))

        @pl.when(nxt_b < nb)
        def _():
            start(nxt_b, nxt_g, 1 - buf)

        wait(b, g, buf)

        if window is None:
            @pl.when(jnp.logical_not(more))
            def _():
                row = g * T + jax.lax.broadcasted_iota(
                    jnp.int32, (1, T, 1), 1)
                v_ = vbuf[buf]
                vbuf[buf] = jnp.where(row <= pos, v_, jnp.zeros_like(v_))
        else:
            # both ends of the live range can fall inside a granule:
            # rows left of the window were never fetched (stale VMEM),
            # rows right of the position are a page's unwritten tail
            row = g * T + jax.lax.broadcasted_iota(jnp.int32, (1, T, 1), 1)
            v_ = vbuf[buf]
            vbuf[buf] = jnp.where(
                jnp.logical_and(row <= pos, row > pos - window), v_,
                jnp.zeros_like(v_))

        k = kbuf[buf]                                # [Hkv, T, D]
        v = vbuf[buf]
        s = jnp.einsum("hrd,hkd->hrk", q, k,
                       preferred_element_type=jnp.float32,
                       precision=_precision(k.dtype)) * scale
        col = g * T + jax.lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
        keep = col <= pos
        if window is not None:
            keep = jnp.logical_and(keep, col > pos - window)
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "hrk,hkd->hrd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
            precision=_precision(v.dtype))
        return m_new, l, acc

    init = (jnp.full((Hkv, R, 1), -jnp.inf, jnp.float32),
            jnp.zeros((Hkv, R, 1), jnp.float32),
            jnp.zeros((Hkv, R, D), jnp.float32))
    _, l, acc = jax.lax.fori_loop(first_granule(b), n_g, body, init)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "granule",
                                    "window"))
def paged_decode_attention(q, pool_k, pool_v, block_table, positions,
                           scale=None, interpret=False,
                           granule=None, window=None):
    """``q`` [B, H, T, D] over pools ``[P, Hkv, pt, D]`` through
    ``block_table`` [B, NP] int32; ``positions`` [B] int32 is the last
    column each slot admits: every one of its ``T`` query rows attends
    columns ``j <= positions[b]``.  For the one-token step that is the
    slot's pre-step length (the column this step wrote included); for
    a block of ``T`` rows at ``base`` that see their whole block it is
    ``base + T - 1``.  The rows of a KV head's group are then ``rep x
    T`` and the page walk is the one-token step's.  ``granule`` is the
    number of positions fetched and contracted per loop turn, rounded
    to whole pages (None: :func:`granule_positions` of the pools' dtype).
    ``window`` (one row only) adds the lower bound ``j
    > positions[b] - window``: the granule loop starts at the window's
    first page and nothing left of it is fetched.  Pools ``[P, Hkv / 2,
    pt, 2 D]`` hold two KV heads a row (this module's docstring).
    Returns [B, H, T, D]."""
    B, H, T, head_d = q.shape
    P, Hkv, pt, D = pool_k.shape
    NP = block_table.shape[1]
    if T > 1 and window is not None:
        raise ValueError("paged_decode_attention: the rows of a block "
                         "share their columns, a sliding window gives "
                         "each row its own")
    if D not in (head_d, 2 * head_d):
        raise ValueError(f"paged_decode_attention: heads of {head_d} over "
                         f"pool rows of {D}")
    packed = D != head_d
    rep = (H // Hkv) * T              # query rows that share a pool row
    tile = sublane_rows(q.dtype.itemsize)
    R = -(-rep // tile) * tile                       # whole sublane tiles
    if granule is None:
        granule = granule_positions(pool_k.dtype)
    G = max(1, min(granule // pt, NP))
    scale = scale if scale is not None else 1.0 / (head_d ** 0.5)

    if packed:
        # [B, pair, half, rows, d] -> each half's rows in its own lanes
        qh = q.reshape(B, Hkv, 2, rep // 2, head_d)
        none = jnp.zeros_like(qh[:, :, 0])
        qg = jnp.concatenate(
            [jnp.concatenate([qh[:, :, 0], none], axis=-1),
             jnp.concatenate([none, qh[:, :, 1]], axis=-1)], axis=2)
    else:
        qg = q.reshape(B, Hkv, rep, D)
    if R != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R - rep), (0, 0)))
    kw = {} if window is None else {"window": int(window)}
    kernel = functools.partial(_kernel, scale=scale, pt=pt, G=G, NP=NP,
                               **kw)
    blk = pl.BlockSpec((1, Hkv, R, D), lambda b, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[blk,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=blk,
            scratch_shapes=[
                pltpu.VMEM((2, Hkv, G * pt, D), pool_k.dtype),
                pltpu.VMEM((2, Hkv, G * pt, D), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_table.reshape(-1).astype(jnp.int32),
      positions.astype(jnp.int32), qg, pool_k, pool_v)
    if packed:
        half = rep // 2
        out = jnp.stack([out[:, :, :half, :head_d],
                         out[:, :, half:rep, head_d:]], axis=2)
        return out.reshape(B, H, T, head_d)
    return out[:, :, :rep].reshape(B, H, T, D)
