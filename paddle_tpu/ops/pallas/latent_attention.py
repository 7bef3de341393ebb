"""Latent (MLA) attention for TPU: the two kernels of a layer whose cache
is ONE row a token, ``[c_kv | k_r]``, not K and V of heads.

The cache is a per-layer pool ``[P, 1, pt, ROW]`` with the block table of
the paged decoder (``ops/decode_ops.py``): a row holds the token's
normalised latent ``c_kv`` (``C`` numbers, 512), then its rotated shared
key ``k_r`` (``R``, 64), then zeros up to ``ROW``, whole lane tiles (640
for 576: a page of 16 tokens is 40 KB of float32, contiguous).

* :func:`mla_decode_attention`, the **absorbed** form of the decode step:
  a slot's ``H`` query rows ``[q_lat | q_rope | 0]`` (``q_lat = q_nope
  W_UK^T``, made outside) meet each cached row once: scores ``q . row``
  over all ``ROW`` lanes, online softmax, and the VALUE is the first ``C``
  lanes of the same row, so each live page is read once from HBM and
  feeds both products.  The page walk is ``paged_attention.py``'s: grid
  ``(B,)``, block table and positions in SMEM, the pool left in HBM,
  granules of 128 positions double-buffered in VMEM with the next
  granule, or the next slot's first, in flight.  Rows beyond a slot's
  live length are zeroed in the buffer before either product, so a
  recycled page's garbage (NaN included) reaches neither.
* :func:`mla_prefill_attention`, the **expanded** form of the prefill:
  causal flash attention of ``H`` heads whose keys (``nope | rope``, 192)
  are wider than their values (128).  Keys and queries are padded to
  whole lane tiles (256: the MXU contracts 192 in two passes of 128
  either way); K and V of one head sit whole in VMEM.

* :func:`mla_chunk_attention`, the **expanded form over latent rows** of
  a prefill chunk: ``C`` new rows at ``base`` attend the slot's cached
  latent rows plus themselves.  Per head the cached rows come in key
  block by key block, each block is expanded through the head's columns
  of ``W_kvb`` in VMEM (``[k_nope | v] = c_kv W_kvb,h``) and meets the
  head's whole chunk of queries under an online softmax, so no key or
  value of any head ever lies in HBM and no temporary grows with
  ``context x heads``.  A pair costs the expanded form's 640 FLOP a head
  and a cached row is re-expanded once a chunk (the absorbed form at ``C
  x H`` query rows costs 2,176 a pair; PERF.md section 6, PR 56, has both
  on the chip).

All feed the MXU float32 operands whole (``PRECISION``), as the paged
kernel does.  Each ``pallas_call`` has a ``name`` of its own, which is
what a trace matches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _block_loop, _fit_block
from .paged_attention import GRANULE_POSITIONS, PRECISION

LANES = 128


def row_lanes(width: int) -> int:
    """A latent row of ``width`` numbers as the pool keeps it: whole lane
    tiles."""
    return -(-int(width) // LANES) * LANES


def decode_supported(num_heads, pool_shape, value_dim):
    """Whether the compiled decode kernel takes these shapes."""
    _, one, pt, row = pool_shape
    return (one == 1 and row % LANES == 0 and value_dim % LANES == 0
            and value_dim <= row and pt % 8 == 0 and num_heads % 8 == 0)


def _decode_kernel(bt_ref, pos_ref, q_ref, pool_hbm, o_ref, buf, sem,
                   turn_ref, *, scale, pt, G, NP, C):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    T = G * pt

    def live_pages(bb):
        return pos_ref[bb] // pt + 1

    def granules(bb):
        return (live_pages(bb) + G - 1) // G

    def copies(bb, g, slot):
        n = live_pages(bb)
        for i in range(G):
            page = g * G + i
            phys = bt_ref[bb * NP + jnp.minimum(page, NP - 1)]
            yield page < n, pltpu.make_async_copy(
                pool_hbm.at[phys, 0], buf.at[slot, pl.ds(i * pt, pt)],
                sem.at[slot])

    def each_live(act, bb, g, slot):
        for live, copy in copies(bb, g, slot):
            @pl.when(live)
            def _(copy=copy):
                act(copy)

    def start(bb, g, slot):
        each_live(lambda c: c.start(), bb, g, slot)

    def wait(bb, g, slot):
        each_live(lambda c: c.wait(), bb, g, slot)

    @pl.when(b == 0)
    def _():
        turn_ref[0] = 0
        start(0, 0, 0)

    pos = pos_ref[b]
    n_g = granules(b)
    q = q_ref[0]                                     # [H, ROW]
    H = q.shape[0]

    def body(g, carry):
        m, l, acc = carry
        slot = turn_ref[0] % 2
        turn_ref[0] = turn_ref[0] + 1
        more = g + 1 < n_g
        nxt_b = jnp.where(more, b, b + 1)
        nxt_g = jnp.where(more, g + 1, 0)

        @pl.when(nxt_b < nb)
        def _():
            start(nxt_b, nxt_g, 1 - slot)

        wait(b, g, slot)

        @pl.when(jnp.logical_not(more))
        def _():
            # the last granule: rows behind the live length are a page's
            # unwritten tail or stale VMEM; they are keys AND values here
            row = g * T + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
            rows = buf[slot]
            buf[slot] = jnp.where(row <= pos, rows, jnp.zeros_like(rows))

        rows = buf[slot]                             # [T, ROW]
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=PRECISION) * scale             # [H, T]
        col = g * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        s = jnp.where(col <= pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(
            p, rows[:, :C], preferred_element_type=jnp.float32,
            precision=PRECISION)
        return m_new, l, acc

    init = (jnp.full((H, 1), -jnp.inf, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, C), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_g, body, init)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "value_dim",
                                             "interpret", "granule"))
def mla_decode_attention(q, pool, block_table, positions, *, scale,
                         value_dim, interpret=False,
                         granule=GRANULE_POSITIONS):
    """``q`` [B, H, ROW] (a slot's absorbed query rows, ``[q_lat | q_rope
    | 0]``) over the latent pool ``[P, 1, pt, ROW]`` through
    ``block_table`` [B, NP] int32; ``positions`` [B] int32 is the last
    column each slot admits (the row this step wrote included).  Returns
    ``o_lat`` [B, H, value_dim]: the softmax-weighted sum of the rows'
    first ``value_dim`` lanes."""
    B, H, row = q.shape
    P, _, pt, pool_row = pool.shape
    if row != pool_row:
        raise ValueError(f"mla_decode_attention: query rows of {row} over "
                         f"pool rows of {pool_row}")
    NP = block_table.shape[1]
    G = max(1, min(granule // pt, NP))
    kernel = functools.partial(_decode_kernel, scale=float(scale), pt=pt,
                               G=G, NP=NP, C=int(value_dim))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, int(value_dim)), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, row), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, int(value_dim)),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, G * pt, row), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode_attention",
    )(block_table.reshape(-1).astype(jnp.int32),
      positions.astype(jnp.int32), q, pool)


# ---------------------------------------------------------------------------
# the expanded form: causal flash attention, keys wider than values
# ---------------------------------------------------------------------------

PREFILL_BLOCK_Q = 256
PREFILL_BLOCK_K = 512


def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, scale, seq_k):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [Bq, Dk]
    bq = q.shape[0]
    dv = v_ref.shape[-1]
    nk = seq_k // block_k

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=PRECISION)
        q_pos = qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        return (m_new, l * corr + p.sum(-1, keepdims=True),
                acc * corr + jnp.dot(p, vb,
                                     preferred_element_type=jnp.float32,
                                     precision=PRECISION))

    init = (jnp.full((bq, 1), NEG_INF, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32),
            jnp.zeros((bq, dv), jnp.float32))
    # key blocks past this query block's last row are fully masked
    upper = jnp.minimum(nk, ((qi + 1) * bq + block_k - 1) // block_k)
    _, l, acc = _block_loop(nk, 0, upper, body, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_prefill_attention(q, k, v, *, scale, interpret=False):
    """Causal attention of ``q`` [B, H, S, Dk] over ``k`` [B, H, S, Dk]
    and ``v`` [B, H, S, Dv], ``Dv`` whole lane tiles; ``Dk`` is padded
    with zeros to whole lane tiles here.  Returns [B, H, S, Dv]."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    pad = row_lanes(dk) - dk
    if pad:
        widths = ((0, 0), (0, 0), (0, 0), (0, pad))
        q, k = jnp.pad(q, widths), jnp.pad(k, widths)
    dk += pad
    bq = _fit_block(PREFILL_BLOCK_Q, S, compiled=not interpret)
    bk = _fit_block(PREFILL_BLOCK_K, S, compiled=not interpret)
    kernel = functools.partial(_prefill_kernel, block_k=bk,
                               scale=float(scale), seq_k=S)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, S // bq),
        in_specs=[pl.BlockSpec((1, bq, dk), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, S, dk), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, S, dv), lambda b, i: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, dv), q.dtype),
        interpret=interpret,
        name="mla_prefill_attention",
    )(q.reshape(B * H, S, dk), k.reshape(B * H, S, dk),
      v.reshape(B * H, S, dv))
    return out.reshape(B, H, S, dv)


# ---------------------------------------------------------------------------
# a prefill chunk over latent rows: expanded block by block, in VMEM
# ---------------------------------------------------------------------------

# q (two parts), a block of rows, a head's W_kvb columns and the output,
# each double-buffered, beside scores and probabilities of [C, block_k]:
# about 12 MB at 1024 rows, over the default scoped 16 MB with Mosaic's own
CHUNK_VMEM_BYTES = 64 * 1024 * 1024


def chunk_supported(num_heads, chunk_len, view_shape, latent_dim, nope_dim,
                    value_dim):
    """Whether the compiled chunk kernel takes these shapes."""
    S, row = view_shape
    return (row % LANES == 0 and latent_dim % LANES == 0
            and latent_dim < row and nope_dim % LANES == 0
            and value_dim % LANES == 0 and chunk_len % 8 == 0
            and S % LANES == 0)


def _chunk_kernel(base_ref, qn_ref, qr_ref, rows_ref, w_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, block_k, ck, dn):
    j = pl.program_id(1)
    nk = pl.num_programs(1)
    base = base_ref[0]
    rows_q = qn_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # a block right of the chunk's last row holds no admitted column
    @pl.when(j * block_k < base + rows_q)
    def _():
        lat = rows_ref[...].astype(jnp.float32)          # [bk, ROW]
        kv = jnp.dot(lat[:, :ck], w_ref[...].astype(jnp.float32),
                     preferred_element_type=jnp.float32,
                     precision=PRECISION)                # [bk, dn + dv]
        contract = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(
            qn_ref[0].astype(jnp.float32), kv[:, :dn], contract,
            preferred_element_type=jnp.float32, precision=PRECISION)
            + jax.lax.dot_general(
                qr_ref[0].astype(jnp.float32), lat[:, ck:], contract,
                preferred_element_type=jnp.float32,
                precision=PRECISION)) * scale            # [C_q, bk]
        q_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, kv[:, dn:], preferred_element_type=jnp.float32,
            precision=PRECISION)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "nope_dim", "latent_dim", "block_k", "interpret"))
def mla_chunk_attention(q_nope, q_rope, rows, w_kvb, base, *, scale,
                        nope_dim, latent_dim, block_k=512,
                        interpret=False):
    """``q_nope`` [H, C, nope] and ``q_rope`` [H, C, rope], the chunk's
    rows at absolute positions ``base .. base + C - 1`` (``base`` [1]
    int32, read at run time), over ``rows`` [S, ROW], the slot's logical
    view of its latent pages (``[c_kv | k_r | 0]`` a row, the chunk's own
    rows already in it; rows no query admits must be finite: the caller
    zeroes what lies behind the chunk), with ``w_kvb`` [latent_dim, H *
    (nope + v)].  Row ``t`` attends columns ``j <= base + t``.  Returns
    [H, C, v]."""
    H, C, dn = q_nope.shape
    S, row = rows.shape
    ck = int(latent_dim)
    per_head = w_kvb.shape[1] // H
    dv = per_head - dn
    rest = row - ck
    # the rotated part over the row's lanes behind the latent: zeros meet
    # the row's zero padding
    q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, rest - q_rope.shape[-1])))
    bk = _fit_block(block_k, S, compiled=not interpret)
    kernel = functools.partial(_chunk_kernel, scale=float(scale),
                               block_k=bk, ck=ck, dn=dn)

    def rows_at(h, j, base):
        # past the last admitted block the index stays: nothing is fetched
        return jnp.minimum(j, (base[0] + C - 1) // bk), 0

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((H, C, dv), q_nope.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, S // bk),
            in_specs=[pl.BlockSpec((1, C, dn), lambda h, j, *_: (h, 0, 0)),
                      pl.BlockSpec((1, C, rest), lambda h, j, *_: (h, 0, 0)),
                      pl.BlockSpec((bk, row), rows_at),
                      pl.BlockSpec((ck, per_head), lambda h, j, *_: (0, h))],
            out_specs=pl.BlockSpec((1, C, dv), lambda h, j, *_: (h, 0, 0)),
            scratch_shapes=[pltpu.VMEM((C, 1), jnp.float32),
                            pltpu.VMEM((C, 1), jnp.float32),
                            pltpu.VMEM((C, dv), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=CHUNK_VMEM_BYTES),
        interpret=interpret,
        name="mla_chunk_attention",
    )(base.astype(jnp.int32).reshape(1), q_nope, q_rope, rows, w_kvb)
