"""Flash attention for TPU — pallas forward AND backward kernels.

Forward: one grid cell per (batch*head, q-block), online softmax over
kv-blocks held in VMEM, fp32 accumulation on the MXU; emits the softmax
LSE rows for the backward.

Backward (FlashAttention-2 style recompute, no S×S materialization):
  * delta = rowsum(dO ⊙ O) — one fused XLA reduce, [B,H,S].
  * dKV kernel: grid (B*H, kv-block); inner fori over q-blocks
    recomputes p = exp(q·kᵀ − lse), accumulates dV += pᵀ·dO and
    dK += dsᵀ·q with ds = p ⊙ (dO·vᵀ − delta).
  * dQ kernel: grid (B*H, q-block); inner fori over kv-blocks
    accumulates dQ += ds·k.
Both kernels stream blocks from VMEM and skip causally-dead blocks, so
backward memory is O(S) like the forward (round-3 verdict: the previous
jax.vjp-of-scan backward materialized per-block probabilities and lost
to unfused XLA at every length).

Reference analog: the fused attention precursors
(operators/fused/multihead_matmul_op.cu, bert_encoder_functor.cu) — those
fuse QK^T+softmax+PV at fixed small S; this kernel is the long-sequence
capability the reference vintage lacks (SURVEY.md §5 long-context).
"""
from __future__ import annotations

import functools

import jax
import numpy as np

# tuned on TPU v5e (tools/attn_microbench.py, fwd+bwd kernels, d 64,
# B=32 H=12): 512/512 is best or within 2% of best at S=512/1024/2048
# (e.g. S=2048: 35.3ms vs 119.3ms at 128/128 and 77.4ms unfused XLA);
# 2048-wide blocks fail to compile (VMEM)
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def sublane_rows(itemsize):
    """Rows a sublane tile of items of that many bytes: 8 of float32, 16
    of bfloat16."""
    return 32 // itemsize


def _fit_block(block, size, compiled=False):
    """Largest halving of `block` that divides `size`.  For a compiled
    (non-interpret) kernel several blocks must each span whole 128-lane
    tiles: the kernels slice lse/delta/bias rows at `i * block` on the
    lane dim, which Mosaic only accepts when provably 128-aligned."""
    b = min(block, size)
    while size % b:
        b //= 2
    if compiled and b < size and b % 128:
        raise ValueError(
            f"flash attention: sequence length {size} has no block of "
            f"whole 128-lane tiles dividing it (best {b}); pad the "
            f"sequence to a multiple of 128 or keep it within one "
            f"{block}-row block")
    return b


def _block_loop(n_blocks, lower, upper, body, init):
    """`fori_loop` over blocks.  A single block runs at the static index
    0, so a block narrower than a lane tile (short prefill buckets)
    never needs a dynamic lane offset."""
    if n_blocks == 1:
        return body(0, init)
    return jax.lax.fori_loop(lower, upper, body, init)


# ---------------------------------------------------------------------------
# blockwise reference formulation (ring attention + GSPMD multi-device path)
# ---------------------------------------------------------------------------

def blockwise_attention(q, k, v, causal=False, sm_scale=None,
                        block_k=DEFAULT_BLOCK_K, kv_offset=0, bias=None,
                        window=None, mask_block=None, precision=None):
    """Online-softmax attention, scanning kv blocks.

    q: [B, H, Sq, D], k/v: [B, H, Sk, D]. kv_offset shifts the global kv
    position for causal masking (ring attention passes the rotating
    shard's offset). bias: optional [B, Sk] additive score bias
    (padding mask: 0 attend / -1e4 pad), broadcast over heads and q.
    window: with ``causal``, query i attends keys j with
    ``i - window < j <= i`` (a sliding window that counts the token
    itself); None is plain causal.
    mask_block: with ``causal``, query i attends keys j with ``j //
    mask_block <= i // mask_block`` (block-causal: a block of that many
    positions sees itself whole and every earlier block).
    precision: of the two products (None: the backend's default).
    Returns (out, (m, l)): out [B,H,Sq,D], m/l the softmax running stats
    [B,H,Sq] (used by ring accumulation).
    """
    import jax
    import jax.numpy as jnp

    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    bk = _fit_block(block_k, Sk)
    nblocks = Sk // bk

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32).reshape(B, H, nblocks, bk, D)
    vf = v.astype(jnp.float32).reshape(B, H, nblocks, bk, D)
    kf = jnp.moveaxis(kf, 2, 0)  # [n, B, H, bk, D]
    vf = jnp.moveaxis(vf, 2, 0)
    if bias is not None:
        bf = bias.astype(jnp.float32).reshape(B, nblocks, bk)
        bf = jnp.moveaxis(bf, 1, 0)  # [n, B, bk]
        xs = (kf, vf, bf)
    else:
        xs = (kf, vf)

    q_pos = jnp.arange(Sq)[:, None]

    def body(carry, blk):
        m, l, acc, j = carry
        kb, vb = blk[:2]
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb,
                       precision=precision)        # [B,H,Sq,bk]
        if len(blk) == 3:
            s = s + blk[2][:, None, None, :]
        if causal:
            k_pos = j * bk + jnp.arange(bk)[None, :] + kv_offset
            if mask_block is None:
                mask = q_pos >= k_pos
            else:
                mask = q_pos // mask_block >= k_pos // mask_block
            if window is not None:
                mask = mask & (q_pos - k_pos < window)
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        # guards: a fully-masked block/row keeps m at NEG_INF — exp(0)=1
        # must not leak in (ring attention hits this when a whole rotated
        # shard is causally masked)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[..., None]))
        corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb, precision=precision)
        return (m_new, l_new, acc_new, j + 1), None

    # derive initializers from qf so they inherit any shard_map
    # varying-axes type (plain zeros would mismatch the scan carry)
    m0 = qf[..., 0] * 0 + NEG_INF
    l0 = qf[..., 0] * 0
    acc0 = qf * 0
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, acc0, 0), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype), (m, l)


# ---------------------------------------------------------------------------
# pallas forward kernel (emits out + lse)
# ---------------------------------------------------------------------------

def _fa_fwd_kernel(q_ref, k_ref, v_ref, *rest, block_k, causal, scale,
                   seq_k, has_bias=False, window=None, mask_block=None,
                   precision=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if has_bias:
        b_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    qi = pl.program_id(1)
    # None: the MXU's default (float32 operands rounded to bf16)
    prec = {} if precision is None \
        else {"precision": jax.lax.Precision(precision)}
    # At the default the MXU takes its operands as bfloat16 whatever they
    # were, so two-byte q, k, v go in as they lie (the scaled q and the
    # probabilities rounded where they enter their product: the values the
    # MXU made of their float32 forms) and nothing is widened on the way;
    # the sums and the softmax state are float32 either way
    operand = q_ref.dtype if precision is None else jnp.float32
    q = (q_ref[0].astype(jnp.float32) * scale).astype(operand)   # [Bq, D]
    bq, d = q.shape
    nk = seq_k // block_k

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(j * block_k, block_k), :].astype(operand)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(operand)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, **prec)  # [Bq, Bk]
        if has_bias:
            bb = b_ref[0, 0, pl.ds(j * block_k, block_k)].astype(
                jnp.float32)
            s = s + bb[None, :]
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            if mask_block is None:
                keep = q_pos >= k_pos
            else:
                # block-causal: a block of mask_block positions sees
                # itself whole
                keep = q_pos // mask_block >= k_pos // mask_block
            if window is not None:
                keep = keep & (q_pos - k_pos < window)
            s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1, keepdims=True)
        acc_new = acc * corr + jnp.dot(
            p.astype(operand), vb, preferred_element_type=jnp.float32,
            **prec)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    lower = 0
    if causal:
        # kv blocks past this q block's last row are fully masked (with
        # mask_block: past the end of that row's block of positions)
        rows_end = (qi + 1) * bq
        if mask_block is not None:
            rows_end = (rows_end + mask_block - 1) // mask_block \
                * mask_block
        upper = jnp.minimum(nk, (rows_end + block_k - 1) // block_k)
        if window is not None:
            # and so are those wholly left of the band of its first
            # row.  A later row of the block may find the first block
            # it visits fully masked: its m stays NEG_INF there, and the
            # first block that holds one of its keys (its own diagonal
            # at the latest) rescales that contribution by exp(-1e30)=0
            lower = jnp.maximum(0, qi * bq - window + 1) // block_k
    else:
        upper = nk
    m, l, acc = _block_loop(nk, lower, upper, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l_safe))[:, 0]


# K and V of one head sit whole in VMEM, double-buffered; past this many
# bytes the kernel asks for a larger scoped limit than Mosaic's default
# (16 MiB on a v5e, of 128 MiB): a 8192-key float32 head is 16 MiB alone
_VMEM_ASK_OVER = 10 << 20


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   bias=None, window=None, mask_block=None, precision=None):
    """Returns (out [B,H,Sq,D], lse [B,H,Sq] f32)."""
    import jax
    from jax.experimental import pallas as pl

    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    bq = _fit_block(block_q, Sq, compiled=not interpret)
    bk = _fit_block(block_k, Sk, compiled=not interpret)

    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)

    if window is not None and not causal:
        raise ValueError("flash attention: a sliding window needs "
                         "causal=True")
    kw = {} if window is None else {"window": int(window)}
    if mask_block is not None:
        if not causal:
            raise ValueError("flash attention: mask_block needs "
                             "causal=True")
        kw["mask_block"] = int(mask_block)
    if precision is not None:
        kw["precision"] = precision
    kernel = functools.partial(_fa_fwd_kernel, block_k=bk, causal=causal,
                               scale=scale, seq_k=Sk,
                               has_bias=bias is not None, **kw)
    resident = 4 * Sk * D * k.dtype.itemsize      # K, V, two buffers each
    call_kw = {}
    if resident > _VMEM_ASK_OVER and not interpret:
        from jax.experimental.pallas import tpu as pltpu

        call_kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=min(resident + (24 << 20), 100 << 20))
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
    ]
    args = [qr, kr, vr]
    if bias is not None:
        # one bias row per batch, shared across the H heads in the grid;
        # [B, 1, Sk] so the block's trailing dims (1, Sk) match the array
        # (Mosaic tiling requires 8/128-divisible or full-dim blocks)
        in_specs.append(
            pl.BlockSpec((1, 1, Sk), lambda b, i: (b // H, 0, 0)))
        args.append(bias.reshape(B, 1, Sk))
    # lse rides as [BH, 1, Sq]: Mosaic requires block last-two-dims to be
    # (8,128)-divisible or equal to the array dims — (1, bq) on a 2D
    # [BH, Sq] array violates the sublane rule, (1, 1, bq) on 3D is legal
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // bq),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, Sq), np.float32)],
        interpret=interpret, **call_kw,
    )(*args)
    return out.reshape(B, H, Sq, D), lse.reshape(B, H, Sq)


# ---------------------------------------------------------------------------
# pallas backward kernels (FA2 recompute)
# ---------------------------------------------------------------------------

def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *rest,
                   block_q, causal, scale, seq_q, has_bias=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if has_bias:
        b_ref, dk_ref, dv_ref, db_ref = rest
    else:
        dk_ref, dv_ref = rest
    kj = pl.program_id(1)
    kb = k_ref[0].astype(jnp.float32)                  # [Bk, D]
    vb = v_ref[0].astype(jnp.float32)
    bk, d = kb.shape
    nq = seq_q // block_q

    def body(i, carry):
        dk, dv, db = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32) * scale                       # [Bq, D]
        dob = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)]  # [Bq]
        dlt = dl_ref[0, 0, pl.ds(i * block_q, block_q)]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Bq, Bk]
        if has_bias:
            s = s + b_ref[0, 0, :].astype(jnp.float32)[None, :]
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            k_pos = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                  # [Bq, Bk]
        # dV += pᵀ·dO
        dv = dv + jax.lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Bk, D]
        # dp = dO·vᵀ ; ds = p ⊙ (dp − delta)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Bq, Bk]
        ds = p * (dp - dlt[:, None])
        # dK += dsᵀ·(q·scale)  (qb already carries the scale)
        dk = dk + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Bk, D]
        if has_bias:
            db = db + ds.sum(0)
        return dk, dv, db

    if causal:
        lower = (kj * bk) // block_q  # q blocks fully above diag are dead
    else:
        lower = 0
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    db0 = jnp.zeros((bk,), jnp.float32)
    dk, dv, db = _block_loop(nq, lower, nq, body, (dk0, dv0, db0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    if has_bias:
        db_ref[0, 0] = db


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *rest,
                  block_k, causal, scale, seq_k, has_bias=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if has_bias:
        b_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    qi = pl.program_id(1)
    qb = q_ref[0].astype(jnp.float32) * scale          # [Bq, D]
    dob = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                # [Bq]
    dlt = dl_ref[0, 0]
    bq, d = qb.shape
    nk = seq_k // block_k

    def body(j, acc):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Bq, Bk]
        if has_bias:
            bb = b_ref[0, 0, pl.ds(j * block_k, block_k)].astype(
                jnp.float32)
            s = s + bb[None, :]
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dlt[:, None])
        return acc + jnp.dot(ds, kb, preferred_element_type=jnp.float32)

    if causal:
        upper = jnp.minimum(nk, ((qi + 1) * bq + block_k - 1) // block_k)
    else:
        upper = nk
    acc0 = jnp.zeros((bq, d), jnp.float32)
    acc = _block_loop(nk, 0, upper, body, acc0)
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, block_q,
                    block_k, interpret, bias=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    bq = _fit_block(block_q, Sq, compiled=not interpret)
    bk = _fit_block(block_k, Sk, compiled=not interpret)

    # delta = rowsum(dO ⊙ O) — cheap fused XLA reduce
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)

    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)
    gr = g.reshape(B * H, Sq, D)
    lser = lse.reshape(B * H, 1, Sq)
    dltr = delta.reshape(B * H, 1, Sq)
    has_bias = bias is not None

    # ---- dK / dV (+ per-head db) -------------------------------------
    dkv_kernel = functools.partial(
        _fa_dkv_kernel, block_q=bq, causal=causal, scale=scale, seq_q=Sq,
        has_bias=has_bias)
    in_specs = [
        pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),   # q (full)
        pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),   # k block
        pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),   # v block
        pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),   # dO (full)
        pl.BlockSpec((1, 1, Sq), lambda b, j: (b, 0, 0)),   # lse
        pl.BlockSpec((1, 1, Sq), lambda b, j: (b, 0, 0)),   # delta
    ]
    args = [qr, kr, vr, gr, lser, dltr]
    out_specs = [pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
                 pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0))]
    out_shapes = [jax.ShapeDtypeStruct((B * H, Sk, D), k.dtype),
                  jax.ShapeDtypeStruct((B * H, Sk, D), v.dtype)]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, j: (b // H, 0, j)))
        args.append(bias.reshape(B, 1, Sk))
        out_specs.append(pl.BlockSpec((1, 1, bk), lambda b, j: (b, 0, j)))
        out_shapes.append(
            jax.ShapeDtypeStruct((B * H, 1, Sk), np.float32))
    res = pl.pallas_call(
        dkv_kernel,
        grid=(B * H, Sk // bk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(*args)
    dk, dv = res[0].reshape(B, H, Sk, D), res[1].reshape(B, H, Sk, D)
    db = None
    if has_bias:
        # bias rows broadcast over heads (and q) — reduce the per-head sums
        db = res[2].reshape(B, H, Sk).sum(1).astype(bias.dtype)

    # ---- dQ ----------------------------------------------------------
    dq_kernel = functools.partial(
        _fa_dq_kernel, block_k=bk, causal=causal, scale=scale, seq_k=Sk,
        has_bias=has_bias)
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),   # q block
        pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),   # k (full)
        pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),   # v (full)
        pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),   # dO block
        pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),   # lse block
        pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),   # delta block
    ]
    args = [qr, kr, vr, gr, lser, dltr]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, Sk), lambda b, i: (b // H, 0, 0)))
        args.append(bias.reshape(B, 1, Sk))
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * H, Sq // bq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        interpret=interpret,
    )(*args)
    dq = dq.reshape(B, H, Sq, D)
    return dq, dk, dv, db


# ---------------------------------------------------------------------------
# public entries: pallas forward + pallas backward via custom_vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def flash_attention_lse(q, k, v, bias=None, causal=False, sm_scale=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        interpret=False, window=None, mask_block=None,
                        precision=None):
    """Multi-head attention and its softmax statistic, q/k/v: [B, H, S, D]
    -> (out [B, H, Sq, D], lse [B, H, Sq] float32): the forward kernel's
    one call, both of its outputs.  ``lse`` is what the backward kernels
    need beside ``out``; a caller that keeps both (the op's grad lowering)
    runs ``_flash_backward`` without this forward.  It is a residual, not
    a result: its cotangent is dropped.
    ``bias``: an additive [B, Sk] score bias (padding mask), or None.
    ``window`` (with ``causal``): query i attends keys j with
    ``i - window < j <= i``; key blocks wholly left of the band are
    skipped as those above the diagonal are.  ``mask_block`` (with
    ``causal``): the block-causal mask, ``j // mask_block <= i //
    mask_block``.  ``precision`` ("highest"; None is the MXU's default,
    which rounds float32 operands to bf16): of the kernel's two products,
    whatever the mask.  All three forward only."""
    return _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret, bias=bias, window=window,
                          mask_block=mask_block, precision=precision)


def _fal_fwd(q, k, v, bias, *static):
    out, lse = flash_attention_lse.fun(q, k, v, bias, *static)
    return (out, lse), (q, k, v, bias, out, lse)


def _fal_bwd(causal, sm_scale, block_q, block_k, interpret, window,
             mask_block, precision, res, g):
    if window is not None or mask_block is not None \
            or precision is not None:
        raise NotImplementedError(
            "flash attention: the sliding window, the block-causal "
            "mask and a set precision have no backward kernel "
            "(the serving path is forward only)")
    q, k, v, bias, out, lse = res
    return _flash_backward(q, k, v, out, lse, g[0], causal, sm_scale,
                           block_q, block_k, interpret, bias=bias)


flash_attention_lse.defvjp(_fal_fwd, _fal_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False, window=None, mask_block=None,
                    precision=None):
    """``flash_attention_lse`` without a bias, the output alone."""
    return flash_attention_lse(q, k, v, None, causal, sm_scale, block_q,
                               block_k, interpret, window, mask_block,
                               precision)[0]


def flash_attention_bias(q, k, v, bias, causal=False, sm_scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         interpret=False):
    """``flash_attention_lse`` with an additive [B, Sk] score bias
    (padding mask), the output alone."""
    return flash_attention_lse(q, k, v, bias, causal, sm_scale, block_q,
                               block_k, interpret)[0]


# ---------------------------------------------------------------------------
# chunk attention: C query rows at a runtime base over a slot's cache view
# ---------------------------------------------------------------------------
#
# The forward kernel above with three things changed.  The rows sit at
# ``base .. base + C - 1``, ``base`` a scalar the kernel reads (scalar
# prefetch), so the causal and window bounds of its block loop move with
# it.  K and V stay in HBM and come in block by block, two buffers a
# stream, so a block right of the diagonal or left of the window is
# neither fetched nor multiplied and a head's keys need not fit VMEM
# whole.  And query head ``g`` reads KV head ``g // rep`` by indexing: no
# key or value is repeated.  Forward only; no statistic comes back.

def chunk_attention_supported(q_shape, kv_shape, itemsize=4):
    """Whether the compiled chunk kernel takes these shapes: one slot,
    whole lane tiles of ``D``, whole sublane tiles of rows (8 of four-byte
    items, 16 of two-byte ones), key blocks of whole 128-lane tiles that
    divide the view."""
    B, H, C, D = q_shape
    _, Hkv, S, _ = kv_shape
    return (B == 1 and D % 128 == 0 and C % sublane_rows(itemsize) == 0
            and H % Hkv == 0 and S % 128 == 0 and kv_shape[3] == D)


def _chunk_kernel(base_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, *,
                  block_k, n_kblocks, rep, scale, window, precision):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, qi = pl.program_id(0), pl.program_id(1)
    kv_head = h // rep
    # (two-byte operands as they lie, at the MXU's default: the forward
    # kernel's rule; "highest" is for float32 operands, which it keeps whole)
    operand = jnp.float32
    if q_ref.dtype != jnp.float32:
        operand, precision = q_ref.dtype, None
    q = (q_ref[0].astype(jnp.float32) * scale).astype(operand)   # [Bq, D]
    bq, d = q.shape
    row0 = base_ref[0] + qi * bq        # this block's first row's position
    prec = {} if precision is None \
        else {"precision": jax.lax.Precision(precision)}
    # key blocks [lower, upper) hold a column some row of the block admits
    upper = jnp.minimum(n_kblocks, (row0 + bq + block_k - 1) // block_k)
    lower = 0 if window is None else jnp.minimum(
        jnp.maximum(row0 - window + 1, 0) // block_k, upper - 1)

    def copies(j, buf):
        at = (kv_head, pl.ds(j * block_k, block_k), slice(None))
        return (pltpu.make_async_copy(k_hbm.at[at], kbuf.at[buf],
                                      sem.at[buf, 0]),
                pltpu.make_async_copy(v_hbm.at[at], vbuf.at[buf],
                                      sem.at[buf, 1]))

    for c in copies(lower, 0):
        c.start()

    def body(j, carry):
        m, l, acc = carry
        buf = (j - lower) % 2

        @pl.when(j + 1 < upper)
        def _():
            for c in copies(j + 1, 1 - buf):
                c.start()

        for c in copies(j, buf):
            c.wait()
        kb = kbuf[buf].astype(operand)
        vb = vbuf[buf].astype(operand)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, **prec)  # [Bq, Bk]
        q_pos = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        keep = q_pos >= k_pos
        if window is not None:
            keep = keep & (q_pos - k_pos < window)
        s = jnp.where(keep, s, NEG_INF)
        # (a row whose first visited block is wholly left of its window
        # keeps m at NEG_INF there; its own diagonal rescales that away,
        # as in the forward kernel)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1, keepdims=True)
        acc_new = acc * corr + jnp.dot(
            p.astype(operand), vb, preferred_element_type=jnp.float32,
            **prec)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(lower, upper, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "sm_scale", "block_q", "block_k", "interpret", "precision"))
def chunk_attention(q, k, v, base, window=None, sm_scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False, precision="highest"):
    """``q`` [1, H, C, D], the chunk's rows at absolute positions ``base
    .. base + C - 1`` (``base`` [1] int32, read at run time), over the
    slot's logical cache view ``k`` / ``v`` [1, Hkv, S, D], the chunk's own
    rows already in it: row ``t`` attends columns ``j <= base + t`` and,
    under ``window``, ``j > base + t - window``.  Query head ``g`` reads
    KV head ``g // (H / Hkv)`` where it lies.  ``precision``: of the two
    products ("highest": float32 operands whole, as the paged decode
    kernel takes them; None: the MXU's default).  Returns [1, H, C, D]."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, H, C, D = q.shape
    _, Hkv, S, _ = k.shape
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    bq = _fit_block(block_q, C)
    bk = _fit_block(block_k, S, compiled=not interpret)
    kernel = functools.partial(
        _chunk_kernel, block_k=bk, n_kblocks=S // bk, rep=H // Hkv,
        scale=scale, window=None if window is None else int(window),
        precision=precision)
    blk = pl.BlockSpec((1, bq, D), lambda h, i, *_: (h, i, 0))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((H, C, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, C // bq),
            in_specs=[blk, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=blk,
            scratch_shapes=[pltpu.VMEM((2, bk, D), k.dtype),
                            pltpu.VMEM((2, bk, D), v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="chunk_attention",
    )(base.astype(jnp.int32).reshape(1), q.reshape(H, C, D),
      k.reshape(Hkv, S, D), v.reshape(Hkv, S, D))
    return out.reshape(1, H, C, D)


# ---------------------------------------------------------------------------
# packed-QKV kernels: transpose-free attention on [B, S, 3H]
# ---------------------------------------------------------------------------
#
# The standard path costs ~2.4 GB/step of pure layout movement on the
# seq-512 BERT bench (xprof: the [B,S,3H] -> [3,B,h,S,d] transpose, the
# q/k/v slices, the ctx transpose back, and all their grads).  These
# kernels consume the fused QKV projection output directly: the grid is
# (batch, 128-lane column chunk, row block) and each cell reads its
# head-pair's columns via BlockSpec index maps (768 = 6 x 128, so chunk
# boundaries are lane-aligned and Mosaic-legal).  head_dim 64 packs two
# heads per chunk (static halves inside the kernel); head_dim 128 maps
# one-to-one.  No transpose, slice, or concat ever materializes in HBM
# on the forward; the backward assembles d(qkv) with one cheap concat.

def _packed_dims(qkv_shape, num_heads):
    B, S, threeH = qkv_shape
    H = threeH // 3
    D = H // num_heads
    if threeH != 3 * H or H % 128 or D not in (64, 128):
        raise ValueError(
            f"flash_attention_packed needs hidden % 128 == 0 and head_dim "
            f"in (64, 128); got qkv {qkv_shape}, num_heads {num_heads}")
    return B, S, H, D, H // 128, 128 // D


def _fp_fwd_kernel(q_ref, k_ref, v_ref, *rest, block_k, causal, scale,
                   seq_k, head_dim, hpc, has_bias=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if has_bias:
        b_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    qi = pl.program_id(2)
    nk = seq_k // block_k
    outs = []
    for h in range(hpc):
        q = q_ref[0][:, h * head_dim:(h + 1) * head_dim].astype(
            jnp.float32) * scale                       # [Bq, D]
        bq = q.shape[0]

        def body(j, carry, q=q, h=h, bq=bq):
            m, l, acc = carry
            kb = k_ref[0, pl.ds(j * block_k, block_k), :][
                :, h * head_dim:(h + 1) * head_dim].astype(jnp.float32)
            vb = v_ref[0, pl.ds(j * block_k, block_k), :][
                :, h * head_dim:(h + 1) * head_dim].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # [Bq, Bk]
            if has_bias:
                s = s + b_ref[0, 0, pl.ds(j * block_k, block_k)].astype(
                    jnp.float32)[None, :]
            if causal:
                q_pos = qi * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 0)
                k_pos = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1, keepdims=True)
            acc_new = acc * corr + jnp.dot(
                p, vb, preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m0 = jnp.full((q.shape[0], 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((q.shape[0], 1), jnp.float32)
        acc0 = jnp.zeros((q.shape[0], head_dim), jnp.float32)
        if causal:
            upper = jnp.minimum(
                nk, ((qi + 1) * q.shape[0] + block_k - 1) // block_k)
        else:
            upper = nk
        m, l, acc = _block_loop(nk, 0, upper, body, (m0, l0, acc0))
        l_safe = jnp.maximum(l, 1e-30)
        outs.append(acc / l_safe)
        lse_ref[0, 0, h] = (m + jnp.log(l_safe))[:, 0]
    o_ref[0] = jnp.concatenate(outs, axis=1).astype(o_ref.dtype)


def _packed_forward(qkv, num_heads, causal, sm_scale, block_q, block_k,
                    interpret, bias=None):
    """qkv [B, S, 3H] -> (out [B, S, H], lse [B, HP, hpc, S] f32)."""
    import jax
    from jax.experimental import pallas as pl

    B, S, H, D, HP, hpc = _packed_dims(qkv.shape, num_heads)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    bq = _fit_block(block_q, S, compiled=not interpret)
    bk = _fit_block(block_k, S, compiled=not interpret)

    kernel = functools.partial(
        _fp_fwd_kernel, block_k=bk, causal=causal, scale=scale, seq_k=S,
        head_dim=D, hpc=hpc, has_bias=bias is not None)
    in_specs = [
        pl.BlockSpec((1, bq, 128), lambda b, hp, i: (b, i, hp)),
        pl.BlockSpec((1, S, 128), lambda b, hp, i: (b, 0, HP + hp)),
        pl.BlockSpec((1, S, 128), lambda b, hp, i: (b, 0, 2 * HP + hp)),
    ]
    args = [qkv, qkv, qkv]
    if bias is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, S), lambda b, hp, i: (b, 0, 0)))
        args.append(bias.reshape(B, 1, S))
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, HP, S // bq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, 128), lambda b, hp, i: (b, i, hp)),
            pl.BlockSpec((1, 1, hpc, bq), lambda b, hp, i: (b, hp, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, S, H), qkv.dtype),
                   jax.ShapeDtypeStruct((B, HP, hpc, S), np.float32)],
        interpret=interpret,
    )(*args)
    return out, lse


def _fp_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *rest,
                   block_q, causal, scale, seq_q, head_dim, hpc,
                   has_bias=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if has_bias:
        b_ref, dk_ref, dv_ref, db_ref = rest
    else:
        dk_ref, dv_ref = rest
    kj = pl.program_id(2)
    nq = seq_q // block_q
    db_acc = None
    dk_parts, dv_parts = [], []
    for h in range(hpc):
        kb = k_ref[0][:, h * head_dim:(h + 1) * head_dim].astype(
            jnp.float32)                               # [Bk, D]
        vb = v_ref[0][:, h * head_dim:(h + 1) * head_dim].astype(
            jnp.float32)
        bk = kb.shape[0]

        def body(i, carry, kb=kb, vb=vb, h=h, bk=bk):
            dk, dv, db = carry
            qb = q_ref[0, pl.ds(i * block_q, block_q), :][
                :, h * head_dim:(h + 1) * head_dim].astype(
                jnp.float32) * scale                   # [Bq, D]
            dob = do_ref[0, pl.ds(i * block_q, block_q), :][
                :, h * head_dim:(h + 1) * head_dim].astype(jnp.float32)
            lse = lse_ref[0, 0, h, pl.ds(i * block_q, block_q)]
            dlt = dl_ref[0, 0, h, pl.ds(i * block_q, block_q)]
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # [Bq, Bk]
            if has_bias:
                s = s + b_ref[0, 0, pl.ds(kj * bk, bk)].astype(
                    jnp.float32)[None, :]
            if causal:
                q_pos = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, bk), 0)
                k_pos = kj * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, bk), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dv = dv + jax.lax.dot_general(
                p, dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                dob, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dlt[:, None])
            dk = dk + jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if has_bias:
                db = db + ds.sum(0)
            return dk, dv, db

        lower = (kj * bk) // block_q if causal else 0
        dk0 = jnp.zeros((bk, head_dim), jnp.float32)
        dv0 = jnp.zeros((bk, head_dim), jnp.float32)
        db0 = jnp.zeros((bk,), jnp.float32)
        dk, dv, db = _block_loop(nq, lower, nq, body, (dk0, dv0, db0))
        dk_parts.append(dk)
        dv_parts.append(dv)
        db_acc = db if db_acc is None else db_acc + db
    dk_ref[0] = jnp.concatenate(dk_parts, axis=1).astype(dk_ref.dtype)
    dv_ref[0] = jnp.concatenate(dv_parts, axis=1).astype(dv_ref.dtype)
    if has_bias:
        # the db row block spans full S and is revisited across the kv
        # grid; each cell writes its own bk-wide chunk
        bk = dk_ref.shape[1]
        db_ref[0, 0, pl.ds(kj * bk, bk)] = db_acc


def _fp_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *rest,
                  block_k, causal, scale, seq_k, head_dim, hpc,
                  has_bias=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if has_bias:
        b_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    qi = pl.program_id(2)
    nk = seq_k // block_k
    dq_parts = []
    for h in range(hpc):
        qb = q_ref[0][:, h * head_dim:(h + 1) * head_dim].astype(
            jnp.float32) * scale
        dob = do_ref[0][:, h * head_dim:(h + 1) * head_dim].astype(
            jnp.float32)
        lse = lse_ref[0, 0, h]
        dlt = dl_ref[0, 0, h]
        bq = qb.shape[0]

        def body(j, acc, qb=qb, dob=dob, lse=lse, dlt=dlt, h=h, bq=bq):
            kb = k_ref[0, pl.ds(j * block_k, block_k), :][
                :, h * head_dim:(h + 1) * head_dim].astype(jnp.float32)
            vb = v_ref[0, pl.ds(j * block_k, block_k), :][
                :, h * head_dim:(h + 1) * head_dim].astype(jnp.float32)
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if has_bias:
                s = s + b_ref[0, 0, pl.ds(j * block_k, block_k)].astype(
                    jnp.float32)[None, :]
            if causal:
                q_pos = qi * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 0)
                k_pos = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dp = jax.lax.dot_general(
                dob, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dlt[:, None])
            return acc + jnp.dot(ds, kb,
                                 preferred_element_type=jnp.float32)

        if causal:
            upper = jnp.minimum(nk, ((qi + 1) * bq + block_k - 1)
                                // block_k)
        else:
            upper = nk
        acc0 = jnp.zeros((bq, head_dim), jnp.float32)
        acc = _block_loop(nk, 0, upper, body, acc0)
        dq_parts.append(acc * scale)
    dq_ref[0] = jnp.concatenate(dq_parts, axis=1).astype(dq_ref.dtype)


def _packed_backward(qkv, num_heads, out, lse, g, causal, sm_scale,
                     block_q, block_k, interpret, bias=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, S, H, D, HP, hpc = _packed_dims(qkv.shape, num_heads)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    bq = _fit_block(block_q, S, compiled=not interpret)
    bk = _fit_block(block_k, S, compiled=not interpret)
    has_bias = bias is not None

    # delta = rowsum(dO ⊙ O) per head, laid out to match lse
    prod = (g.astype(jnp.float32) * out.astype(jnp.float32))
    delta = prod.reshape(B, S, HP, hpc, D).sum(-1)       # [B,S,HP,hpc]
    delta = jnp.moveaxis(delta, 1, 3)                    # [B,HP,hpc,S]

    common_specs = [
        pl.BlockSpec((1, S, 128), lambda b, hp, j: (b, 0, hp)),        # q
        pl.BlockSpec((1, S, 128), lambda b, hp, j: (b, 0, HP + hp)),   # k
        pl.BlockSpec((1, S, 128), lambda b, hp, j: (b, 0, 2 * HP + hp)),
        pl.BlockSpec((1, S, 128), lambda b, hp, j: (b, 0, hp)),        # dO
        pl.BlockSpec((1, 1, hpc, S), lambda b, hp, j: (b, hp, 0, 0)),  # lse
        pl.BlockSpec((1, 1, hpc, S), lambda b, hp, j: (b, hp, 0, 0)),  # dlt
    ]

    # ---- dK / dV ------------------------------------------------------
    dkv_kernel = functools.partial(
        _fp_dkv_kernel, block_q=bq, causal=causal, scale=scale, seq_q=S,
        head_dim=D, hpc=hpc, has_bias=has_bias)
    in_specs = list(common_specs)
    in_specs[1] = pl.BlockSpec((1, bk, 128),
                               lambda b, hp, j: (b, j, HP + hp))
    in_specs[2] = pl.BlockSpec((1, bk, 128),
                               lambda b, hp, j: (b, j, 2 * HP + hp))
    args = [qkv, qkv, qkv, g, lse, delta]
    out_specs = [pl.BlockSpec((1, bk, 128), lambda b, hp, j: (b, j, hp)),
                 pl.BlockSpec((1, bk, 128), lambda b, hp, j: (b, j, hp))]
    out_shapes = [jax.ShapeDtypeStruct((B, S, H), qkv.dtype),
                  jax.ShapeDtypeStruct((B, S, H), qkv.dtype)]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, S), lambda b, hp, j: (b, 0, 0)))
        args.append(bias.reshape(B, 1, S))
        out_specs.append(pl.BlockSpec(
            (1, 1, S), lambda b, hp, j: (b * HP + hp, 0, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((B * HP, 1, S), np.float32))
    res = pl.pallas_call(
        dkv_kernel,
        grid=(B, HP, S // bk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(*args)
    dk, dv = res[0], res[1]
    db = None
    if has_bias:
        db = res[2].reshape(B, HP, S).sum(1).astype(bias.dtype)

    # ---- dQ -----------------------------------------------------------
    dq_kernel = functools.partial(
        _fp_dq_kernel, block_k=bk, causal=causal, scale=scale, seq_k=S,
        head_dim=D, hpc=hpc, has_bias=has_bias)
    in_specs = list(common_specs)
    in_specs[0] = pl.BlockSpec((1, bq, 128), lambda b, hp, i: (b, i, hp))
    in_specs[3] = pl.BlockSpec((1, bq, 128), lambda b, hp, i: (b, i, hp))
    in_specs[4] = pl.BlockSpec((1, 1, hpc, bq),
                               lambda b, hp, i: (b, hp, 0, i))
    in_specs[5] = pl.BlockSpec((1, 1, hpc, bq),
                               lambda b, hp, i: (b, hp, 0, i))
    args = [qkv, qkv, qkv, g, lse, delta]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, S), lambda b, hp, i: (b, 0, 0)))
        args.append(bias.reshape(B, 1, S))
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, HP, S // bq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, 128), lambda b, hp, i: (b, i, hp)),
        out_shape=jax.ShapeDtypeStruct((B, S, H), qkv.dtype),
        interpret=interpret,
    )(*args)

    dqkv = jnp.concatenate([dq, dk, dv], axis=-1)
    return dqkv, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def flash_attention_packed_lse(qkv, bias=None, num_heads=None, causal=False,
                               sm_scale=None, block_q=DEFAULT_BLOCK_Q,
                               block_k=DEFAULT_BLOCK_K, interpret=False):
    """Transpose-free attention on the fused projection, and its softmax
    statistic: qkv [B, S, 3H] -> (out [B, S, H], lse [B, heads, S]
    float32), as ``flash_attention_lse``.  ``bias``: an additive [B, S]
    score bias, or None.  Requires H % 128 == 0 and head_dim in
    (64, 128)."""
    out, lse = _packed_forward(qkv, num_heads, causal, sm_scale, block_q,
                               block_k, interpret, bias=bias)
    # the kernel's [B, H/128, heads a chunk, S]: the heads in order
    return out, lse.reshape(lse.shape[0], num_heads, lse.shape[-1])


def packed_backward(qkv, bias, out, lse, g, num_heads, causal, sm_scale,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    """(dqkv, dbias or None) off ``flash_attention_packed_lse``'s saved
    ``out`` and ``lse`` [B, heads, S]."""
    B, S, H, _, HP, hpc = _packed_dims(qkv.shape, num_heads)
    return _packed_backward(qkv, num_heads, out, lse.reshape(B, HP, hpc, S),
                            g, causal, sm_scale, block_q, block_k,
                            interpret, bias=bias)


def _fpl_fwd(qkv, bias, *static):
    out, lse = flash_attention_packed_lse.fun(qkv, bias, *static)
    return (out, lse), (qkv, bias, out, lse)


def _fpl_bwd(num_heads, causal, sm_scale, block_q, block_k, interpret,
             res, g):
    qkv, bias, out, lse = res
    return packed_backward(qkv, bias, out, lse, g[0], num_heads, causal,
                           sm_scale, block_q, block_k, interpret)


flash_attention_packed_lse.defvjp(_fpl_fwd, _fpl_bwd)


def flash_attention_packed(qkv, num_heads, causal=False, sm_scale=None,
                           block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                           interpret=False):
    """``flash_attention_packed_lse`` without a bias, the output alone."""
    return flash_attention_packed_lse(qkv, None, num_heads, causal,
                                      sm_scale, block_q, block_k,
                                      interpret)[0]


def flash_attention_packed_bias(qkv, bias, num_heads, causal=False,
                                sm_scale=None, block_q=DEFAULT_BLOCK_Q,
                                block_k=DEFAULT_BLOCK_K, interpret=False):
    """``flash_attention_packed_lse`` with an additive [B, S] score bias,
    the output alone."""
    return flash_attention_packed_lse(qkv, bias, num_heads, causal,
                                      sm_scale, block_q, block_k,
                                      interpret)[0]
