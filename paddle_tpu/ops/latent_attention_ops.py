"""Latent (MLA) attention ops: a layer whose cache is one row a token,
``[c_kv | k_r]`` (the normalised latent and the rotated shared key), in
ONE page pool ``[P, 1, pt, ROW]`` (``latent_pool_shape``: the row padded
to whole lane tiles, 576 -> 640), written by ``kv_pool_write`` as any
pool of one head is.

* ``latent_prefill_attention``: the **expanded** form.  Q and K [B, H, S,
  nope + rope] (keys built from the latent through ``W_kvb``, the shared
  rotated key beside each head's), V [B, H, S, v_dim]: causal attention
  with keys wider than values.  A TPU backend, one device: the Pallas
  kernel ``mla_prefill_attention``; anywhere else the plain einsum
  formulation.
* ``latent_decode_attention``: the **absorbed** form of the decode step.
  QNope [B, H, 1, nope], QRope [B, H, 1, rope], Wkvb [C, H * (nope +
  v_dim)] (the layer's up-projection, read here as ``W_UK`` and ``W_UV``:
  views, not copies), Pool, BlockTable, Positions.  ``q_lat = q_nope
  W_UK^T`` [B, H, C]; scores ``q_lat . c_kv + q_rope . k_r`` over the
  cached rows ``j <= positions[b]``; ``o_lat = sum p c_kv``; ``Out =
  o_lat W_UV`` [B, H, 1, v_dim].  A TPU backend: the Pallas kernel
  ``mla_decode_attention`` reads each live page once; anywhere else the
  gathered view and einsums of the same absorbed arithmetic.

* ``latent_chunk_attention``: a **prefill chunk** over latent pages.
  QNope [1, H, C, nope] and QRope [1, H, C, rope], the chunk's rows at
  ``positions[0] + t``, Wkvb, Pool (the chunk's own rows already written),
  BlockTable [1, NP], Positions [1], Lengths [1] (the chunk's real rows).
  The slot's logical view of its latent rows is gathered ([S, ROW]: 2.5 KB
  a position, 33 MB at 12,800), rows behind the chunk's last real one are
  zeroed (a recycled page's NaN must meet no product), and row ``t``
  attends ``j <= positions[0] + t`` in the EXPANDED arithmetic: ``[k_nope
  | v] = c_kv W_kvb`` a head.  A TPU backend, one device: the Pallas kernel
  ``mla_chunk_attention`` expands each key block in VMEM, so no head's
  keys or values lie in HBM; anywhere else einsums of the same arithmetic
  over the whole view (toy sizes: ``[S, H, nope + v]`` is made).

All are inference only and book which lowering ran
(``attention_lowered_latent_prefill``, ``_latent_decode``,
``_latent_decode_reference``, ``_latent_chunk``,
``_latent_chunk_reference``), a reference with its reason, once, on a TPU.
"""
from __future__ import annotations

from .registry import in_var, register_op, set_out


# cached rows the chunk kernel expands at a time: the engine's span
# attribute ``latent_rows_expanded`` counts in these
CHUNK_BLOCK_K = 512


def latent_pool_shape(num_pages, page_tokens, latent_dim, rope_dim):
    """A latent layer's one page pool: ``[P, 1, pt, ROW]``, ``ROW`` the
    ``latent_dim + rope_dim`` numbers of a row in whole lane tiles."""
    from .pallas.latent_attention import row_lanes

    return [num_pages, 1, page_tokens, row_lanes(latent_dim + rope_dim)]


def _prefill_infer(op, block):
    q, v = in_var(op, block, "Q"), in_var(op, block, "V")
    set_out(op, block, "Out", tuple(q.shape[:-1]) + (v.shape[-1],),
            q.dtype)


@register_op("latent_prefill_attention", infer=_prefill_infer, grad=None)
def _latent_prefill_attention(ctx, op):
    import jax
    import jax.numpy as jnp

    from .attention_ops import _lowered
    from .math_ops import _mm_precision
    from .pallas import latent_attention

    q = ctx.get_input(op, "Q")
    k = ctx.get_input(op, "K")
    v = ctx.get_input(op, "V")
    scale = float(op.attr("scale"))
    S, dv = q.shape[2], v.shape[-1]
    n_mesh = ctx.mesh.devices.size if ctx.mesh is not None else 1
    if (jax.default_backend() == "tpu" and n_mesh == 1 and dv % 128 == 0
            and op.attr("impl", "auto") != "xla"
            and (S <= latent_attention.PREFILL_BLOCK_Q or S % 128 == 0)):
        _lowered("latent_prefill")
        ctx.set_output(op, "Out", latent_attention.mla_prefill_attention(
            q, k, v, scale=scale))
        return
    _lowered("xla")
    prec = _mm_precision(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=prec) * scale
    keep = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(keep, s, jnp.asarray(-1e30, s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    ctx.set_output(op, "Out", jnp.einsum("bhqk,bhkd->bhqd", p, v,
                                         precision=prec).astype(q.dtype))


def _decode_infer(op, block):
    q = in_var(op, block, "QNope")
    set_out(op, block, "Out", tuple(q.shape[:-1])
            + (int(op.attr("value_dim")),), q.dtype)


def absorb(w_kvb, num_heads, nope_dim):
    """``W_kvb`` [C, H * (nope + v)] as the two views the absorbed form
    reads: ``W_UK`` [C, H, nope] and ``W_UV`` [C, H, v]."""
    per_head = w_kvb.reshape(w_kvb.shape[0], num_heads, -1)
    return per_head[:, :, :nope_dim], per_head[:, :, nope_dim:]


@register_op("latent_decode_attention", infer=_decode_infer, grad=None)
def _latent_decode_attention(ctx, op):
    import jax
    import jax.numpy as jnp

    from .attention_ops import _lowered
    from .decode_ops import _gather_pages
    from .math_ops import _mm_precision
    from .pallas import latent_attention

    q_nope = ctx.get_input(op, "QNope")[:, :, 0]            # [B, H, nope]
    q_rope = ctx.get_input(op, "QRope")[:, :, 0]            # [B, H, rope]
    w_kvb = ctx.get_input(op, "Wkvb")
    pool = ctx.get_input(op, "Pool")
    bt = ctx.get_input(op, "BlockTable").astype(jnp.int32)
    pos = ctx.get_input(op, "Positions").astype(jnp.int32)
    scale = float(op.attr("scale"))
    B, H, nope = q_nope.shape
    C, row = w_kvb.shape[0], pool.shape[-1]
    rope = q_rope.shape[-1]
    prec = _mm_precision(q_nope.dtype)
    w_uk, w_uv = absorb(w_kvb, H, nope)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, w_uk, precision=prec)
    on_tpu = jax.default_backend() == "tpu"
    n_mesh = ctx.mesh.devices.size if ctx.mesh is not None else 1
    fits = latent_attention.decode_supported(H, pool.shape, C)
    if on_tpu and n_mesh == 1 and fits:
        q_row = jnp.concatenate(
            [q_lat, q_rope,
             jnp.zeros((B, H, row - C - rope), q_lat.dtype)], axis=-1)
        o_lat = latent_attention.mla_decode_attention(
            q_row, pool, bt, pos, scale=scale, value_dim=C)
        _lowered("latent_decode")
    else:
        rows = _gather_pages(pool, bt)[:, 0]                # [B, S, ROW]
        s = (jnp.einsum("bhc,bsc->bhs", q_lat, rows[..., :C],
                        precision=prec)
             + jnp.einsum("bhr,bsr->bhs", q_rope, rows[..., C:C + rope],
                          precision=prec)) * scale
        live = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] \
            <= pos[:, None]                                  # [B, S]
        s = jnp.where(live[:, None], s, jnp.asarray(-1e30, s.dtype))
        p = jax.nn.softmax(s, axis=-1)
        # masked columns weigh exactly 0; a recycled page's NaN must not
        # reach the sum through 0 * NaN
        vals = jnp.where(live[..., None], rows[..., :C], 0)
        o_lat = jnp.einsum("bhs,bsc->bhc", p, vals, precision=prec)
        reason = None
        if on_tpu:
            reason = (f"latent_decode_attention under a {n_mesh}-device "
                      f"mesh" if n_mesh > 1 else
                      f"latent_decode_attention with {H} heads over pages "
                      f"{pool.shape[1:]} (kernel needs one row a token in "
                      f"whole lane tiles, a latent of whole lane tiles, "
                      f"page_tokens % 8 == 0, heads % 8 == 0)")
        _lowered("latent_decode_reference", reason)
    out = jnp.einsum("bhc,chd->bhd", o_lat, w_uv, precision=prec)
    ctx.set_output(op, "Out", out[:, :, None].astype(q_nope.dtype))


def _chunk_infer(op, block):
    q = in_var(op, block, "QNope")
    set_out(op, block, "Out", tuple(q.shape[:-1])
            + (int(op.attr("value_dim")),), q.dtype)


@register_op("latent_chunk_attention", infer=_chunk_infer, grad=None)
def _latent_chunk_attention(ctx, op):
    import jax
    import jax.numpy as jnp

    from .attention_ops import _lowered
    from .decode_ops import _gather_pages
    from .math_ops import _mm_precision
    from .pallas import latent_attention

    q_nope = ctx.get_input(op, "QNope")                     # [1, H, C, nope]
    q_rope = ctx.get_input(op, "QRope")
    w_kvb = ctx.get_input(op, "Wkvb")
    pool = ctx.get_input(op, "Pool")
    bt = ctx.get_input(op, "BlockTable").astype(jnp.int32)
    base = ctx.get_input(op, "Positions").astype(jnp.int32)
    length = ctx.get_input(op, "Lengths").astype(jnp.int32)
    scale = float(op.attr("scale"))
    B, H, T, nope = q_nope.shape
    if B != 1:
        raise ValueError(f"latent_chunk_attention takes one slot's chunk, "
                         f"got {B}")
    C, rope = w_kvb.shape[0], q_rope.shape[-1]
    rows = _gather_pages(pool, bt)[0, 0]                    # [S, ROW]
    S = rows.shape[0]
    col = jnp.arange(S, dtype=jnp.int32)
    rows = jnp.where((col < base[0] + length[0])[:, None], rows, 0)
    on_tpu = jax.default_backend() == "tpu"
    n_mesh = ctx.mesh.devices.size if ctx.mesh is not None else 1
    dv = w_kvb.shape[1] // H - nope
    fits = latent_attention.chunk_supported(H, T, rows.shape, C, nope, dv)
    if on_tpu and n_mesh == 1 and fits:
        out = latent_attention.mla_chunk_attention(
            q_nope[0], q_rope[0], rows, w_kvb, base, scale=scale,
            nope_dim=nope, latent_dim=C, block_k=CHUNK_BLOCK_K)
        _lowered("latent_chunk")
    else:
        prec = _mm_precision(q_nope.dtype)
        kv = jnp.dot(rows[:, :C], w_kvb, precision=prec).reshape(S, H, -1)
        s = (jnp.einsum("hqd,shd->hqs", q_nope[0], kv[..., :nope],
                        precision=prec)
             + jnp.einsum("hqr,sr->hqs", q_rope[0], rows[:, C:C + rope],
                          precision=prec)) * scale
        t = jnp.arange(T, dtype=jnp.int32)
        keep = col[None, :] <= base[0] + t[:, None]          # [T, S]
        s = jnp.where(keep[None], s, jnp.asarray(-1e30, s.dtype))
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("hqs,shd->hqd", p, kv[..., nope:], precision=prec)
        reason = None
        if on_tpu:
            reason = (f"latent_chunk_attention under a {n_mesh}-device mesh"
                      if n_mesh > 1 else
                      f"latent_chunk_attention with {T} rows over a view "
                      f"{rows.shape}, latent {C}, heads of {nope} | {dv} "
                      f"(kernel needs whole lane tiles of each, rows % 8 "
                      f"== 0)")
        _lowered("latent_chunk_reference", reason)
    ctx.set_output(op, "Out", out[None].astype(q_nope.dtype))
