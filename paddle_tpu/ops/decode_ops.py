"""Autoregressive-decode ops: block-paged KV-cache write + cached attention.

New capability for the generation serving path (no reference analog —
the reference vintage predates KV-cached LLM serving).  They make a
decoder block's attention O(1) per step instead of O(n²) over the
prefix, over a block-paged cache (PagedAttention, Kwon et al., SOSP
'23): a flat per-layer pool ``[num_pages, n_kv, page_tokens, D]`` and a
per-slot block table that maps logical page index -> physical page.

* ``kv_pool_write`` — scatter the step's fresh K/V rows into the pages
  the block table names.  The output aliases the pool *variable name*,
  so the executor classifies the pool as mutated persistable state →
  donated buffer → XLA updates it in place in HBM (no pool copy per
  token).  One algorithm, three scatter windows, chosen from the shape of
  the write, because XLA:TPU lays a scatter's operand out index dims
  major, window dims minor, and copies a whole pool in and out of any
  layout that is not the pool's own row-major one: a decode step's rows
  (``T == 1``, or ``per_head``) go in under a ``(page, head, offset)``
  index with the ``D`` lanes as the window; a whole-prompt prefill
  (``whole_pages``: one slot, from a page boundary, a whole number of
  pages) goes in page by page, the window a whole ``[Hkv, pt, D]`` page;
  only a chunk at a runtime base position (prefill chunks, prefix-reuse
  tails, the verify program) keeps the ``[Hkv, D]`` window a row, and
  pays the pool's re-layout.  ``kv_pool_write_pages`` /
  ``kv_pool_write_rows`` count which a ``T > 1`` write lowered to.
* ``kv_pool_gather`` — reconstruct a slot's logical
  ``[B, n_kv, NP*page_tokens, D]`` cache view from its pages.
* ``cached_attention`` — a chunk of query rows attends over that view
  with a per-row validity mask (``j <= position[b] + t``).  The
  formulation mirrors ``flash_attention impl='xla'`` exactly (same
  einsum contractions, same ``-1e30`` mask constant, same
  ``jax.nn.softmax``): masked columns contribute exact zeros whatever
  garbage they hold, and cached decode logits match the uncached full
  forward on the CPU to the accumulation order of one matmul (asserted
  in ``tests/test_generation.py``).

All are inference-only (``grad=None``): the decode path never trains.

``paged_decode_attention`` is the decode step's attention (one
query token per slot, or one block of rows that see their whole block).  Its contract has two halves.  On a TPU backend
it is a Pallas kernel (``ops/pallas/paged_attention.py``) that reads
each slot's **live** pages in place through the block table — no dense
view, no GQA expansion, no contraction over dead columns — and is held
to the plain float32 reference at a stated tolerance (ROADMAP D1), not
to the einsum's bits.  Everywhere else it runs the gather + einsum
formulation above through the very same functions, so on the CPU the
decode step, the prefill chunks and the verify program share one
attention.
Physical page 0 is the reserved **trash page**: rows a write must
discard (idle slots, pad-tail rows of a chunk) are redirected there
instead of branching, so the scatter stays a single fused op.

**Heads narrower than a lane tile** (``head_dim`` 64): the pool is kept
``[P, Hkv / 2, pt, 128]``, KV heads ``2p`` and ``2p + 1`` side by side in
the 128 lanes of one row (``pool_shape``), because a TPU pads a minor
dim of 64 to 128 in HBM: the plain shape would take twice the memory and
twice the bytes of every read.  ``kv_pool_write`` packs by the pool's
own shape, the gathered view unpacks by the query's ``head_dim``, and the
Pallas kernel reads the packed pages as they lie (``paged_attention``).

**Slot state that is not pages** (a gated short convolution's last
``L - 1`` inputs): one ``[slots + 1, L - 1, H]`` variable a layer, row
``slots`` the **trash row** a warm-up prefill writes.  ``short_conv`` is
the causal depthwise convolution over a whole sequence (zero history),
``short_conv_tail`` takes the rows a prompt leaves behind at its TRUE
last positions, ``slot_state_write`` puts them in a slot's row, and
``short_conv_step`` is the decode step: the state's rows and the fresh
one give the output, the state moves on by one, in place, for live rows
only.  All plain ``jax.numpy``: XLA fuses them, there is no kernel.
"""
from __future__ import annotations

from ..monitor import monitor as _monitor
from .registry import in_var, register_op, set_out


LANES = 128

# the form a T > 1 ``kv_pool_write`` lowered to, counted at trace time
# per pool per program build (like ``attention_lowered_*``): whole pages,
# or one [Hkv, D] window a row (the form that re-lays the pool on a TPU)
_WRITE_LOWERED = {
    "pages": _monitor.get("kv_pool_write_pages"),
    "rows": _monitor.get("kv_pool_write_rows"),
}


def pool_shape(num_pages, num_kv_heads, page_tokens, head_dim):
    """The shape of a layer's K (or V) page pool: ``[P, Hkv, pt, D]``, or
    for heads of half a lane tile with an even head count ``[P, Hkv / 2,
    pt, 2 D]``, two KV heads a row (this module's docstring)."""
    if 2 * head_dim == LANES and num_kv_heads % 2 == 0:
        return [num_pages, num_kv_heads // 2, page_tokens, LANES]
    return [num_pages, num_kv_heads, page_tokens, head_dim]


def _kv_pool_write_infer(op, block):
    p = in_var(op, block, "Pool")
    set_out(op, block, "Out", p.shape, p.dtype)


@register_op("kv_pool_write", infer=_kv_pool_write_infer, grad=None,
             stateful_outputs=("Out",))
def _kv_pool_write(ctx, op):
    """Paged cache write: Pool [P, Hkv, pt, D], New [B, Hkv, T, D],
    Positions [B] int (logical base position per row), BlockTable
    [B, NP] int (logical page -> physical page), Lengths [B] int
    (valid rows per batch row).  Row (b, t) of New lands at logical
    position ``positions[b] + t``, i.e. physical page
    ``block_table[b, (positions[b]+t) // pt]`` at in-page offset
    ``(positions[b]+t) % pt``.  Rows with ``t >= lengths[b]`` (idle
    slots, the pad tail of a bucketed prefill chunk) are redirected to
    the reserved trash page 0 — one scatter, no branch on data.  The
    output aliases the pool variable name, so the executor donates the
    buffer (in-place HBM update).

    Attr ``whole_pages`` (the whole-prompt prefill): the caller vouches
    that ``positions[0]`` is a page boundary; ``B == 1`` and ``T % pt ==
    0`` are checked.  New is then ``T / pt`` whole pages and goes in page
    by page; every page but the trash page ends up with the bytes the
    row forms leave there."""
    import jax.numpy as jnp

    pool = ctx.get_input(op, "Pool")
    new = ctx.get_input(op, "New")
    pos = ctx.get_input(op, "Positions").astype(jnp.int32)
    bt = ctx.get_input(op, "BlockTable").astype(jnp.int32)
    length = ctx.get_input(op, "Lengths").astype(jnp.int32)
    P, Hkv, pt, D = pool.shape
    B, _, T, _ = new.shape
    if op.attr("whole_pages", False):
        if B != 1 or T % pt:
            raise ValueError(
                f"kv_pool_write(whole_pages=True) takes one slot's whole "
                f"pages: New {tuple(new.shape)} over pages of {pt} tokens")
        _WRITE_LOWERED["pages"].increase()
        ctx.set_output(op, "Out", _write_whole_pages(
            pool, new, pos[0] // pt, bt[0], length[0]))
        return
    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    logical = pos[:, None] + t                        # [B, T]
    page_idx = jnp.clip(logical // pt, 0, bt.shape[1] - 1)
    phys = jnp.take_along_axis(bt, page_idx, axis=1,  # [B, T]
                               mode="clip")
    off = logical % pt
    valid = t < length[:, None]
    # invalid rows all collapse onto trash slot (0, 0): duplicate
    # scatter indices there are fine — the trash page is never read
    # unmasked
    phys = jnp.where(valid, phys, 0)
    off = jnp.where(valid, off, 0)
    # (the pool's own Hkv and D: a packed pool's row takes two heads)
    rows = jnp.transpose(new, (0, 2, 1, 3)).reshape(B * T, Hkv, D)
    rows = rows.astype(pool.dtype)
    phys, off = phys.reshape(-1), off.reshape(-1)
    if T == 1 or op.attr("per_head", False):
        # the decode step (one row a slot, or with ``per_head`` a short
        # block of rows): every (row, head) is its own index, so the
        # scatter's window is the D lanes alone and the pool keeps its
        # row-major layout.  With the [Hkv, D] window below XLA:TPU lays
        # the pool out {D, Hkv, pt, P} for the scatter and copies every
        # pool in and out of that layout, each layer, each step (32 pool
        # copies, 18 of a 29 ms step at 32 slots x 1408; PERF.md PR 25)
        # — in front of a kernel that reads the pool in place.  Same
        # values either way.
        head = jnp.arange(Hkv, dtype=jnp.int32)[None, :]
        out = pool.at[phys[:, None], head, off[:, None], :].set(rows)
    else:
        # a chunk of rows from a runtime base position (prefill chunks,
        # prefix-reuse tails, verify; no benchmark cell runs them): one
        # [Hkv, D] window a row, and on a TPU the pool's re-layout with
        # it.  B * T * Hkv single-lane-row updates instead were slower
        # at the long rungs on the chip (543 against 523 ms at 3712
        # tokens), faster at the short ones (70 against 85 ms at 1024,
        # PERF.md PR 25)
        _WRITE_LOWERED["rows"].increase()
        out = pool.at[phys, :, off, :].set(rows)
    ctx.set_output(op, "Out", out)


def _write_whole_pages(pool, new, first_page, table, length):
    """New [1, Hkv, T, D] as ``T / pt`` whole pages of Pool [P, Hkv, pt,
    D], from logical page ``first_page`` of the slot's ``table`` [NP];
    ``length`` rows are real.  The scatter's window is a whole page,
    contiguous in the pool's row-major layout, so XLA:TPU updates the
    pool where it lies.  The one page ``length`` cuts keeps the pool's
    rows behind the cut (pages are compared, and shared, by content);
    pages wholly behind it go to the trash page, as a table's zero
    entries already send a window layer's uncovered pages."""
    import jax.numpy as jnp

    _, Hkv, pt, D = pool.shape
    n = new.shape[2] // pt
    # (the pool's own Hkv and D: a packed pool's row takes two heads)
    pages = jnp.transpose(new, (0, 2, 1, 3)).reshape(n, pt, Hkv, D)
    pages = jnp.transpose(pages, (0, 2, 1, 3)).astype(pool.dtype)
    idx = jnp.clip(first_page + jnp.arange(n, dtype=jnp.int32), 0,
                   table.shape[0] - 1)
    phys = jnp.take(table, idx)
    first_row = jnp.arange(n, dtype=jnp.int32) * pt
    row = first_row[:, None] + jnp.arange(pt, dtype=jnp.int32)[None, :]
    cut = jnp.clip(length // pt, 0, n - 1)          # the page length cuts
    held = pool[phys[cut]]                          # [Hkv, pt, D]
    pages = jnp.where((row < length)[:, None, :, None], pages, held[None])
    return pool.at[jnp.where(first_row < length, phys, 0)].set(pages)


def _kv_pool_gather_infer(op, block):
    pool = in_var(op, block, "Pool")
    bt = in_var(op, block, "BlockTable")
    P, hkv, pt, d = pool.shape
    b, np_ = bt.shape
    pack = d // int(op.attr("head_dim", d))
    set_out(op, block, "Out", (b, hkv * pack, np_ * pt, d // pack),
            pool.dtype)


def _gather_pages(pool, bt, head_dim=None):
    """Pool [P, Hkv, pt, D] through BlockTable [B, NP] -> the dense
    logical view [B, Hkv, NP*pt, D].  A packed pool (``pool_shape``)
    comes back unpacked: ``head_dim`` is the heads' own width."""
    import jax.numpy as jnp

    P, Hkv, pt, D = pool.shape
    B, NP = bt.shape
    pages = jnp.take(pool, bt.reshape(-1), axis=0, mode="clip")
    pack = D // (head_dim or D)
    if pack == 1:
        return jnp.transpose(pages.reshape(B, NP, Hkv, pt, D),
                             (0, 2, 1, 3, 4)).reshape(B, Hkv, NP * pt, D)
    pages = pages.reshape(B, NP, Hkv, pt, pack, D // pack)
    return jnp.transpose(pages, (0, 2, 4, 1, 3, 5)).reshape(
        B, Hkv * pack, NP * pt, D // pack)


@register_op("kv_pool_gather", infer=_kv_pool_gather_infer, grad=None)
def _kv_pool_gather(ctx, op):
    """Reassemble a slot's logical cache view from its pages: Pool
    [P, Hkv, pt, D] gathered through BlockTable [B, NP] ->
    [B, Hkv, NP*pt, D].  Column j of the output is logical position j
    of slot b, which is what ``cached_attention`` contracts over.
    Unmapped block-
    table entries read the trash page; those columns sit beyond the
    slot's validity limit and mask to exact zeros."""
    import jax.numpy as jnp

    pool = ctx.get_input(op, "Pool")
    bt = ctx.get_input(op, "BlockTable").astype(jnp.int32)
    ctx.set_output(op, "Out",
                   _gather_pages(pool, bt, op.attr("head_dim", None)))


def _cached_attn_infer(op, block):
    q = in_var(op, block, "Q")
    set_out(op, block, "Out", q.shape, q.dtype)


def _attend_cache(q, k, v, pos, scale=None, window=None, block=False):
    """Q [B, H, T, D] over the logical cache view K/V [B, Hkv, S, D] with the
    validity rule ``j <= pos[b] + t`` (and, under a sliding ``window``,
    ``j > pos[b] + t - window``): the einsum formulation.  With ``block``
    the T rows are one block whose rows all see the whole block: ``j <=
    pos[b] + T - 1`` for every row.  Two-byte q, k, v (a bfloat16 serving
    program's rows and pages): the scores are the first product's float32
    sum, the softmax runs on them in float32, the probabilities are
    rounded where they enter the second product, whose sum is float32
    too; float32 operands lower exactly as they always did."""
    import jax
    import jax.numpy as jnp

    whole = {} if q.dtype == jnp.float32 \
        else {"preferred_element_type": jnp.float32}

    B, H, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        # repeat_interleave [k1,k1,..,k2,k2,..]: query-head group g maps
        # to kv head g//rep (same convention as llama_block's expand_kv)
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if T == 1:
        # a Q=1 scores dot lowers to a GEMV-style rewrite whose
        # accumulation order over D differs from the generic GEMM the
        # uncached forward uses (measured on CPU: ~1e-6 logit drift,
        # breaking the bit-exactness contract).  Duplicating the query
        # row keeps the generic row-consistent GEMM path; the clone's
        # scores are sliced away before the softmax.
        s = jnp.einsum("bhqd,bhkd->bhqk",
                       jnp.concatenate([q, q], axis=2), k, **whole)[:, :, :1]
        s = s * scale
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, **whole) * scale
    # validity mask: same -1e30 constant as flash_attention impl="xla";
    # exp underflows to exact 0 for masked columns, so softmax sums and
    # the PV contraction are bit-identical to the shorter uncached row
    j = jnp.arange(S, dtype=jnp.int32)[None, None, None, :]
    t = jnp.arange(T, dtype=jnp.int32)[None, None, :, None]
    limit = pos[:, None, None, None] + (T - 1 if block else t)
    keep = j <= limit
    if window is not None:
        keep = keep & (j > limit - int(window))
    s = jnp.where(keep, s, jnp.asarray(-1e30, s.dtype))
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, **whole).astype(q.dtype)


@register_op("cached_attention", infer=_cached_attn_infer, grad=None)
def _cached_attention(ctx, op):
    """Q [B, H, T, D] over caches K/V [B, Hkv, S_max, D]; Positions [B]
    is the pre-step sequence length (row b's query t sits at absolute
    position ``positions[b] + t`` and attends columns ``j`` with
    ``j <= positions[b] + t``).  GQA caches (Hkv < H) expand
    repeat-interleave style, matching the uncached block's ``expand_kv``
    values exactly."""
    import jax.numpy as jnp

    q = ctx.get_input(op, "Q")
    k = ctx.get_input(op, "K")
    v = ctx.get_input(op, "V")
    pos = ctx.get_input(op, "Positions").astype(jnp.int32)
    ctx.set_output(op, "Out",
                   _attend_cache(q, k, v, pos, op.attr("scale", None),
                                 op.attr("window", None)))


@register_op("chunk_attention", infer=_cached_attn_infer, grad=None)
def _chunk_attention(ctx, op):
    """A prefill chunk's attention: Q [B, H, C, D], the chunk's rows at
    ``positions[b] + t``, over the gathered logical views K / V [B, Hkv, S,
    D] of the slot's pages, which already hold the chunk's own rows;
    ``cached_attention``'s validity rule and ``window``.

    On a TPU backend, one device, at a shape the kernel takes (one slot,
    ``D`` a multiple of 128, ``C`` of 8, ``S`` of 128) this is the Pallas
    kernel ``chunk_attention`` of ``ops/pallas/flash_attention.py``: online
    softmax over key blocks fetched one by one, so no ``[H, C, S]`` scores
    and no K / V repeated to the query heads ever lie in HBM, blocks right
    of the diagonal or left of the window neither fetched nor multiplied,
    float32 operands whole.  It matches the einsum formulation to float32
    rounding.  Anywhere else it IS ``cached_attention`` (the same
    function), so on the CPU a chunk, the decode step and the verify
    program share one attention.  ``attention_lowered_chunk_pallas`` /
    ``_chunk_reference`` count which, per program build."""
    import jax
    import jax.numpy as jnp

    from .attention_ops import _lowered
    from .pallas.flash_attention import (chunk_attention,
                                         chunk_attention_supported)

    q = ctx.get_input(op, "Q")
    k = ctx.get_input(op, "K")
    v = ctx.get_input(op, "V")
    pos = ctx.get_input(op, "Positions").astype(jnp.int32)
    scale, window = op.attr("scale", None), op.attr("window", None)
    on_tpu = jax.default_backend() == "tpu"
    n_mesh = ctx.mesh.devices.size if ctx.mesh is not None else 1
    if on_tpu and n_mesh == 1 \
            and chunk_attention_supported(q.shape, k.shape,
                                          q.dtype.itemsize):
        out = chunk_attention(q, k, v, pos, window=window, sm_scale=scale)
        _lowered("chunk_pallas")
    else:
        out = _attend_cache(q, k, v, pos, scale, window)
        reason = None
        if on_tpu:
            reason = (f"chunk_attention under a {n_mesh}-device mesh"
                      if n_mesh > 1 else
                      f"chunk_attention with Q {q.shape} over a view "
                      f"{k.shape} of {q.dtype} (kernel needs one slot, "
                      f"head_dim % 128 == 0, whole sublane tiles of rows: "
                      f"8 of float32, 16 of bfloat16, columns % 128 == 0)")
        _lowered("chunk_reference", reason)
    ctx.set_output(op, "Out", out)


@register_op("paged_decode_attention", infer=_cached_attn_infer, grad=None)
def _paged_decode_attention(ctx, op):
    """The paged decode step's attention, one query token per slot: Q
    [B, H, 1, D] over the pools PoolK/PoolV [P, Hkv, pt, D] through
    BlockTable [B, NP]; Positions [B] as in ``cached_attention`` (the
    column ``positions[b]`` this step's ``kv_pool_write`` filled is
    attended: the pool inputs are that op's outputs).  Q [B, H, T, D]
    with T > 1 is a block of T rows a slot at ``positions[b] ..
    positions[b] + T - 1`` (block diffusion): every row attends the
    committed columns and the whole block, ``j <= positions[b] + T -
    1``, so the rows of a slot share their columns and one page walk
    serves them all.

    On a TPU backend, one device, at a shape the kernel takes (``D`` a
    multiple of 128, or 64 over a pool packed two heads a row; ``pt`` a
    multiple of 8) this is the Pallas kernel of
    ``ops/pallas/paged_attention.py``: live pages read in place, no
    dense view, no GQA expansion, online softmax; it matches the einsum
    formulation to float32 rounding, not bit for bit.  Anywhere else it
    is exactly ``kv_pool_gather`` x 2 + ``cached_attention`` — the same
    code — so on the CPU the decode step and a one-row chunk agree
    bit for bit."""
    import jax
    import jax.numpy as jnp

    from .attention_ops import _lowered
    from .pallas import paged_attention

    q = ctx.get_input(op, "Q")
    pool_k = ctx.get_input(op, "PoolK")
    pool_v = ctx.get_input(op, "PoolV")
    bt = ctx.get_input(op, "BlockTable").astype(jnp.int32)
    pos = ctx.get_input(op, "Positions").astype(jnp.int32)
    scale = op.attr("scale", None)
    # a sliding window: columns j > positions[b] - window only; the
    # kernel starts at the window's first page, and block-table entries
    # left of it may point at the trash page (masked in both lowerings)
    window = op.attr("window", None)

    rows = q.shape[2]
    if rows > 1 and window is not None:
        raise NotImplementedError(
            "paged_decode_attention: a block of query rows under a "
            "sliding window (each row would admit its own columns)")
    on_tpu = jax.default_backend() == "tpu"
    n_mesh = ctx.mesh.devices.size if ctx.mesh is not None else 1
    fits = paged_attention.supported(q.shape, pool_k.shape, window,
                                     pool_k.dtype.itemsize)
    D = q.shape[3]
    if on_tpu and n_mesh == 1 and fits:
        kw = {} if window is None else {"window": int(window)}
        # the kernel takes the last column a slot admits
        last = pos if rows == 1 else pos + (rows - 1)
        out = paged_attention.paged_decode_attention(
            q, pool_k, pool_v, bt, last, scale=scale, **kw)
        _lowered("paged_decode", window=window)
    else:
        kw = {} if rows == 1 else {"block": True}
        out = _attend_cache(q, _gather_pages(pool_k, bt, D),
                            _gather_pages(pool_v, bt, D), pos, scale,
                            window, **kw)
        reason = None
        if on_tpu:
            reason = (f"paged_decode_attention under a {n_mesh}-device "
                      f"mesh" if n_mesh > 1 else
                      f"paged_decode_attention with Q {q.shape} over "
                      f"pages {pool_k.shape[1:]} of {pool_k.dtype} (kernel "
                      f"needs head_dim % 128 == 0 or heads of 64 packed "
                      f"two a row, pages of whole sublane tiles: "
                      f"page_tokens % 8 == 0 at float32, % 16 at "
                      f"bfloat16, at most "
                      f"{paged_attention.MAX_GROUP_ROWS} query rows a "
                      f"KV head)")
        _lowered("paged_decode_reference", reason, window=window)
    ctx.set_output(op, "Out", out)


# ---------------------------------------------------------------------------
# block diffusion: a decode step whose unit is a block of positions
# ---------------------------------------------------------------------------

def _block_pair_infer(op, block):
    t = in_var(op, block, "Tokens")
    m = in_var(op, block, "Masked")
    set_out(op, block, "TokensOut", t.shape, t.dtype)
    set_out(op, block, "MaskedOut", m.shape, m.dtype)


@register_op("block_begin", infer=_block_pair_infer, grad=None)
def _block_begin(ctx, op):
    """The block a slot's pass works on: Tokens [S, B] and Masked [S, B]
    (1 = undecided) as the pass before left them on the device, or, for
    a slot with Fresh [S] set, a new block: the ``mask_id`` token at
    every position, all of them undecided.  So the pass after a commit
    starts the next block without the host having read the last."""
    import jax.numpy as jnp

    tokens = ctx.get_input(op, "Tokens")
    masked = ctx.get_input(op, "Masked")
    fresh = ctx.get_input(op, "Fresh").astype(bool)[:, None]
    mask_id = jnp.asarray(int(op.attr("mask_id")), tokens.dtype)
    ctx.set_output(op, "TokensOut", jnp.where(fresh, mask_id, tokens))
    ctx.set_output(op, "MaskedOut",
                   jnp.where(fresh, jnp.ones_like(masked), masked))


@register_op("block_unmask", infer=_block_pair_infer, grad=None)
def _block_unmask(ctx, op):
    """One denoising pass's decision, on the device: Logits [S, B, V] of
    a block's B positions, Tokens [S, B], Masked [S, B] (1 = undecided),
    Quota [S] int.  Every undecided position proposes ``x0 =
    argmax(logits)`` with the confidence ``softmax(logits)[x0]``; the
    ``quota`` undecided positions of highest confidence (ties to the
    lower index) take their ``x0`` and are decided from here on.  Quota
    0 (a commit pass, an idle slot) changes nothing."""
    import jax.numpy as jnp

    logits = ctx.get_input(op, "Logits").astype(jnp.float32)
    tokens = ctx.get_input(op, "Tokens")
    masked_in = ctx.get_input(op, "Masked")
    masked = masked_in.astype(bool)
    quota = ctx.get_input(op, "Quota").astype(jnp.int32)
    B = tokens.shape[1]
    x0 = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
    top = logits.max(axis=-1, keepdims=True)
    # softmax(logits)[argmax] = 1 / sum(exp(logits - max))
    conf = 1.0 / jnp.exp(logits - top).sum(axis=-1)
    conf = jnp.where(masked, conf, -jnp.inf)
    ci, cj = conf[:, :, None], conf[:, None, :]
    idx = jnp.arange(B)
    ahead = (cj > ci) | ((cj == ci) & (idx[None, None, :]
                                       < idx[None, :, None]))
    rank = (ahead & masked[:, None, :]).sum(axis=-1)
    fix = masked & (rank < quota[:, None])
    ctx.set_output(op, "TokensOut", jnp.where(fix, x0, tokens))
    ctx.set_output(op, "MaskedOut",
                   jnp.where(fix, jnp.zeros_like(masked_in), masked_in))


# ---------------------------------------------------------------------------
# slot state that is not pages: the gated short convolution's history
# ---------------------------------------------------------------------------

def _taps(rows, w, bias=None):
    """``sum_j w[:, j] * rows[j]`` in one fixed order (oldest first), so
    the whole-sequence form and the one-row step give the same float for
    the same inputs.  ``rows``: L arrays [..., H], oldest first; ``w``
    [H, L]."""
    acc = rows[0] * w[:, 0]
    for j in range(1, len(rows)):
        acc = acc + rows[j] * w[:, j]
    return acc if bias is None else acc + bias


def _same_as_x(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", x.shape, x.dtype)


@register_op("short_conv", infer=_same_as_x, grad="auto")
def _short_conv(ctx, op):
    """Causal depthwise convolution over a sequence, zero history: X
    [B, S, H], W [H, L], optional Bias [H]; ``Out[:, t] = sum_j W[:, j] *
    X[:, t - (L-1) + j]`` with ``X[:, <0] = 0``: L shifted multiply-adds."""
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    w = ctx.get_input(op, "W").astype(x.dtype)
    bias = ctx.get_input(op, "Bias") if op.single_input("Bias") else None
    L, S = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (L - 1, 0), (0, 0)))
    ctx.set_output(op, "Out", _taps([xp[:, j:j + S] for j in range(L)],
                                    w, bias))


def _short_conv_tail_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", (x.shape[0], int(op.attr("rows")),
                               x.shape[2]), x.dtype)


@register_op("short_conv_tail", infer=_short_conv_tail_infer, grad=None)
def _short_conv_tail(ctx, op):
    """What a sequence leaves behind for the step after it: X [B, S, H]
    (right-padded), Lengths [B] int (real rows) -> the ``rows`` rows
    before position ``lengths[b]``, oldest first, zero where the sequence
    is shorter: [B, rows, H].  Taken at the TRUE last positions, not the
    padded ones."""
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    n = ctx.get_input(op, "Lengths").astype(jnp.int32)
    r = int(op.attr("rows"))
    xp = jnp.pad(x, ((0, 0), (r, 0), (0, 0)))      # row i is position i - r
    take = jax.vmap(lambda seq, at: jax.lax.dynamic_slice_in_dim(
        seq, at, r, axis=0))
    ctx.set_output(op, "Out", take(xp, jnp.clip(n, 0, x.shape[1])))


def _state_infer(op, block):
    s = in_var(op, block, "State")
    set_out(op, block, "StateOut", s.shape, s.dtype)


@register_op("slot_state_write", infer=_state_infer, grad=None,
             stateful_outputs=("StateOut",))
def _slot_state_write(ctx, op):
    """State [slots + 1, R, H] gets Rows [1, R, H] as the whole of row
    ``Slot[0]``; ``slots`` is the trash row (a warm-up's).  The output
    aliases the state variable: donated, updated in place, as the page
    pools are."""
    import jax
    import jax.numpy as jnp

    state = ctx.get_input(op, "State")
    rows = ctx.get_input(op, "Rows").astype(state.dtype)
    slot = ctx.get_input(op, "Slot").astype(jnp.int32)[0]
    ctx.set_output(op, "StateOut", jax.lax.dynamic_update_slice_in_dim(
        state, rows, slot, axis=0))


def _short_conv_step_infer(op, block):
    _same_as_x(op, block)
    _state_infer(op, block)


@register_op("short_conv_step", infer=_short_conv_step_infer, grad=None,
             stateful_outputs=("StateOut",))
def _short_conv_step(ctx, op):
    """The decode step of ``short_conv``: X [slots, 1, H] is each slot's
    fresh row, State [slots + 1, L - 1, H] its last ``L - 1`` (oldest
    first); ``Out`` [slots, 1, H] is the convolution at the fresh row and
    the state moves on by one row where Live [slots] is set; a dead
    row's state stays as it was.  StateOut aliases State."""
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    state = ctx.get_input(op, "State")
    w = ctx.get_input(op, "W").astype(x.dtype)
    bias = ctx.get_input(op, "Bias") if op.single_input("Bias") else None
    live = ctx.get_input(op, "Live").astype(bool)
    n = x.shape[0]
    old = state[:n]                                       # [slots, L-1, H]
    fresh = x[:, 0]
    rows = [old[:, j] for j in range(old.shape[1])] + [fresh]
    ctx.set_output(op, "Out", _taps(rows, w, bias)[:, None])
    moved = jnp.concatenate([old[:, 1:], fresh[:, None].astype(old.dtype)],
                            axis=1)
    new = jnp.where(live[:, None, None], moved, old)
    ctx.set_output(op, "StateOut", state.at[:n].set(new))
