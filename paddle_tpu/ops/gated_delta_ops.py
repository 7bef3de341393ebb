"""The gated delta rule (Gated DeltaNet linear attention): a matrix of
state a head, ``S`` [Dk, Dv], that every token decays, corrects along its
key and reads along its query::

    S_t = a_t S_{t-1} + k_t (b_t (v_t - (a_t S_{t-1})^T k_t))^T,  o_t = S_t^T q_t

with ``a_t = exp(g_t)`` in (0, 1] and ``b_t`` in [0, 2].  The log decay
``g_t`` is a scalar a head (G [.., H]: Gated DeltaNet) or a vector a head,
one value a key channel (G [.., H, Dk]: Kimi Delta Attention, ``a_t S`` is
then ``Diag(a_t) S``, a scaling of the state's rows); both ops take either,
by G's rank.  Two ops, float32 throughout, every product at "highest":

* ``gated_delta_chunk``: a whole (right-padded) sequence, the prefill's
  and the uncached forward's.  Q, K [B, T, H, Dk], V [B, T, H, Dv], G and
  Beta [B, T, H], an optional State0 [B, H, Dk, Dv] and Valid [B] (real
  rows) -> Out [B, T, H, Dv] and StateOut [B, H, Dk, Dv], the state after
  the last REAL token: rows behind ``valid`` decay by 1 and correct by 0,
  and whatever they hold (a NaN too) reaches nothing.  The recurrence is
  rearranged exactly into chunks of ``CHUNK`` tokens: inside a chunk a
  unit-triangular system gives every token's correction from the chunk's
  first state, and the state is carried from chunk to chunk.  On a TPU
  all of that is ONE Pallas kernel, ``gated_delta_chunk``
  (``pallas/gated_delta.py``: a chunk's terms are made in VMEM and
  applied there, the state in VMEM across a head's chunks; q, k, v, the
  decay, beta, the outputs and the last state are all that touches HBM,
  after ``lay``'s copies where a head is not whole lane tiles).  This
  module keeps the same mathematics in XLA, ``chunked``: ``chunk_terms``
  (batched matmuls over all chunks at once, the system solved row by
  row) and ``scan_chunks`` under ``lax.scan``: what the kernel is held
  to, and the path off a TPU and under a mesh (``_kernel_route``).
* ``gated_delta_step``: the decode step, one row a slot over State
  [slots + 1, H, Dk, Dv] (row ``slots`` is the trash row a warm-up's
  prefill writes): the state of the rows ``Live`` marks moves on in
  place, a dead row's stays as it was.  Three contractions, or on a TPU
  the Pallas kernel ``gated_delta_step`` over (head group, slot) blocks.

``gated_delta_lowered_pallas`` / ``gated_delta_lowered_reference`` count,
per program build, which an op lowered to (as ``attention_lowered_*``):
on a TPU the second is a downgrade and is logged once with its reason.
``gated_delta_lowered_channel_decay`` counts, beside them, the ops built
with a decay a key channel.

**A chunk with a decay a channel** (``chunk_terms_channel``).  What token
t needs of token i < t is ``sum_d x_t[d] k_i[d] exp(cum_t[d] - cum_i[d])``
(x = k for the corrections, q for the outputs), ``cum`` the running sum of
g inside the chunk.  With a scalar decay the exponential leaves the sum and
[C, C] differences do; a channel each, the array of differences is [C, C,
Dk] a head a chunk, and the factored form ``(x_t exp(cum_t)) . (k_i
exp(-cum_i))`` overflows float32 inside one chunk.  So every exponent is
kept non-positive: the chunk's 64 tokens are taken in blocks of ``BLOCK``
= 16; for t in a later block than i the difference is split at the first
token ``T0`` of t's block, ``exp(cum_t - cum_T0) exp(cum_T0 - cum_i)``,
two safe scalings around one product; within a block the 16 x 16 x Dk
differences are taken outright.  From there on the chunk's terms, the
unit-triangular solve and the carried pass are the scalar form's, the
state scaled by ``exp(cum_last)`` a row where that was one number.  The
kernel takes the same blocks the same way (its ``_decayed_products``).
"""
from __future__ import annotations

import logging

from ..monitor import monitor as _monitor
from .registry import in_var, register_op, set_out

logger = logging.getLogger(__name__)

CHUNK = 64
BLOCK = 16        # a chunk's sub-blocks under a decay a channel

_LOWERED = {
    "pallas": _monitor.get("gated_delta_lowered_pallas"),
    "reference": _monitor.get("gated_delta_lowered_reference"),
    "channel": _monitor.get("gated_delta_lowered_channel_decay"),
}
_downgrades_logged = set()


def _lowered(path, downgrade_reason=None, channel=False):
    _LOWERED[path].increase()
    if channel:
        _LOWERED["channel"].increase()
    if downgrade_reason and downgrade_reason not in _downgrades_logged:
        _downgrades_logged.add(downgrade_reason)
        logger.warning("the gated delta rule lowered to its XLA "
                       "formulation on a TPU backend, not the Pallas "
                       "kernel: %s", downgrade_reason)


def _hi():
    import jax

    return jax.lax.Precision.HIGHEST


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` [..., C, C], by
    forward substitution, a row a turn over every matrix at once: row i
    of the inverse is ``e_i - sum_{j<i} a[i, j] * row j``."""
    import jax
    import jax.numpy as jnp

    C = a.shape[-1]

    def body(i, t):
        row = jax.lax.dynamic_index_in_dim(t, i, axis=-2, keepdims=False)
        # row[j] is zero for j >= i and t[j, k] for k >= j: no mask
        row = row + jnp.einsum("...j,...jk->...k", row, t, precision=_hi())
        return jax.lax.dynamic_update_index_in_dim(t, row, i, axis=-2)

    t = jax.lax.fori_loop(1, C, body, -a)
    return t + jnp.eye(C, dtype=a.dtype)


def chunk_terms(q, k, v, g, beta):
    """What a chunk's tokens need of each other, for every chunk at once:
    q, k [B, H, N, C, Dk], v [B, H, N, C, Dv], g, beta [B, H, N, C] ->
    ``(qg, w, u0, p, kd, gc)`` with which, from the chunk's first state
    ``S``: the corrections ``U = u0 - w S`` [C, Dv], the outputs ``qg S
    + p U`` and the chunk's last state ``gc S + kd^T U``."""
    import jax.numpy as jnp

    C = q.shape[-2]
    cum = jnp.cumsum(g, axis=-1)                          # log decay so far
    diff = cum[..., :, None] - cum[..., None, :]          # [.., t, i]
    tri = jnp.tril(jnp.ones((C, C), bool))
    # decay from token i to token t >= i; differences, so nothing overflows
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    kk = jnp.einsum("...id,...jd->...ij", k, k, precision=_hi())
    a = jnp.where(jnp.tril(tri, -1), beta[..., :, None] * decay * kk, 0.0)
    t = _unit_lower_inverse(a)
    gam = jnp.exp(cum)[..., None]
    bk = beta[..., None] * gam * k
    w = jnp.einsum("...ij,...jd->...id", t, bk, precision=_hi())
    u0 = jnp.einsum("...ij,...jd->...id", t, beta[..., None] * v,
                    precision=_hi())
    p = decay * jnp.einsum("...id,...jd->...ij", q, k, precision=_hi())
    last = cum[..., -1:]
    kd = k * jnp.exp(last - cum)[..., None]
    return q * gam, w, u0, p, kd, jnp.exp(last[..., 0])


def _decayed_products(q, k, cum):
    """``sum_d x_t[d] k_i[d] exp(cum_t[d] - cum_i[d])`` for i <= t inside
    a chunk and 0 above the diagonal, for x = k and for x = q: q, k, cum
    [..., C, Dk] -> two [..., C, C], every exponent non-positive (this
    module's docstring)."""
    import jax.numpy as jnp

    C, Dk = k.shape[-2:]
    nb, lead = C // BLOCK, k.shape[:-2]

    def blocks(x):
        return x.reshape(lead + (nb, BLOCK, Dk))

    cb, kb = blocks(cum), blocks(k)
    x = jnp.stack([kb, blocks(q)], axis=-3)              # [.., nb, 2, t, Dk]
    # within a block the differences outright, one fused pass over
    # [.., nb, 2, t, i, Dk] that is never stored
    tri = jnp.tril(jnp.ones((BLOCK, BLOCK), bool))[:, :, None]
    diff = cb[..., :, None, :] - cb[..., None, :, :]     # [.., nb, t, i, Dk]
    near = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0) \
        * kb[..., None, :, :]
    diag = (x[..., :, None, :] * near[..., None, :, :, :]).sum(-1)
    # across blocks: the rows of blocks 1.. scaled back to their block's
    # first token, the columns before that token scaled on to it
    before = C - BLOCK
    first = cb[..., 1:, :1, :]                           # [.., nb-1, 1, Dk]
    rows = x[..., 1:, :, :, :] * jnp.exp(cb[..., 1:, :, :]
                                         - first)[..., None, :, :]
    old = (jnp.arange(before)[None, :]
           < (jnp.arange(1, nb) * BLOCK)[:, None])[..., None]
    ahead = first - cum[..., None, :before, :]       # [.., nb-1, before, Dk]
    cols = jnp.where(old, jnp.exp(jnp.where(old, ahead, 0.0)), 0.0) \
        * k[..., None, :before, :]
    far = jnp.einsum("...nxtd,...nid->...xnti", rows, cols, precision=_hi())
    far = jnp.pad(far.reshape(lead + (2, before, before)),
                  [(0, 0)] * (len(lead) + 1) + [(BLOCK, 0), (0, BLOCK)])
    # the diagonal blocks into place: [.., 2, nb, t, nb, i]
    diag = jnp.moveaxis(diag, -3, -4)[..., :, :, None, :] \
        * jnp.eye(nb, dtype=k.dtype)[:, None, :, None]
    both = far + diag.reshape(lead + (2, C, C))
    return both[..., 0, :, :], both[..., 1, :, :]


def chunk_terms_channel(q, k, v, g, beta):
    """:func:`chunk_terms` with a log decay a key channel, ``g`` [B, H, N,
    C, Dk]: the same six terms, ``gc`` [B, H, N, Dk] the state's row
    scaling over the chunk."""
    import jax.numpy as jnp

    C = q.shape[-2]
    if C % BLOCK:
        raise ValueError(f"a chunk of {C} tokens is not whole blocks of "
                         f"{BLOCK}")
    cum = jnp.cumsum(g, axis=-2)
    kk, p = _decayed_products(q, k, cum)
    a = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                  beta[..., :, None] * kk, 0.0)
    t = _unit_lower_inverse(a)
    gam = jnp.exp(cum)
    w = jnp.einsum("...ij,...jd->...id", t, beta[..., None] * gam * k,
                   precision=_hi())
    u0 = jnp.einsum("...ij,...jd->...id", t, beta[..., None] * v,
                    precision=_hi())
    last = cum[..., -1:, :]
    return q * gam, w, u0, p, k * jnp.exp(last - cum), \
        jnp.exp(last[..., 0, :])


def scan_chunks(terms, s0):
    """The state from chunk to chunk under ``lax.scan``: ``terms`` of
    :func:`chunk_terms` (or ``chunk_terms_channel``: ``gc`` then scales
    the state's rows), ``s0`` [B, H, Dk, Dv] -> (out [B, H, N, C, Dv],
    the last state)."""
    import jax
    import jax.numpy as jnp

    def step(s, x):
        qg, w, u0, p, kd, gc = x
        u = u0 - jnp.einsum("bhck,bhkv->bhcv", w, s, precision=_hi())
        o = jnp.einsum("bhck,bhkv->bhcv", qg, s, precision=_hi()) \
            + jnp.einsum("bhct,bhtv->bhcv", p, u, precision=_hi())
        s = (gc[..., None, None] if gc.ndim == 2 else gc[..., None]) * s \
            + jnp.einsum("bhck,bhcv->bhkv", kd, u, precision=_hi())
        return s, o

    s, o = jax.lax.scan(step, s0,
                        tuple(jnp.moveaxis(x, 2, 0) for x in terms))
    return jnp.moveaxis(o, 0, 2), s


def lay(x, valid, chunk=CHUNK):
    """[B, T, H, ...] -> [B, H, N, C, ...] in whole chunks, the rows that
    are not real (behind ``valid`` [B], or the padding to a whole chunk)
    zero: decay 1, correction 0, nothing read."""
    import jax.numpy as jnp

    B, T = x.shape[:2]
    if valid is not None:
        real = jnp.arange(T)[None, :] < valid.astype(jnp.int32)[:, None]
        x = jnp.where(real.reshape(real.shape + (1,) * (x.ndim - 2)), x, 0.0)
    pad = -T % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape((B, (T + pad) // chunk, chunk) + x.shape[2:])
    return jnp.moveaxis(x, 3, 1)


def chunked(q, k, v, g, beta, s0=None, valid=None, chunk=CHUNK):
    """The whole-sequence form on [B, T, H, ...] operands, in XLA: ``lay``,
    ``chunk_terms`` (``chunk_terms_channel`` under a decay a channel), then
    ``scan_chunks``.  Returns (out [B, T, H, Dv], state [B, H, Dk, Dv])."""
    import jax.numpy as jnp

    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    if s0 is None:
        s0 = jnp.zeros((B, H, Dk, Dv), q.dtype)
    terms = chunk_terms if g.ndim == 3 else chunk_terms_channel
    o, s = scan_chunks(terms(*(lay(x, valid, chunk)
                               for x in (q, k, v, g, beta))), s0)
    o = jnp.moveaxis(o, 1, 3).reshape(B, -1, H, Dv)
    return o[:, :T], s


def step(q, k, v, g, beta, state, live):
    """The one-row step in plain ``jax.numpy``: q, k [n, H, Dk], v
    [n, H, Dv], g [n, H] or [n, H, Dk], beta [n, H], state [n + 1, H, Dk,
    Dv], live [n] bool -> (out [n, H, Dv], the state with live rows moved
    on)."""
    import jax.numpy as jnp

    n = q.shape[0]
    old = state[:n]
    s = (jnp.exp(g)[..., None, None] if g.ndim == 2
         else jnp.exp(g)[..., None]) * old
    r = v - jnp.einsum("nhkv,nhk->nhv", s, k, precision=_hi())
    s = s + k[..., :, None] * (beta[..., None] * r)[..., None, :]
    o = jnp.einsum("nhkv,nhk->nhv", s, q, precision=_hi())
    new = jnp.where(live[:, None, None, None], s, old)
    return o, state.at[:n].set(new)


def _kernel_route(ctx, what):
    """``(use the Pallas kernel, why not)``: a TPU backend and one
    device."""
    import jax

    if jax.default_backend() != "tpu":
        return False, None
    n_mesh = ctx.mesh.devices.size if ctx.mesh is not None else 1
    if n_mesh > 1:
        return False, f"{what} under a {n_mesh}-device mesh"
    return True, None


def _chunk_infer(op, block):
    q, v = in_var(op, block, "Q"), in_var(op, block, "V")
    set_out(op, block, "Out", v.shape, v.dtype)
    set_out(op, block, "StateOut",
            (q.shape[0], q.shape[2], q.shape[3], v.shape[3]), v.dtype)


@register_op("gated_delta_chunk", infer=_chunk_infer, grad=None)
def _gated_delta_chunk(ctx, op):
    """This module's docstring."""
    import jax.numpy as jnp

    from .pallas import gated_delta

    q, k, v = (ctx.get_input(op, n) for n in ("Q", "K", "V"))
    g, beta = ctx.get_input(op, "G"), ctx.get_input(op, "Beta")
    s0 = ctx.get_input(op, "State0") if op.single_input("State0") else None
    valid = ctx.get_input(op, "Valid") if op.single_input("Valid") else None
    kernel, why = _kernel_route(ctx, "gated_delta_chunk")
    if kernel and not gated_delta.chunk_supported(q.shape, CHUNK):
        kernel, why = False, (f"gated_delta_chunk with Q {q.shape}, V "
                              f"{v.shape} (kernel needs whole sublane "
                              f"tiles)")
    out, state = (gated_delta.chunk if kernel else chunked)(
        *(x.astype(jnp.float32) for x in (q, k, v, g, beta)),
        s0=s0, valid=valid)
    _lowered("pallas" if kernel else "reference", why, g.ndim == 4)
    ctx.set_output(op, "Out", out.astype(v.dtype))
    ctx.set_output(op, "StateOut", state)


def _step_infer(op, block):
    v, s = in_var(op, block, "V"), in_var(op, block, "State")
    set_out(op, block, "Out", v.shape, v.dtype)
    set_out(op, block, "StateOut", s.shape, s.dtype)


@register_op("gated_delta_step", infer=_step_infer, grad=None,
             stateful_outputs=("StateOut",))
def _gated_delta_step(ctx, op):
    """Q, K [slots, 1, H, Dk], V [slots, 1, H, Dv], G [slots, 1, H] or
    [slots, 1, H, Dk], Beta [slots, 1, H] over State [slots + 1, H, Dk,
    Dv]; Live [slots].  StateOut aliases State."""
    import jax.numpy as jnp

    from .pallas import gated_delta

    q, k, v, g, beta = (ctx.get_input(op, n)[:, 0].astype(jnp.float32)
                        for n in ("Q", "K", "V", "G", "Beta"))
    state = ctx.get_input(op, "State")
    live = ctx.get_input(op, "Live")
    kernel, why = _kernel_route(ctx, "gated_delta_step")
    if kernel and not gated_delta.step_supported(state.shape):
        kernel, why = False, (f"gated_delta_step over state {state.shape} "
                              f"(kernel needs whole sublane tiles)")
    if kernel:
        out, new = gated_delta.step(q, k, v, g, beta, state,
                                    live.astype(jnp.int32))
    else:
        out, new = step(q, k, v, g, beta, state, live.astype(bool))
    _lowered("pallas" if kernel else "reference", why, g.ndim == 3)
    ctx.set_output(op, "Out", out[:, None].astype(v.dtype))
    ctx.set_output(op, "StateOut", new)
