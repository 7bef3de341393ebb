"""The state-space duality layer's recurrence (Mamba-2, SSD): a matrix of
state a head, ``S`` [P, N], that every token decays by one number and
writes one outer product into, and reads along one vector::

    S_t = a_t S_{t-1} + (dt_t x_t) B_t^T,    y_t = S_t C_t + D x_t

with ``x_t`` [P] a head, ``B_t`` and ``C_t`` [N] shared by every head (one
group), ``dt_t`` > 0 and ``a_t = exp(dt_t A)``, ``A`` < 0, one number a
head.  With G groups ``B_t`` and ``C_t`` are [G, N] and the H heads lie in
G runs of H / G consecutive heads, head ``h`` reading group ``h // (H /
G)``'s: G recurrences of H / G heads each, side by side along the state's
lanes (every operand below that is written ``[.., N]`` may be ``[.., G,
N]``).  The transition is diagonal (a scalar times the identity): there is
no correction along a key and so no triangular system, which is what sets
this recurrence beside ``gated_delta_ops``' and not inside it.

**How the state lies.**  ``[N, H P]``: the N state rows on sublanes, all
heads' channels side by side on lanes, ``S[n, h P + p]``.  Then ``x_t``
(all heads) and ``y_t`` are rows as the projections make and take them,
``B_t`` and ``C_t`` are columns shared by every lane, the decay is a row
(``a_t[h]`` repeated over the head's P lanes), the step is ``S <- a * S +
B x`` and ``y = sum_n C[n] S[n, :]``: two broadcasts and a sum over
sublanes, nothing of a head's own and no reduction along lanes.  The
chunked form's two large products (the carried state read, ``C S``, and
written, ``B^T (w x)``) are then one product each over every head's lanes.

Two ops, float32 throughout, every product at "highest":

* ``ssd_chunk``: a whole (right-padded) sequence, the prefill's and the
  uncached forward's.  X [B, T, H, P], Dt [B, T, H], A and D [H], Bm and
  Cm [B, T, N], an optional State0 [B, N, H P] and Valid [B] (real rows)
  -> Out [B, T, H, P] and StateOut [B, N, H P], the state after the last
  REAL token: rows behind ``valid`` take ``dt = 0`` (they neither decay
  nor write) and read as zeros, whatever they hold.  The recurrence is
  rearranged exactly into chunks of ``CHUNK`` tokens: inside a chunk
  ``y_t = sum_{s<=t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s`` (``cum``
  the running sum of ``dt A`` inside the chunk; every exponent is
  non-positive), plus ``exp(cum_t) C_t S`` of the state the chunk began
  from, and the state is carried from chunk to chunk.  On a TPU that is
  ONE Pallas kernel (``pallas/ssd.py`` ``chunk``); this module keeps the
  same mathematics in XLA, ``chunked``, which the kernel is held to and
  which runs off a TPU and under a mesh, and the token-by-token
  ``recurrence`` that both are held to.
* ``ssd_step``: the decode step, one row a slot over State [slots + 1, N,
  H P] (row ``slots`` is the trash row a warm-up's prefill writes): the
  state of the rows ``Live`` marks moves on in place, a dead row's stays
  as it was.  On a TPU the Pallas kernel ``pallas/ssd.py`` ``step``.

``ssd_lowered_pallas`` / ``ssd_lowered_reference`` count, per program
build, which an op lowered to (as ``attention_lowered_*``): on a TPU the
second is a downgrade and is logged once with its reason.
"""
from __future__ import annotations

import logging

from ..monitor import monitor as _monitor
from .registry import in_var, register_op, set_out

logger = logging.getLogger(__name__)

CHUNK = 128       # tokens a chunk of the prefill's scan (PERF.md section 6)

_LOWERED = {"pallas": _monitor.get("ssd_lowered_pallas"),
            "reference": _monitor.get("ssd_lowered_reference")}
_downgrades_logged = set()


def _lowered(path, downgrade_reason=None):
    _LOWERED[path].increase()
    if downgrade_reason and downgrade_reason not in _downgrades_logged:
        _downgrades_logged.add(downgrade_reason)
        logger.warning("the state-space recurrence lowered to its XLA "
                       "formulation on a TPU backend, not the Pallas "
                       "kernel: %s", downgrade_reason)


def _hi():
    import jax

    return jax.lax.Precision.HIGHEST


def masked(x, dt, bm, cm, valid):
    """The rows behind ``valid`` [B] zero in every operand ([B, T, ...]):
    ``dt = 0`` is decay 1 and nothing written, ``C = 0`` and ``x = 0``
    nothing read, whatever the rows hold (a NaN too)."""
    import jax.numpy as jnp

    if valid is None:
        return x, dt, bm, cm
    real = jnp.arange(x.shape[1])[None, :] < valid.astype(jnp.int32)[:, None]

    def keep(t):
        return jnp.where(real.reshape(real.shape + (1,) * (t.ndim - 2)),
                         t, 0.0)

    return keep(x), keep(dt), keep(bm), keep(cm)


def _over_groups(fn, x, dt, a, bm, cm, d, state, **kw):
    """``fn`` (one of the three formulations below, as it stands for one
    group) for ``bm``, ``cm`` of ``[.., G, N]``: the G groups' heads are G
    recurrences that share nothing, so ``fn`` runs over each group's H / G
    heads (``vmap``), a group's lanes of the state beside the next's."""
    import jax

    G = bm.shape[-2]
    (H, P), lead = x.shape[-2:], x.shape[:-2]
    per = H // G
    sg = None if state is None \
        else state.reshape(state.shape[:-1] + (G, per * P))
    out, new = jax.vmap(
        lambda x, dt, a, bm, cm, d, s: fn(x, dt, a, bm, cm, d, s, **kw),
        in_axes=(-3, -2, 0, -2, -2, 0, None if sg is None else -2),
        out_axes=(-3, -2))(
            x.reshape(lead + (G, per, P)), dt.reshape(lead + (G, per)),
            a.reshape(G, per), bm, cm, d.reshape(G, per), sg)
    return out.reshape(x.shape), new.reshape(new.shape[:-2] + (H * P,))


def recurrence(x, dt, a, bm, cm, d, s0=None, valid=None):
    """The definition, token by token under ``lax.scan``: x [B, T, H, P],
    dt [B, T, H], a, d [H], bm, cm [B, T, N], ``s0`` [B, N, H P] -> (out
    [B, T, H, P], the state after the last real token [B, N, H P])."""
    import jax
    import jax.numpy as jnp

    if bm.ndim > dt.ndim:
        return _over_groups(recurrence, x, dt, a, bm, cm, d, s0,
                            valid=valid)
    B, T, H, P = x.shape
    N = bm.shape[-1]
    x, dt, bm, cm = masked(x, dt, bm, cm, valid)
    s = jnp.zeros((B, N, H, P), jnp.float32) if s0 is None \
        else s0.reshape(B, N, H, P)

    def token(s, row):
        xt, dtt, bt, ct = row
        s = jnp.exp(dtt * a)[:, None, :, None] * s \
            + bt[:, :, None, None] * (dtt[..., None] * xt)[:, None]
        y = (ct[:, :, None, None] * s).sum(axis=1) + d[:, None] * xt
        return s, y

    s, y = jax.lax.scan(token, s, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1), s.reshape(B, N, H * P)


def chunked(x, dt, a, bm, cm, d, s0=None, valid=None, chunk=CHUNK):
    """The whole-sequence form in chunks, in XLA (this module's
    docstring): operands as :func:`recurrence` takes them, the same two
    results."""
    import jax
    import jax.numpy as jnp

    if bm.ndim > dt.ndim:
        return _over_groups(chunked, x, dt, a, bm, cm, d, s0, valid=valid,
                            chunk=chunk)
    B, T, H, P = x.shape
    N = bm.shape[-1]
    x, dt, bm, cm = masked(x, dt, bm, cm, valid)
    pad = -T % chunk
    L, n = chunk, (T + pad) // chunk

    def lay(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape((B, n, L) + t.shape[2:])

    xs, dts, bs, cs = lay(x), lay(dt), lay(bm), lay(cm)
    cum = jnp.cumsum(dts * a, axis=2)                      # [B, n, L, H]
    xd = dts[..., None] * xs                               # dt x
    tri = jnp.tril(jnp.ones((L, L), bool))[:, :, None]
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [B, n, t, s, H]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    g = jnp.einsum("bctn,bcsn->bcts", cs, bs, precision=_hi())
    inside = jnp.einsum("bctsh,bcshp->bcthp", g[..., None] * decay, xd,
                        precision=_hi())
    last = cum[:, :, -1:, :]                               # [B, n, 1, H]
    wrote = jnp.einsum("bcsn,bcshp->bcnhp", bs,
                       jnp.exp(last - cum)[..., None] * xd, precision=_hi())
    s = jnp.zeros((B, N, H, P), jnp.float32) if s0 is None \
        else s0.reshape(B, N, H, P)

    def carry(s, row):
        ct, gam, keep, new = row     # [B, L, N], [B, L, H], [B, H], [B,N,H,P]
        read = gam[..., None] * jnp.einsum("btn,bnhp->bthp", ct, s,
                                           precision=_hi())
        return keep[:, None, :, None] * s + new, read

    s, carried = jax.lax.scan(
        carry, s, (jnp.moveaxis(cs, 1, 0), jnp.moveaxis(jnp.exp(cum), 1, 0),
                   jnp.moveaxis(jnp.exp(last[:, :, 0]), 1, 0),
                   jnp.moveaxis(wrote, 1, 0)))
    out = inside + jnp.moveaxis(carried, 0, 1) + d[:, None] * xs
    return out.reshape(B, n * L, H, P)[:, :T], s.reshape(B, N, H * P)


def step(x, dt, a, bm, cm, d, state, live):
    """The one-row step in plain ``jax.numpy``: x [n, H, P], dt [n, H], a,
    d [H], bm, cm [n, N], state [n + 1, N, H P], live [n] bool -> (out
    [n, H, P], the state with live rows moved on)."""
    import jax.numpy as jnp

    if bm.ndim > dt.ndim:
        return _over_groups(step, x, dt, a, bm, cm, d, state, live=live)
    n, H, P = x.shape
    old = state[:n]
    decay = jnp.repeat(jnp.exp(dt * a), P, axis=1)          # [n, H P]
    s = decay[:, None, :] * old \
        + bm[:, :, None] * (dt[..., None] * x).reshape(n, 1, H * P)
    y = (cm[:, :, None] * s).sum(axis=1).reshape(n, H, P) + d[:, None] * x
    new = jnp.where(live[:, None, None], s, old)
    return y, state.at[:n].set(new)


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


def _operands(ctx, op):
    import jax.numpy as jnp

    return tuple(ctx.get_input(op, n).astype(jnp.float32)
                 for n in ("X", "Dt", "A", "Bm", "Cm", "D"))


def _chunk_infer(op, block):
    x, bm = in_var(op, block, "X"), in_var(op, block, "Bm")
    set_out(op, block, "Out", x.shape, x.dtype)
    set_out(op, block, "StateOut",
            (x.shape[0], bm.shape[-1], x.shape[2] * x.shape[3]), x.dtype)


@register_op("ssd_chunk", infer=_chunk_infer, grad=None)
def _ssd_chunk(ctx, op):
    """This module's docstring."""
    from .pallas import ssd

    x, dt, a, bm, cm, d = _operands(ctx, op)
    s0 = ctx.get_input(op, "State0") if op.single_input("State0") else None
    valid = ctx.get_input(op, "Valid") if op.single_input("Valid") else None
    kernel, why = _kernel_route(ctx, "ssd_chunk")
    groups = 1 if bm.ndim == 3 else bm.shape[2]
    if kernel and not ssd.chunk_supported(x.shape, bm.shape[-1], CHUNK,
                                          groups):
        kernel, why = False, (f"ssd_chunk with X {x.shape}, state rows "
                              f"{bm.shape[-1]} in {groups} group(s) (the "
                              f"kernel needs heads that divide a lane "
                              f"tile, whole tiles of both and of a group)")
    out, state = (ssd.chunk if kernel else chunked)(
        x, dt, a, bm, cm, d, s0=s0, valid=valid)
    _lowered("pallas" if kernel else "reference", why)
    ctx.set_output(op, "Out", out.astype(ctx.get_input(op, "X").dtype))
    ctx.set_output(op, "StateOut", state)


def _step_infer(op, block):
    x, s = in_var(op, block, "X"), in_var(op, block, "State")
    set_out(op, block, "Out", x.shape, x.dtype)
    set_out(op, block, "StateOut", s.shape, s.dtype)


@register_op("ssd_step", infer=_step_infer, grad=None,
             stateful_outputs=("StateOut",))
def _ssd_step(ctx, op):
    """X [slots, 1, H, P], Dt [slots, 1, H], A, D [H], Bm, Cm [slots, 1,
    N] (or [slots, 1, G, N]) over State [slots + 1, N, H P]; Live [slots].
    StateOut aliases State."""
    import jax.numpy as jnp

    from .pallas import ssd

    x, dt, a, bm, cm, d = _operands(ctx, op)
    x, dt, bm, cm = x[:, 0], dt[:, 0], bm[:, 0], cm[:, 0]
    state = ctx.get_input(op, "State")
    live = ctx.get_input(op, "Live")
    kernel, why = _kernel_route(ctx, "ssd_step")
    groups = 1 if bm.ndim == 2 else bm.shape[1]
    if kernel and not ssd.step_supported(state.shape, groups):
        kernel, why = False, (f"ssd_step over state {state.shape} in "
                              f"{groups} group(s) (the kernel needs whole "
                              f"tiles of both and of a group)")
    if kernel:
        out, new = ssd.step(x, dt, a, bm, cm, d, state,
                            live.astype(jnp.int32))
    else:
        out, new = step(x, dt, a, bm, cm, d, state, live.astype(bool))
    _lowered("pallas" if kernel else "reference", why)
    ctx.set_output(op, "Out",
                   out[:, None].astype(ctx.get_input(op, "X").dtype))
    ctx.set_output(op, "StateOut", new)
