"""Rotary position embedding op (new capability for the LLM configs;
no reference analog — the reference vintage predates RoPE adoption)."""
from __future__ import annotations

import numpy as np

from .registry import in_var, register_op, set_out


def yarn_inv_freq(inv_freq, base, dim, factor, original_max, beta_fast,
                  beta_slow):
    """YaRN's frequency table (Peng et al. 2023, as the DeepSeek family's
    modelling code computes it): frequency ``i`` of ``inv_freq``
    [dim / 2] becomes ``(1 - m_i) f_i / factor + m_i f_i``, ``m_i`` 1 for
    the dimensions that turn more than ``beta_fast`` times over
    ``original_max`` positions (kept), 0 for those that turn fewer than
    ``beta_slow`` times (interpolated), a linear ramp between the two
    correction dimensions.  float64 on the host."""
    def correction_dim(rotations):
        return dim * np.log(original_max / (rotations * 2 * np.pi)) \
            / (2 * np.log(base))

    low = max(np.floor(correction_dim(beta_fast)), 0)
    high = min(np.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inv_freq * (ramp / factor + (1.0 - ramp))


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 where
    nothing is stretched).  The DeepSeek family multiplies cos and sin by
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``
    and the softmax scale by ``yarn_mscale(factor, mscale_all_dim) ** 2``."""
    if factor <= 1:
        return 1.0
    return 0.1 * float(mscale) * float(np.log(factor)) + 1.0


def _rope_infer(op, block):
    x = in_var(op, block, "X")
    set_out(op, block, "Out", x.shape, x.dtype)


@register_op("rope", infer=_rope_infer, grad="auto")
def _rope(ctx, op):
    """X: [B, H, S, D] (D even). Rotates pairs (x[..., :D/2], x[..., D/2:])
    by position-dependent angles — the 'rotate_half' convention.

    Optional input ``Offset`` [B] (int): per-row dynamic position
    offset for cached decode — row b's positions are
    ``offset[b] .. offset[b]+S-1``.  The angle math is identical to the
    static path (``pos * inv_freq``), so a token rotated at decode step
    p is bit-equal to the same token rotated at position p of a full
    forward.

    Attr ``interleave``: the pairs are ``(x[..., 2i], x[..., 2i + 1])``
    and stay where they lie.  Attr ``yarn`` ``(factor, original_max,
    beta_fast, beta_slow)``: :func:`yarn_inv_freq`'s table in place of
    the one base's."""
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    base = op.attr("base", 10000.0)
    pos_offset = op.attr("position_offset", 0)
    B, H, S, D = x.shape
    half = D // 2

    inv_freq = 1.0 / (base ** (np.arange(0, half) / half))
    yarn = op.attr("yarn", None)
    if yarn:
        inv_freq = yarn_inv_freq(inv_freq, base, D, *yarn)
    offset = ctx.get_input(op, "Offset") if op.single_input("Offset") \
        else None
    if offset is None:
        pos = jnp.arange(pos_offset, pos_offset + S, dtype=jnp.float32)
        freqs = jnp.outer(pos, inv_freq)          # [S, half]
        cos = jnp.cos(freqs)[None, None]          # [1,1,S,half]
        sin = jnp.sin(freqs)[None, None]
    else:
        pos = offset.astype(jnp.float32)[:, None] \
            + jnp.arange(S, dtype=jnp.float32)[None, :]      # [B, S]
        freqs = pos[..., None] * jnp.asarray(inv_freq,
                                             jnp.float32)    # [B,S,half]
        cos = jnp.cos(freqs)[:, None]             # [B,1,S,half]
        sin = jnp.sin(freqs)[:, None]

    xf = x.astype(jnp.float32)
    if op.attr("interleave", False):
        # pairs (2i, 2i + 1), each rotated where it lies
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(xf.shape)
    else:
        x1, x2 = xf[..., :half], xf[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin,
                               x2 * cos + x1 * sin], axis=-1)
    ctx.set_output(op, "Out", out.astype(x.dtype))
