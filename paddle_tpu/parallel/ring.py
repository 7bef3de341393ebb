"""Sequence/context parallelism: ring attention + Ulysses head-scatter.

NEW CAPABILITY — absent in the reference vintage (SURVEY.md §2.6 last
row: no sequence/context parallelism of any kind; longest-sequence support
was LoD ragged tensors). Required for the long-context LLM configs.

Ring attention (Liu et al.): shard the sequence over the `sp` mesh axis;
each device holds q/k/v chunks. K/V rotate around the ring via
lax.ppermute (compiles to ICI collective-permute) while each device
accumulates online-softmax partials of its local queries against every
chunk — full attention without ever materializing the full sequence on
one chip, and with communication overlapped against the chunk matmuls by
XLA's latency-hiding scheduler.

Ulysses (head-scatter): all_to_all converts the seq shard into a head
shard, runs dense local attention on full sequences for H/n heads, and
converts back. Cheaper comm for moderate S; requires H % n == 0.

Monitor stats: ``collective_ppermute_calls`` /
``collective_all_to_all_calls`` count the collective ops *emitted at
trace time* (once per program build, not per device step) — a cheap
audit of how much ICI traffic each compiled program carries.
"""
from __future__ import annotations

import numpy as np

from ..monitor import stat_add, stat_add_per_device
from ..ops.pallas.flash_attention import (NEG_INF, blockwise_attention)



def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale=None):
    """Attention over a sequence sharded on `axis_name` (inside
    shard_map). q/k/v: local chunks [B, H, S_local, D], sequence order =
    mesh order along the axis. Returns the local output chunk."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, H, Sl, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    qf = q.astype(jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]
    stat_add("collective_ppermute_calls", 2)  # k + v rotation per build
    # every device on the axis executes the emitted collective: the
    # per-shard series attributes it chip-by-chip (n is concrete at
    # trace time — it sizes the ring permutation)
    stat_add_per_device("collective_ppermute_calls", n, 2)

    def step(carry, t):
        m, l, acc, kc, vc = carry
        src = (idx - t) % n  # whose chunk we currently hold
        s = jnp.einsum("bhqd,bhkd->bhqk", qf * scale,
                       kc.astype(jnp.float32))
        if causal:
            q_pos = idx * Sl + jnp.arange(Sl)[:, None]
            k_pos = src * Sl + jnp.arange(Sl)[None, :]
            s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.where(s <= NEG_INF / 2, 0.0,
                      jnp.exp(s - m_new[..., None]))
        corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (m_new, l_new, acc_new, kc, vc), None

    m0 = jnp.full((B, H, Sl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sl), jnp.float32)
    acc0 = jnp.zeros((B, H, Sl, D), jnp.float32)
    # mark the device-constant initializers as varying over the ring axis
    # so the scan carry type matches the per-device accumulation
    m0, l0, acc0 = (lax.pcast(x, (axis_name,), to="varying")
                    for x in (m0, l0, acc0))
    (m, l, acc, _, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(n))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      sm_scale=None):
    """Head-scatter sequence parallelism: seq-shard -> head-shard via
    all_to_all, dense attention on the full sequence per head group,
    scatter back."""
    import jax.lax as lax

    n = lax.axis_size(axis_name)
    B, H, Sl, D = q.shape
    if H % n:
        raise ValueError(f"ulysses: heads {H} not divisible by group {n}")

    def scatter(x):  # [B,H,Sl,D] -> [B,H/n,S,D]
        stat_add("collective_all_to_all_calls")
        stat_add_per_device("collective_all_to_all_calls", n)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def gather(x):   # [B,H/n,S,D] -> [B,H,Sl,D]
        stat_add("collective_all_to_all_calls")
        stat_add_per_device("collective_all_to_all_calls", n)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = scatter(q), scatter(k), scatter(v)
    out, _ = blockwise_attention(qh, kh, vh, causal=causal,
                                 sm_scale=sm_scale)
    return gather(out)
