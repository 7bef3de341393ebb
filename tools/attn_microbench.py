"""Attention fwd+bwd microbenchmark on the real chip.

Times one training-style attention call (value + grads wrt q,k,v) for the
pallas flash kernel vs the unfused einsum formulation, across seq lengths
and block sizes. Used to pick DEFAULT_BLOCK_Q/K and the per-seq default
impl.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def timeit(f, *args, iters=20):
    jax.block_until_ready(f(*args))  # compile + settle
    t0 = time.perf_counter()
    r = None
    for _ in range(iters):
        r = f(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters


def main():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    B = int(os.environ.get("MB_B", "32"))
    H, D = 12, 64
    dt = jnp.bfloat16
    for S in (int(s) for s in os.environ.get("MB_SEQS", "512,1024,2048").split(",")):
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, H, S, D), dt)
        k = jnp.asarray(rng.randn(B, H, S, D), dt)
        v = jnp.asarray(rng.randn(B, H, S, D), dt)

        def unfused_loss(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (1.0 / np.sqrt(D))
            p = jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v).astype(
                jnp.float32).sum()

        g_unf = jax.jit(jax.value_and_grad(unfused_loss, (0, 1, 2)))
        t = timeit(g_unf, q, k, v)
        print(f"S={S} unfused: {t*1e3:.2f} ms")

        for bq in (128, 256, 512):
            for bk in (128, 256, 512):
                if bq > S or bk > S:
                    continue

                def floss(q, k, v, bq=bq, bk=bk):
                    return flash_attention(
                        q, k, v, False, None, bq, bk, False).astype(
                            jnp.float32).sum()

                gf = jax.jit(jax.value_and_grad(floss, (0, 1, 2)))
                try:
                    t = timeit(gf, q, k, v)
                    print(f"S={S} pallas bq={bq} bk={bk}: {t*1e3:.2f} ms")
                except Exception as e:
                    print(f"S={S} pallas bq={bq} bk={bk}: FAIL "
                          f"{type(e).__name__}")


if __name__ == "__main__":
    main()
