"""Attention fwd+bwd microbenchmark on the real chip.

Times one training-style attention call (value + grads wrt q,k,v) for the
pallas flash kernel vs the unfused einsum formulation, across seq lengths
and block sizes. Used to pick DEFAULT_BLOCK_Q/K and the per-seq default
impl.

``python tools/attn_microbench.py chunk`` times the prefill CHUNK shape
instead (PR 51): 128 query over 8 KV heads of 128, ``C`` 1024 rows at
``base`` 0 / 4096 / 11,264 of a 12,800-column view, window none and 4096,
float32 operands at "highest": the Pallas kernel ``chunk_attention``
(blocks 512 x 512 and others) against ``blockwise_attention`` with
``kv_offset`` over the admitted columns (K and V repeated to the query
heads), and against the single-shot prefill kernel's FLOP rate at rung
4096 with the same heads.  Rates count the pairs a causal row admits, 4 x
128 x 128 FLOP each, whatever blocks a kernel runs.
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


def chunk_main():
    from paddle_tpu.ops.pallas.flash_attention import (
        blockwise_attention, chunk_attention, flash_attention)

    H, Hkv, D, S, C = 128, 8, 128, 12800, 1024
    rng = np.random.RandomState(0)
    k = jnp.asarray(rng.randn(1, Hkv, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(1, Hkv, S, D), jnp.float32)
    q = jnp.asarray(rng.randn(1, H, C, D), jnp.float32)
    per_pair = 4.0 * D * H

    def pairs(base, window):
        ends = np.arange(base + 1, base + C + 1, dtype=np.float64)
        return float((ends if window is None
                      else np.minimum(ends, window)).sum())

    # the single-shot prefill kernel at rung 4096, the same heads
    R = 4096
    q1 = jnp.asarray(rng.randn(1, H, R, D), jnp.float32)
    k1 = jnp.repeat(k[:, :, :R], H // Hkv, axis=1)
    v1 = jnp.repeat(v[:, :, :R], H // Hkv, axis=1)
    for window in (None, 4096):
        f = jax.jit(lambda q, k, v, w=window: flash_attention(
            q, k, v, True, None, 512, 512, False, w, None, "highest"))
        t = timeit(f, q1, k1, v1, iters=5)
        rate = per_pair * R * (R + 1) / 2 / t
        print(f"single-shot prefill kernel rung {R} window {window}: "
              f"{t*1e3:.2f} ms, {rate/1e12:.2f} TFLOP/s")
    del q1, k1, v1
    for window in (None, 4096):
        for base in (0, 4096, 11264):
            b = jnp.asarray([base], jnp.int32)
            need = per_pair * pairs(base, window)
            for bq, bk in ((512, 512), (256, 512), (1024, 512),
                           (512, 256), (512, 1024)):
                f = jax.jit(lambda q, k, v, b, w=window, bq=bq, bk=bk:
                            chunk_attention(q, k, v, b, window=w,
                                            block_q=bq, block_k=bk))
                try:
                    t = timeit(f, q, k, v, b, iters=10)
                    print(f"chunk_attention base {base} window {window} "
                          f"bq={bq} bk={bk}: {t*1e3:.2f} ms, "
                          f"{need/t/1e12:.2f} TFLOP/s")
                except Exception as e:
                    print(f"chunk_attention base {base} window {window} "
                          f"bq={bq} bk={bk}: FAIL {type(e).__name__}")
            # blockwise over the columns a row of the chunk can admit
            lo = 0 if window is None else max(0, base - window + 1) \
                // 512 * 512
            hi = base + C

            def blockwise(q, k, v, lo=lo, hi=hi, w=window, base=base):
                kk = jnp.repeat(k[:, :, lo:hi], H // Hkv, axis=1)
                vv = jnp.repeat(v[:, :, lo:hi], H // Hkv, axis=1)
                return blockwise_attention(
                    q, kk, vv, causal=True, block_k=512,
                    kv_offset=lo - base, window=w, precision="highest")[0]

            try:
                t = timeit(jax.jit(blockwise), q, k, v, iters=3)
                print(f"blockwise_attention base {base} window {window} "
                      f"over columns [{lo}, {hi}): {t*1e3:.2f} ms, "
                      f"{need/t/1e12:.2f} TFLOP/s")
            except Exception as e:
                print(f"blockwise_attention base {base} window {window}: "
                      f"FAIL {type(e).__name__}: {str(e)[:200]}")


if __name__ == "__main__":
    chunk_main() if sys.argv[1:2] == ["chunk"] else main()
