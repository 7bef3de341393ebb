#!/usr/bin/env python3
"""Times the two attention kernels of a dense decoder's serving programs at
float32 and at bfloat16 operands, at Mistral-7B's heads (32 query over 8 KV
heads of 128), for the constants that were measured at float32 (PR 68):

* the paged decode kernel (``ops/pallas/paged_attention.py``) over pages of
  16 at granules of 128 / 256 / 512 positions: 32 slots of short chat
  contexts (100-1,400 positions) and 8 slots of long ones (1,000-3,700);
* the prefill kernel (``ops/pallas/flash_attention.py``
  ``flash_attention``, causal) at rungs 1024, 2048 and 3712 (padded to
  3840: the kernel's blocks are whole lane tiles) at blocks of 256 / 512 /
  1024 query rows by 256 / 512 / 1024 keys.

``python tools/attn_dtype_microbench.py`` (chip only, about three
minutes): milliseconds a call, the median of ``--reps`` runs of 8 calls
dispatched back to back.  Writes ``chiprun_out/attn_dtype_microbench.json``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

INNER = 8


def timed(fn, reps):
    import jax

    jax.block_until_ready(fn())
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(INNER - 1):
            fn()
        jax.block_until_ready(fn())
        took.append((time.perf_counter() - t0) / INNER)
    return 1e3 * statistics.median(took)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--only", choices=("paged", "prefill"), default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("attn_dtype_microbench: no TPU backend, nothing is timed")
        return 2
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    H, HKV, D, PT = 32, 8, 128, 16
    rng = np.random.default_rng(68)
    out = {"device": jax.devices()[0].device_kind, "paged": [],
           "prefill": []}
    for what, slots, lo, hi, seq in (() if args.only == "prefill" else (
            ("chat", 32, 100, 1400, 1408), ("long", 8, 1000, 3700, 3712))):
        np_slot = seq // PT
        pages = slots * np_slot + 1
        pos = jnp.asarray(rng.integers(lo, hi, slots), jnp.int32)
        table = jnp.asarray(1 + np.arange(slots * np_slot).reshape(
            slots, np_slot), jnp.int32)
        for dtype in (jnp.float32, jnp.bfloat16):
            q = jnp.asarray(rng.normal(size=(slots, H, 1, D)), dtype)
            pool_k, pool_v = (jnp.asarray(
                rng.normal(size=(pages, HKV, PT, D)), dtype)
                for _ in range(2))
            for granule in (128, 256, 512):
                ms = timed(lambda: paged_decode_attention(
                    q, pool_k, pool_v, table, pos, granule=granule),
                    args.reps)
                out["paged"].append({"contexts": what, "dtype": str(
                    jnp.dtype(dtype)), "granule": granule, "ms": ms})
                print(f"paged {what} {jnp.dtype(dtype)} granule {granule}: "
                      f"{1e3 * ms:.1f} us a layer", flush=True)
            del q, pool_k, pool_v
    for rung in () if args.only == "paged" else (1024, 2048, 3840):
        for dtype in (jnp.float32, jnp.bfloat16):
            q = jnp.asarray(rng.normal(size=(1, H, rung, D)), dtype)
            # (K and V expanded to the query heads, as ``llama_block``
            # hands them to the op)
            k, v = (jnp.asarray(rng.normal(size=(1, H, rung, D)), dtype)
                    for _ in range(2))
            for bq in (256, 512, 1024):
                for bk in (256, 512, 1024):
                    # (jitted: called bare, the kernel is traced anew at
                    # every call and the host's 0.2 s of tracing is what is
                    # timed; PR 68's first table)
                    fn = jax.jit(functools.partial(
                        flash_attention, causal=True, block_q=bq,
                        block_k=bk))
                    try:
                        ms = timed(lambda: fn(q, k, v), args.reps)
                    except Exception as e:      # a block VMEM cannot hold
                        print(f"prefill {rung} {jnp.dtype(dtype)} {bq}x{bk}: "
                              f"{type(e).__name__}", flush=True)
                        continue
                    out["prefill"].append({
                        "rung": rung, "dtype": str(jnp.dtype(dtype)),
                        "block_q": bq, "block_k": bk, "ms": ms})
                    print(f"prefill {rung} {jnp.dtype(dtype)} {bq}x{bk}: "
                          f"{ms:.3f} ms a layer", flush=True)
            del q, k, v
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_dtype_microbench.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
