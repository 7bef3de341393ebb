#!/usr/bin/env python3
"""Times one dense product of a prefill rung, ``[rung, K] x [K, N]`` in
float32 at "highest", whole (op ``mul``'s ``dot_general`` over every row)
against segmented (op ``mul_valid_rows``: ``ops/math_ops.py``
``valid_rows_product``, only the segments that hold one of the first
``valid`` rows), and then the whole SwiGLU (gate | up, ``silu(gate) * up``,
down: three XLA operations over every row) against the segmented one (op
``swiglu_valid_rows``: ``valid_rows_swiglu``, all three a segment), at the
two cells' FFN shapes whose prefills the products set the pace of:

* mistral-7b-v0.1 (``mistral7b-longprompt``): gate | up ``4096 x 28672``
  and down ``14336 x 4096`` at rungs 2048 and 3712 (no multiple of the
  segment: the last one starts early);
* olmo-hybrid-7b (``olmo-hybrid7b-longdoc``): gate | up ``3840 x 22016``
  and down ``11008 x 3840`` at rungs 2048 and 6144;

``valid`` at a quarter, a half, three quarters and the whole of the rung,
segments of 256 and 512 rows: milliseconds, milliseconds a real row beside
the whole product's, and the seconds the first call took (trace, compile
and one run).  The table fixes ``VALID_ROW_SEGMENT`` (PERF.md §6, PR 64).

``python tools/dense_rows_microbench.py`` (chip only, about six
minutes): each the median of ``--reps`` runs after a warm-up.  Writes
``chiprun_out/dense_rows_microbench.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = [("mistral gate|up", 4096, 28672, (2048, 3712)),
          ("mistral down", 14336, 4096, (2048, 3712)),
          ("olmo gate|up", 3840, 22016, (2048, 6144)),
          ("olmo down", 11008, 3840, (2048, 6144))]
FFNS = [("mistral swiglu", 4096, 14336, (2048, 3712)),
        ("olmo swiglu", 3840, 11008, (2048, 6144))]
SEGMENTS = (256, 512)
INNER = 8       # calls dispatched back to back before the host waits


def timed(fn, reps):
    """``(ms a call, seconds of the first call)``: the median over ``reps``
    of ``INNER`` calls dispatched back to back and waited for once, so the
    host's dispatch and its wait are not in the number."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(INNER - 1):
            fn()
        jax.block_until_ready(fn())
        took.append((time.perf_counter() - t0) / INNER)
    return 1e3 * statistics.median(took), first


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--ffn-only", action="store_true",
                    help="the SwiGLUs alone, not the single products")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("dense_rows_microbench: no TPU backend, nothing is timed")
        return 2
    from paddle_tpu.ops.math_ops import valid_rows_product, valid_rows_swiglu

    rng = np.random.default_rng(64)
    out = {"device": jax.devices()[0].device_kind, "rows": []}

    def dot(x, w):
        return jax.lax.dot_general(x, w, (((2,), (0,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST)

    def swiglu(x, w):
        gu = dot(x, w[0])
        width = w[1].shape[0]
        return dot(jax.nn.silu(gu[..., :width]) * gu[..., width:], w[1])

    def draw(k, n):
        return jnp.asarray(rng.normal(size=(k, n)) * k ** -0.5, jnp.float32)

    # (a case draws its matrices when its turn comes: 0.5-0.9 GB each)
    cases = [(what, k, rungs, lambda k=k, n=n: draw(k, n), dot,
              valid_rows_product)
             for what, k, n, rungs in ([] if args.ffn_only else SHAPES)]
    cases += [(what, k, rungs, lambda k=k, i=i: (draw(k, 2 * i), draw(i, k)),
               swiglu, lambda x, w, v, segment: valid_rows_swiglu(
                   x, w[0], w[1], v, segment))
              for what, k, i, rungs in FFNS]
    for what, k, rungs, matrices, whole_fn, seg_fn in cases:
        w = matrices()
        whole = jax.jit(whole_fn)
        for rung in rungs:
            x = jnp.asarray(rng.normal(size=(1, rung, k)), jnp.float32)
            whole_ms, whole_first = timed(lambda: whole(x, w), args.reps)
            want = whole(x, w)
            row = {"product": what, "k": k, "rung": rung,
                   "whole_ms": whole_ms, "whole_first_s": whole_first}
            print(f"{what} rung {rung}: whole {whole_ms:.3f} ms "
                  f"({1e3 * whole_ms / rung:.3f} us a row), first call "
                  f"{whole_first:.2f} s", flush=True)
            for segment in SEGMENTS:
                seg = jax.jit(lambda x, w, v, segment=segment:
                              seg_fn(x, w, v, segment))
                for quarter in (1, 2, 3, 4):
                    valid = rung * quarter // 4
                    v = jnp.asarray(valid, jnp.int32)
                    ms, first = timed(lambda: seg(x, w, v), args.reps)
                    got = seg(x, w, v)
                    off = float(jnp.abs(got[:, :valid]
                                        - want[:, :valid]).max())
                    run = min(rung, -(-valid // segment) * segment)
                    behind = float(jnp.abs(got[:, run:]).max()) \
                        if run < rung else 0.0
                    row[f"seg{segment}_valid{valid}"] = {
                        "ms": ms, "first_s": first, "off": off,
                        "behind": behind}
                    print(f"  segment {segment} valid {valid:5d}: {ms:.3f} "
                          f"ms, {1e3 * ms / valid:.3f} us a real row (whole "
                          f"{1e3 * whole_ms / rung:.3f}), first call "
                          f"{first:.2f} s, off {off:.1e}, behind "
                          f"{behind:.1e}", flush=True)
            out["rows"].append(row)
            del x, want
        del w
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "dense_rows_microbench.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
