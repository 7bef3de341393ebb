#!/usr/bin/env python
"""Which formulation of the grouped expert matmul, on the chip.

    chiprun -- python tools/moe_microbench.py

Times, at a sparse-expert decoder's published shape (hidden 2560, 64
experts of width 768, top 6, float32), the two grouped matmuls of an
expert FFN (gate + up, then down) over rows sorted by expert, at the
decode step's 32 x 6 rows and a prefill's 8192 x 6:

* ``ragged_dot`` -- ``jax.lax.ragged_dot`` at "highest" (what
  ``parallel/moe.py`` runs) and at default precision;
* ``megablox`` -- the Pallas grouped matmul that ships with JAX
  (``jax.experimental.pallas.ops.tpu.megablox.gmm``), tiles (128, 128,
  128) and (512, 512, 512); it feeds the MXU at default precision only;
* ``dense`` -- every expert over every row, masked (decode shape only).

Writes ``chiprun_out/moe_formulation_sweep.json`` and prints one line per
formulation: milliseconds for the pair of matmuls, the share of 819 GB/s
the touched experts' bytes make of it, and the error against a float64
loop.  Refuses to run without a TPU backend.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

H, E, I, K = 2560, 64, 768, 6


def timed(fn, *args, reps=20):
    out = fn(*args)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e3, out


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("moe_microbench: no TPU backend, nothing measured",
              file=sys.stderr)
        return 2
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    key = jax.random.key(0)
    gu = jax.random.normal(jax.random.fold_in(key, 1), (E, H, 2 * I)) * .02
    dn = jax.random.normal(jax.random.fold_in(key, 2), (E, I, H)) * .02
    results = []
    for n_tokens in (32, 8192):
        m = n_tokens * K
        rows = jax.random.normal(jax.random.fold_in(key, m), (m, H))
        experts = jax.random.randint(jax.random.fold_in(key, m + 1), (m,),
                                     0, E)
        sizes = jnp.bincount(jnp.sort(experts), length=E).astype(jnp.int32)
        touched = int((sizes > 0).sum())

        def ffn(mm):
            def f(rows, gu, dn, sizes):
                h = mm(rows, gu, sizes)
                return mm(jnp.maximum(h[:, :I], 0) * h[:, I:], dn, sizes)
            return jax.jit(f)

        def ragged(prec):
            return lambda a, b, s: jax.lax.ragged_dot(
                a, b, s, precision=prec, preferred_element_type=a.dtype)

        def mega(tile):
            return lambda a, b, s: gmm(a, b, s, a.dtype, tile)

        def dense(a, b, s):
            ends = jnp.cumsum(s)
            row = jnp.arange(a.shape[0])[None, :]
            mask = (row < ends[:, None]) & (row >= (ends - s)[:, None])
            y = jnp.einsum("mk,gkn->gmn", a, b,
                           precision=jax.lax.Precision.HIGHEST)
            return jnp.einsum("gm,gmn->mn", mask.astype(a.dtype), y)

        cases = [("ragged_dot highest", ragged(jax.lax.Precision.HIGHEST)),
                 ("ragged_dot default", ragged(None)),
                 ("megablox 128", mega((128, 128, 128))),
                 ("megablox 512", mega((512, 512, 512)))]
        if n_tokens == 32:
            cases.append(("dense masked highest", dense))
        # float64 loop on the host, over the first 64 rows
        r64 = np.asarray(rows[:64], np.float64)
        ends = np.cumsum(np.asarray(sizes))
        which = np.searchsorted(ends, np.arange(64), side="right")
        want = np.stack([
            (lambda h: (np.maximum(h[:I], 0) * h[I:])
             @ np.asarray(dn[g], np.float64))(r @ np.asarray(gu[g],
                                                             np.float64))
            for r, g in zip(r64, which)])
        weight_bytes = touched * 3 * H * I * 4
        for name, mm in cases:
            try:
                ms, out = timed(ffn(mm), rows, gu, dn, sizes)
            except Exception as e:  # noqa: BLE001 — a formulation the
                # compiler refuses is a finding, not a crash
                print(f"{n_tokens:5d} tokens  {name:22s} refused: "
                      f"{str(e)[:200]}", flush=True)
                results.append({"tokens": n_tokens, "formulation": name,
                                "refused": str(e)[:400]})
                continue
            err = float(np.abs(np.asarray(out[:64]) - want).max()
                        / np.abs(want).max())
            share = 100.0 * weight_bytes / 819e9 / (ms / 1e3)
            flops = 2.0 * m * 3 * H * I
            print(f"{n_tokens:5d} tokens  {name:22s} {ms:9.3f} ms  "
                  f"{touched} experts touched, their bytes {share:5.1f}% "
                  f"of 819 GB/s, {flops / ms / 1e9:7.1f} TFLOP/s, off the "
                  f"float64 loop by {err:.3g}", flush=True)
            results.append({"tokens": n_tokens, "formulation": name,
                            "ms": ms, "experts_touched": touched,
                            "hbm_share_pct": share,
                            "tflops": flops / ms / 1e9, "rel_err": err})
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_formulation_sweep.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "results": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
