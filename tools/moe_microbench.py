#!/usr/bin/env python
"""Which formulation of the grouped expert matmul, on the chip.

    chiprun -- python tools/moe_microbench.py [--only NAME] [--tiles TM,TN;...]

Times the two grouped matmuls of one expert FFN (gate + up, then down;
float32 "highest") over rows sorted by expert, at the shapes the
benchmark's three routed configurations run (``SHAPES``: rows a group at
each prefill rung, and the decode step's or block pass's rows), with the
groups even and with the routing's skew (the largest group 2.5 times the
mean):

* ``parent`` -- what ``grouped_matmul`` ran before the Pallas kernel: one
  ``jax.lax.ragged_dot`` call, or runs of 192 sorted rows where
  ``192 < M < 512 x groups`` (PR 32);
* ``ragged_dot`` -- the one call, at "highest" and (at the shapes PR 28
  measured) at default precision;
* ``megablox`` -- the Pallas grouped matmul that ships with JAX
  (``jax.experimental.pallas.ops.tpu.megablox.gmm``), tiles (128, 128,
  128) and (512, 512, 512), at those shapes too; it feeds the MXU at
  default precision only;
* ``route`` -- ``grouped_matmul`` itself, whatever it chooses: the Pallas
  kernel ``ops/pallas/grouped_matmul.py`` at the blocks its ``tiles`` gives
  the shape, or the one ``ragged_dot`` call;
* ``kernel`` -- the kernel at every TM,TN pair of ``--tiles`` (the sweep
  those blocks were chosen from).

Writes ``chiprun_out/moe_formulation_sweep.json`` and prints one line per
formulation: milliseconds for the pair of matmuls, against the six-pass
MXU floor and the touched experts' bytes over 819 GB/s, and the error
against a float64 loop over 64 rows spread over the call.  Refuses to run
without a TPU backend.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# name: (groups, K, width I, rows a group at the prefill rungs, the rows
# of the decode step / block pass: the cell's, and one row block's where
# the cell's is not the fewest the kernel takes)
SHAPES = {
    "smallthinker-21b-a3b": (64, 2560, 768, (48, 96, 192, 384, 768),
                             (192, 64)),
    "lfm2-24b-a2b": (64, 2048, 1536, (8, 16, 32, 64, 128, 256), (256, 64)),
    "sdar-30b-a3b-chat": (128, 2048, 768, (8, 16, 32, 64), (1536,)),
}
SKEW = 2.5                      # moe_expert_load_max_over_mean.pool
PEAK, HBM = 197e12, 819e9
RUN_ROWS, WIDE_TILE = 192, 512  # the parent's runs (PR 32)


def timed(fn, *args, reps=20):
    out = fn(*args)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e3, out


def group_sizes(rng, m, groups, skew):
    """``m`` rows over ``groups``: even, or drawn from loads whose largest
    is ``skew`` times their mean."""
    if not skew:
        sizes = np.full(groups, m // groups)
        sizes[:m - sizes.sum()] += 1
        return sizes.astype(np.int32)
    z = rng.standard_normal(groups)
    lo, hi = 0.0, 4.0
    for _ in range(50):
        s = (lo + hi) / 2
        p = np.exp(s * z)
        lo, hi = (s, hi) if p.max() / p.mean() < skew else (lo, s)
    return rng.multinomial(m, p / p.sum()).astype(np.int32)


def parent_runs(rows, weights, sizes, precision, run=RUN_ROWS):
    """PR 32's ``_in_runs``, kept here as the line the kernel is timed
    against: runs of ``run`` sorted rows, a ``ragged_dot`` call a run."""
    import jax
    import jax.numpy as jnp

    m = rows.shape[0]
    n = -(-m // run)
    rows = jnp.pad(rows, ((0, n * run - m), (0, 0))).reshape(n, run, -1)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    lo = jnp.arange(n, dtype=ends.dtype)[:, None] * run
    cut = jnp.clip(jnp.minimum(ends, lo + run) - jnp.maximum(starts, lo),
                   0, None).astype(sizes.dtype)
    out = jax.lax.map(lambda a: jax.lax.ragged_dot(
        a[0], weights, a[1], precision=precision,
        preferred_element_type=a[0].dtype), (rows, cut))
    return out.reshape(n * run, -1)[:m]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="one name of SHAPES")
    ap.add_argument("--tiles", default="",
                    help="kernel lines at these TM,TN pairs too (a sweep)")
    ap.add_argument("--even", type=int, default=1,
                    help="0: the skewed groups alone")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("moe_microbench: no TPU backend, nothing measured",
              file=sys.stderr)
        return 2
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from paddle_tpu.ops.pallas import grouped_matmul as kernel
    from paddle_tpu.parallel import moe

    highest = jax.lax.Precision.HIGHEST
    sweep = [tuple(int(x) for x in t.split(","))
             for t in args.tiles.split(";") if t]
    results = []
    for name, (groups, K, I, rungs, step_rows) in SHAPES.items():
        if args.only and name != args.only:
            continue
        key = jax.random.key(groups + K)
        gu = jax.random.normal(jax.random.fold_in(key, 1),
                               (groups, K, 2 * I)) * .02
        dn = jax.random.normal(jax.random.fold_in(key, 2),
                               (groups, I, K)) * .02
        f64 = {}                # a group's weights in float64, as needed
        cells = [(m, "step", SKEW) for m in step_rows]
        for r in rungs:
            cells.append((r * groups, f"{r} a group", SKEW))
            if args.even and name == "smallthinker-21b-a3b":
                cells.append((r * groups, f"{r} a group", 0))
        for m, label, skew in cells:
            rng = np.random.default_rng(m + groups)
            sizes_np = group_sizes(rng, m, groups, skew)
            sizes = jnp.asarray(sizes_np)
            rows = jax.random.normal(jax.random.fold_in(key, m), (m, K))
            touched = int((sizes_np > 0).sum())

            def ffn(mm):
                def f(rows, gu, dn, sizes):
                    h = mm(rows, gu, sizes)
                    return mm(jnp.maximum(h[:, :I], 0) * h[:, I:], dn, sizes)
                return jax.jit(f)

            def ragged(prec):
                return lambda a, b, s: jax.lax.ragged_dot(
                    a, b, s, precision=prec, preferred_element_type=a.dtype)

            def parent(a, b, s):
                if RUN_ROWS < a.shape[0] < WIDE_TILE * b.shape[0]:
                    return parent_runs(a, b, s, highest)
                return moe._one_call(a, b, s, highest)

            def tiled(tm, tn):
                """... the widest whole-lane-tile divisor of N up to tn"""
                return lambda a, b, s: kernel.grouped_matmul(
                    a, b, s, tm=tm, tn=max(
                        t for t in range(128, min(tn, b.shape[2]) + 1, 128)
                        if b.shape[2] % t == 0))

            chosen = [kernel.tiles(m, K, 2 * I), kernel.tiles(m, I, K)]
            cases = [("parent", parent),
                     ("ragged_dot highest", ragged(highest)),
                     ("route: " + ("kernel {} {}".format(*chosen)
                                   if all(chosen) else "ragged_dot"),
                      lambda a, b, s: moe.grouped_matmul(a, b, s, highest))]
            if name == "smallthinker-21b-a3b" and m in (
                    192, 768 * groups) and skew:
                cases += [("ragged_dot default", ragged(None)),
                          ("megablox 128", lambda a, b, s: gmm(
                              a, b, s, a.dtype, (128, 128, 128))),
                          ("megablox 512", lambda a, b, s: gmm(
                              a, b, s, a.dtype, (512, 512, 512)))]
            cases += [(f"kernel ({tm}, {tn})", tiled(tm, tn))
                      for tm, tn in sweep]
            # float64 loop on the host, over 64 rows spread over the call
            at = np.unique(np.linspace(0, m - 1, 64).astype(int))
            ends = np.cumsum(sizes_np)
            which = np.searchsorted(ends, at, side="right")
            for g in set(which.tolist()) - set(f64):
                f64[g] = (np.asarray(gu[g], np.float64),
                          np.asarray(dn[g], np.float64))
            r64 = np.asarray(rows[at], np.float64)
            want = np.stack([
                (lambda h: (np.maximum(h[:I], 0) * h[I:]) @ f64[g][1])(
                    r @ f64[g][0]) for r, g in zip(r64, which)])
            flops = 2.0 * m * 3 * K * I
            floor_mxu = 6 * flops / PEAK * 1e3
            floor_hbm = touched * 3 * K * I * 4 / HBM * 1e3
            print(f"{name} {label} ({m} rows, "
                  f"{'skew %.1f' % skew if skew else 'even'}; largest group "
                  f"{sizes_np.max()}, {touched} touched): six-pass MXU floor "
                  f"{floor_mxu:.2f} ms, weights' bytes {floor_hbm:.2f} ms",
                  flush=True)
            for case, mm in cases:
                rec = {"shape": name, "rows": m, "label": label,
                       "skew": skew, "formulation": case}
                try:
                    ms, out = timed(ffn(mm), rows, gu, dn, sizes)
                except Exception as e:  # noqa: BLE001 — a formulation the
                    # compiler refuses is a finding, not a crash
                    print(f"    {case:34s} refused: {str(e)[:200]}",
                          flush=True)
                    results.append(dict(rec, refused=str(e)[:400]))
                    continue
                err = float(np.abs(np.asarray(out[at]) - want).max()
                            / np.abs(want).max())
                print(f"    {case:34s} {ms:9.3f} ms  {flops / ms / 1e9:6.1f} "
                      f"TFLOP/s, x{ms / max(floor_mxu, floor_hbm):.2f} the "
                      f"larger floor, off the float64 loop by {err:.3g}",
                      flush=True)
                results.append(dict(
                    rec, ms=ms, experts_touched=touched,
                    largest_group=int(sizes_np.max()), mxu_floor_ms=floor_mxu,
                    bytes_floor_ms=floor_hbm, tflops=flops / ms / 1e9,
                    rel_err=err))
        del gu, dn
    os.makedirs("chiprun_out", exist_ok=True)
    out = "chiprun_out/moe_formulation_sweep.json"
    if args.only or sweep:
        out = out.replace(".json", f"_{args.only or 'all'}"
                          f"{'_tiles' if sweep else ''}.json")
    with open(out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "results": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
