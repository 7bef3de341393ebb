#!/usr/bin/env python3
"""Times the two state-space (SSD) kernels of ``ops/pallas/ssd.py`` on the
chip at granite-4.0-h-micro's published sizes (64 heads of 64, 128 state
rows, one group; float32), each against its XLA formulation
(``ops/ssd_ops.py``), and says how far the two are apart:

* the decode step over 128 slots' states ``[129, 128, 4096]`` at lane
  blocks of 512 / 1024 / 2048 / 4096, all slots live and half of them,
  with the floor (the live states read once and written once at 819
  GB/s);
* the prefill's chunked scan at rungs 256 and 1024 (the prompt 7/8 of the
  rung) at chunks of 64 / 128 / 256 tokens and lane blocks of 256 / 512 /
  1024, ``LAYERS`` scans chained in one program (each reads the one
  before it, as a model's layers do), so that one dispatch carries that
  many scans and the host's dispatch is not what is timed.

``python tools/ssd_microbench.py`` (chip only, about three minutes): each
the median of ``--reps`` runs after a warm-up.  Writes
``chiprun_out/ssd_microbench.json``.  ``--heads 128 --groups 8`` (PR 63)
times both at nemotron3-super-120b-a12b's sizes: 128 heads of 64 in eight
groups of B and C, a state ``[129, 128, 8192]`` (lane blocks wider than a
group's 1024 lanes are held to them); the file is then
``ssd_microbench_g8.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

P, N = 64, 128       # head_dim, state rows (heads: --heads, default 64)
HBM = 819e9


INNER = 20      # calls dispatched back to back before the host waits
LAYERS = 9      # scans chained in one program (one period's)


def timed(fn, reps):
    """Milliseconds a call, the median over ``reps`` of ``INNER`` calls
    dispatched back to back and waited for once: the device runs them one
    behind the other, so the host's dispatch and its wait (0.8 ms a call
    through this tool) are not in the number."""
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
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--groups", type=int, default=1)
    args = ap.parse_args(argv)
    H, G = args.heads, args.groups
    # B and C of one group as the kernels have always taken them, [.., N]
    bc = (lambda *lead: lead + (N,)) if G == 1 \
        else (lambda *lead: lead + (G, N))

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("ssd_microbench: no TPU backend, nothing is timed")
        return 2
    from paddle_tpu.ops import ssd_ops
    from paddle_tpu.ops.pallas import ssd

    rng = np.random.default_rng(59)

    def draw(*shape, scale=1.0):
        return jnp.asarray(scale * rng.normal(size=shape), jnp.float32)

    a = -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)
    d = jnp.ones((H,), jnp.float32)
    out = {"device": jax.devices()[0].device_kind, "heads": H, "groups": G,
           "step": [], "chunk": []}

    # -- the step ----------------------------------------------------------
    n = args.slots
    x, bm, cm = draw(n, H, P), draw(*bc(n)), draw(*bc(n))
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (n, H))),
                     jnp.float32)
    state0 = draw(n + 1, N, H * P)
    for share in (1.0, 0.5):
        live = jnp.asarray(rng.random(n) < share, jnp.int32) if share < 1 \
            else jnp.ones((n,), jnp.int32)
        floor_ms = 1e3 * 2 * int(live.sum()) * N * H * P * 4 / HBM
        want_y, want_s = jax.jit(ssd_ops.step)(x, dt, a, bm, cm, d, state0,
                                               live.astype(bool))
        xla_step = jax.jit(ssd_ops.step, donate_argnums=6)
        holder = {"s": state0 + 0}

        def run_xla():
            y, holder["s"] = xla_step(x, dt, a, bm, cm, d, holder["s"],
                                      live.astype(bool))
            return y

        row = {"live": int(live.sum()), "floor_ms": floor_ms,
               "xla_ms": timed(run_xla, args.reps)}
        for lanes in (512, 1024, 2048, 4096):
            kern = jax.jit(lambda *t, lanes=lanes: ssd.step(
                *t, lanes_block=lanes), donate_argnums=6)
            got_y, got_s = kern(x, dt, a, bm, cm, d, state0 + 0, live)
            off = float(jnp.abs(jnp.where(live[:, None, None] != 0,
                                          got_y - want_y, 0)).max()
                        / jnp.abs(want_y).max())
            off_s = float(jnp.abs(got_s - want_s).max())
            holder = {"s": state0 + 0}

            def run():
                y, holder["s"] = kern(x, dt, a, bm, cm, d, holder["s"], live)
                return y

            ms = timed(run, args.reps)
            row[f"lanes{lanes}_ms"] = ms
            row[f"lanes{lanes}_off"] = max(off, off_s)
            print(f"step live {row['live']:4d} lanes {lanes:5d}: {ms:.3f} ms "
                  f"(floor {floor_ms:.3f}, {100 * floor_ms / ms:.1f}%), off "
                  f"{off:.2e} / state {off_s:.2e}", flush=True)
        print(f"step live {row['live']:4d} XLA form: {row['xla_ms']:.3f} ms",
              flush=True)
        out["step"].append(row)
    del state0, holder

    # -- the chunked scan ----------------------------------------------------
    for T in (256, 1024):
        valid = jnp.asarray([T - T // 8], jnp.int32)
        x, bm, cm = draw(1, T, H, P), draw(*bc(1, T)), draw(*bc(1, T))
        dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                            (1, T, H))), jnp.float32)
        want_y, want_s = jax.jit(ssd_ops.recurrence)(x, dt, a, bm, cm, d,
                                                     valid=valid)
        scale_y, scale_s = jnp.abs(want_y).max(), jnp.abs(want_s).max()
        row = {"rung": T, "valid": int(valid[0])}
        def chained(one):
            """``LAYERS`` scans, each over the one before's output."""
            def many(x, dt, a, bm, cm, d, valid):
                for _ in range(LAYERS):
                    y, s = one(x, dt, a, bm, cm, d, valid)
                    x = x + 1e-3 * y
                return y, s
            return jax.jit(many)

        for L in (64, 128, 256):
            one = lambda *t, L=L: ssd_ops.chunked(  # noqa: E731
                *t[:6], valid=t[6], chunk=L)
            scan, many = jax.jit(one), chained(one)
            row[f"xla_chunk{L}_ms"] = timed(
                lambda: many(x, dt, a, bm, cm, d, valid), args.reps) / LAYERS
            y, s = scan(x, dt, a, bm, cm, d, valid)
            print(f"scan rung {T} chunk {L} XLA form: "
                  f"{row[f'xla_chunk{L}_ms']:.3f} ms, off the recurrence "
                  f"{float(jnp.abs(y - want_y).max() / scale_y):.2e} / state "
                  f"{float(jnp.abs(s - want_s).max() / scale_s):.2e}",
                  flush=True)
            for lanes in (256, 512, 1024):
                one = lambda *t, L=L, lanes=lanes: ssd.chunk(  # noqa: E731
                    *t[:6], valid=t[6], chunk=L, lanes_block=lanes)
                kern, many = jax.jit(one), chained(one)
                try:
                    ms = timed(lambda: many(x, dt, a, bm, cm, d, valid),
                               args.reps) / LAYERS
                except Exception as e:  # noqa: BLE001 — say and go on
                    print(f"scan rung {T} chunk {L} lanes {lanes}: "
                          f"{str(e)[:300]}", flush=True)
                    continue
                y, s = kern(x, dt, a, bm, cm, d, valid)
                off = float(jnp.abs(y - want_y).max() / scale_y)
                off_s = float(jnp.abs(s - want_s).max() / scale_s)
                row[f"chunk{L}_lanes{lanes}_ms"] = ms
                row[f"chunk{L}_lanes{lanes}_off"] = max(off, off_s)
                print(f"scan rung {T} chunk {L} lanes {lanes}: {ms:.3f} ms, "
                      f"off the recurrence {off:.2e} / state {off_s:.2e}",
                      flush=True)
        out["chunk"].append(row)

    os.makedirs("chiprun_out", exist_ok=True)
    name = "ssd_microbench.json" if G == 1 else f"ssd_microbench_g{G}.json"
    with open(os.path.join("chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
