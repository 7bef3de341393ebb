#!/usr/bin/env python
"""The gated delta rule's kernels against their XLA formulations, on the
chip.

    chiprun -- python tools/gated_delta_microbench.py [channel | giga]

With ``channel``: the decay a vector a key channel at 64 heads of 128 x
128 over 64 slots (``solar-open2-250b``), the step at 1, 4, 8, 16 and 32
heads a block, the scan over rungs of 1024 and 4096; written to
``chiprun_out/gated_delta_microbench_channel.json``.

With ``giga``: a state of [64, 128, 128] a slot whose 64 value heads
share 32 key heads, over 32 slots, rungs of 1024 and 2048
(``gigachat35-432b-a28b``): the step and the scan with each key head
REPEATED for its two value heads, as the mixer hands them to the ops; what
making the chunk's ``k k^T`` and ``q k^T`` once a KEY head could save at
most (those two products alone, at 32 heads against 64: decay and beta are
a value head's, so everything behind them is not shared); and the latent
decode kernel alone, 32 slots of 2,000 cached rows of 640 lanes, a hundred
calls inside one jitted loop; written to
``chiprun_out/gated_delta_microbench_giga.json``.

Times, at a hybrid decoder's published head sizes (30 heads, keys of 96,
values of 192, float32): the decode step over 28 slots, a hundred steps
inside one jitted loop that carries the state (one call of a kernel this
short is mostly the host's dispatch), as the XLA formulation (three
contractions) and as the Pallas kernel at 1, 10 and 30 heads a block (a
loop over one 62 MB state reads above the HBM peak on a v5e: read the
lines against each other, not against 819 GB/s); and the prefill's scan
over a rung of 2048 and of 6144 rows, as ``chunk_terms`` + ``lax.scan``
(the XLA form that stays) and as the fused Pallas kernel at 1, 2, 4 and
its own choice of heads a grid step (with how far it lies off the XLA
form) and with half the rung behind ``valid``, and the XLA form's parts
beside them: the five operands laid out by chunks, the terms alone, the
``lax.scan`` alone.  Prints one line per
formulation: milliseconds, and the share of 819 GB/s that the bytes the
mathematics must move make of it (the state read once and written once;
q, k, v, decay, beta read and the outputs written).  Writes
``chiprun_out/gated_delta_microbench.json``.  Refuses to run without a
TPU backend.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CHANNEL = "channel" in sys.argv[1:]
GIGA = "giga" in sys.argv[1:]
H, DK, DV, SLOTS = (64, 128, 128, 64) if CHANNEL else \
    (64, 128, 128, 32) if GIGA else (30, 96, 192, 28)
HEADS_BLOCKS = (1, 4, 8, 16, 32) if CHANNEL else \
    (8, 16, 32) if GIGA else (1, 10, 30)
RUNGS = (1024, 4096) if CHANNEL else (1024, 2048) if GIGA else (2048, 6144)
CHUNK_HEADS = (None, 1, 2, 8)       # the fused scan's heads a grid step
KEY_HEADS = 32                      # under ``giga``: two value heads each
HBM = 819e9


def timed(fn, *args, reps=20):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def latent_decode(say, loops, slots=32, cached=2000, pt=16):
    """The latent decode kernel alone: 64 query rows of 640 lanes a slot
    over ``cached`` rows a slot, ``loops`` calls in one jitted loop (each
    call's output feeds the next one's query, so none is dropped)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import latent_attention as la

    heads, c, row = 64, 512, la.row_lanes(576)
    np_slot = -(-cached // pt)
    key = jax.random.key(47)
    pool = jax.random.normal(key, (slots * np_slot + 1, 1, pt, row),
                             jnp.float32)
    table = (jnp.arange(slots * np_slot, dtype=jnp.int32) + 1).reshape(
        slots, np_slot)
    pos = jnp.full((slots,), cached - 1, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 1), (slots, heads, row),
                          jnp.float32) * 0.05

    @jax.jit
    def run(q, pool):
        def body(_, q):
            o = la.mla_decode_attention(q, pool, table, pos, scale=0.07,
                                        value_dim=c)
            return q.at[:, :, :c].add(1e-3 * o)
        return jax.lax.fori_loop(0, loops, body, q)

    ms = timed(run, q, pool, reps=3) / loops
    nbytes = slots * cached * row * 4
    say(f"latent decode kernel, {slots} slots x {cached} rows of {row} "
        f"lanes", ms, nbytes)
    flops = 2.0 * heads * (576 + c) * slots * cached
    print(f"  = {flops / (ms / 1e3) / 1e12:.2f} TFLOP/s of the work's "
          f"{flops / 1e9:.2f} GFLOP ({100 * flops / (ms / 1e3) / 197e12:.1f}"
          f"% of the bf16 peak; float32 whole takes six passes)",
          flush=True)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_ops as gd
    from paddle_tpu.ops.pallas import gated_delta as kern

    if jax.default_backend() != "tpu":
        print("gated_delta_microbench: no TPU backend", file=sys.stderr)
        return 2
    key = jax.random.key(41)

    def draw(i, *shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    rows = []

    def say(what, ms, nbytes):
        rows.append({"what": what, "ms": ms,
                     "roofline_pct": 100 * nbytes / HBM / (ms / 1e3)})
        print(f"{what}: {ms:.4f} ms, {rows[-1]['roofline_pct']:.1f}% of "
              f"819 GB/s for {nbytes / 1e6:.1f} MB", flush=True)

    n = SLOTS
    state = draw(0, n + 1, H, DK, DV)
    q, k = unit(draw(1, n, H, DK)) * DK ** -0.5, unit(draw(2, n, H, DK))
    # the log decay: one value a head, or one a key channel
    gdim = (DK,) if CHANNEL else ()
    v, g = draw(3, n, H, DV), -jnp.abs(draw(4, n, H, *gdim))
    beta = 2 * jax.nn.sigmoid(draw(5, n, H))
    live = jnp.ones((n,), jnp.int32)
    step_bytes = 2 * n * H * DK * DV * 4
    loops = 100

    def looped(one):
        """``loops`` steps in one program, the state carried: a step's
        device time without the dispatch of a call."""
        def run(q, k, v, g, beta, state):
            def body(_, carry):
                state, acc = carry
                out, state = one(q, k, v, g, beta, state)
                return state, acc + out[0, 0, 0]
            return jax.lax.fori_loop(0, loops, body, (state, 0.0))
        return jax.jit(run)

    xla = looped(lambda *a: gd.step(*a, live.astype(bool)))
    say("step, XLA contractions",
        timed(xla, q, k, v, g, beta, state, reps=3) / loops, step_bytes)
    for hb in HEADS_BLOCKS:
        what = f"step, Pallas, {hb} heads a block"
        try:
            fn = looped(lambda *a, hb=hb: kern.step(*a, live,
                                                    heads_block=hb))
            say(what, timed(fn, q, k, v, g, beta, state, reps=3) / loops,
                step_bytes)
        except Exception as e:  # noqa: BLE001 — a block the chip refuses
            print(f"{what}: refused: {str(e)[:300]}", flush=True)
    for T in RUNGS:
        q, k = unit(draw(6, 1, T, H, DK)) * DK ** -0.5, \
            unit(draw(7, 1, T, H, DK))
        v, g = draw(8, 1, T, H, DV), -jnp.abs(draw(9, 1, T, H, *gdim))
        beta = 2 * jax.nn.sigmoid(draw(10, 1, T, H))
        valid = jnp.asarray([T - 100], jnp.int32)
        nbytes = 4 * (H * (2 * DK + 2 * DV + 1 + (DK if CHANNEL else 1)) * T
                      + H * DK * DV)
        scan = jax.jit(lambda *a: gd.chunked(*a, valid=valid))
        say(f"chunk {T}, terms + lax.scan (XLA)", timed(scan, q, k, v, g,
                                                        beta, reps=5), nbytes)
        want = scan(q, k, v, g, beta)
        for hb in CHUNK_HEADS:
            what = f"chunk {T}, the fused kernel, {hb or 'default'} heads " \
                   f"a block"
            try:
                fused = jax.jit(lambda *a, hb=hb: kern.chunk(
                    *a, valid=valid, heads_block=hb))
                got = fused(q, k, v, g, beta)
                off = max(float(jnp.abs(a - b).max() / jnp.abs(b).max())
                          for a, b in zip(got, want))
                say(what, timed(fused, q, k, v, g, beta, reps=5), nbytes)
                print(f"  off the XLA form by {off:.3g} of the range",
                      flush=True)
            except Exception as e:  # noqa: BLE001 — a block the chip refuses
                print(f"{what}: refused: {str(e)[:300]}", flush=True)
        short = jnp.asarray([T // 2 + 1], jnp.int32)
        say(f"chunk {T}, the fused kernel, {T // 2 + 1} rows real",
            timed(jax.jit(lambda *a: kern.chunk(*a, valid=short)), q, k, v,
                  g, beta, reps=5), nbytes)
        if GIGA:
            # the two products of a chunk that read q and k alone, at the
            # 64 repeated heads the ops run and at the 32 key heads
            def grams(q, k):
                qc, kc = (x.reshape(1, T // gd.CHUNK, gd.CHUNK, -1, DK)
                          for x in (q, k))
                hi = jax.lax.Precision.HIGHEST
                return (jnp.einsum("bnchk,bndhk->bnhcd", kc, kc,
                                   precision=hi),
                        jnp.einsum("bnchk,bndhk->bnhcd", qc, kc,
                                   precision=hi))

            gram = jax.jit(grams)
            say(f"chunk {T}, k k^T and q k^T at {H} repeated heads",
                timed(gram, q, k, reps=5), nbytes)
            say(f"chunk {T}, k k^T and q k^T once a key head "
                f"({KEY_HEADS})",
                timed(gram, q[:, :, ::2], k[:, :, ::2], reps=5), nbytes)
            continue
        lay = [gd.lay(x, None) for x in (q, k, v, g, beta)]
        say(f"chunk {T}, the five operands laid out by chunks (XLA)",
            timed(jax.jit(lambda *a: [gd.lay(x, valid) for x in a]),
                  q, k, v, g, beta, reps=5), nbytes)
        terms_of = gd.chunk_terms_channel if CHANNEL else gd.chunk_terms
        say(f"chunk {T}, the terms alone (XLA)",
            timed(jax.jit(terms_of), *lay, reps=5), nbytes)
        if not CHANNEL:
            s0 = jnp.zeros((1, H, DK, DV), jnp.float32)
            terms = jax.jit(terms_of)(*lay)
            say(f"chunk {T}, lax.scan alone",
                timed(jax.jit(gd.scan_chunks), terms, s0, reps=5), nbytes)
    if GIGA:
        latent_decode(say, loops)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gated_delta_microbench"
              + ("_channel" if CHANNEL else "_giga" if GIGA else "")
              + ".json", "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
