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

``--short`` (PR 65) times the rungs under 2048 rows instead: rungs 256, 512
and 1024 at segments of 128 and 256 rows, ``valid`` at every whole count of
segments, at the products of the configurations whose cells run such rungs
(``SHORT_SHAPES``, ``SHORT_FFNS``), and prints for every (rung, segment)
what ``models/llama.py`` ``dense_rows_segment``'s rule asks of it: the most
the segmented form costs over the whole one at ``valid`` = rung over all
shapes (no more than 5 %), and the least it saves at ``valid`` one segment
short of the rung, as a share of that segment's share of the rung's rows
(at least half), for the fused SwiGLUs, the single products of a weight of
``DENSE_MIN_K`` rows or more, and those of a narrower one, each family by
itself.  A product whose whole form takes under ``HOST_BOUND_MS`` is the
host's dispatch of a call (0.22-0.26 ms whatever the shape, PR 65) and not
the chip's work, and a configuration none of whose cells has a rung that
long (``LONGEST_RUNG``) does not multiply its shapes there: both are timed,
printed, and left out of the rule.

``--dtype bfloat16`` (PR 68) times the same at bfloat16 operands, as op
``mul`` multiplies them: one MXU pass at the default precision, float32
sums, the result rounded to bfloat16; segments of 256, 512 and 1024 rows
(``--short``: 256 and 512), and the rule's verdicts by the bfloat16 table's
``min_k``.  ``--only WORD`` keeps the shapes whose name starts with it
(``--only mistral``: the one configuration served in bfloat16 at PR 68).

``python tools/dense_rows_microbench.py [--short] [--dtype bfloat16]``
(chip only, six to fifteen minutes): each the median of ``--reps`` runs
after a warm-up.  Writes
``chiprun_out/dense_rows_microbench[_short][_bfloat16].json``.
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
          ("olmo down", 11008, 3840, (2048, 6144)),
          # (PR 68) the mixer's two projections, single products of the
          # rungs' programs as the FFN's are not (the SwiGLU is fused)
          ("mistral q|k|v", 4096, 6144, (2048, 3712)),
          ("mistral attn out", 4096, 4096, (2048, 3712))]
FFNS = [("mistral swiglu", 4096, 14336, (2048, 3712)),
        ("olmo swiglu", 3840, 11008, (2048, 6144))]
SEGMENTS = (256, 512)
# (PR 65) the rungs under 2048 rows: K, N of a product; K, I of a SwiGLU
SHORT_RUNGS = (256, 512, 1024)
SHORT_SEGMENTS = (128, 256)
SHORT_SHAPES = [("mistral gate|up", 4096, 28672, SHORT_RUNGS),
                ("mistral down", 14336, 4096, SHORT_RUNGS),
                ("mistral q|k|v", 4096, 6144, SHORT_RUNGS),
                ("mistral attn out", 4096, 4096, SHORT_RUNGS),
                ("granite ssd in", 2048, 8512, SHORT_RUNGS),
                ("granite ssd out", 4096, 2048, SHORT_RUNGS),
                ("nemotron ssd in", 4096, 18560, SHORT_RUNGS),
                ("nemotron ssd out", 8192, 4096, SHORT_RUNGS),
                ("nemotron latent down", 4096, 1024, SHORT_RUNGS),
                ("nemotron latent up", 1024, 4096, SHORT_RUNGS),
                ("nemotron shared up", 4096, 5376, SHORT_RUNGS),
                ("solar q|k|v|gate", 4096, 24576, SHORT_RUNGS),
                ("gigachat q_b", 1536, 12288, SHORT_RUNGS),
                ("sdar q|k|v", 2048, 5120, SHORT_RUNGS),
                ("solar router", 4096, 128, SHORT_RUNGS)]
SHORT_FFNS = [("mistral swiglu", 4096, 14336, SHORT_RUNGS),
              ("granite swiglu", 2048, 8192, SHORT_RUNGS),
              ("lfm2 dense swiglu", 2048, 11776, SHORT_RUNGS),
              ("solar shared swiglu", 4096, 1280, SHORT_RUNGS),
              ("gigachat dense swiglu", 7168, 18432, SHORT_RUNGS),
              ("gigachat shared swiglu", 7168, 2048, SHORT_RUNGS),
              ("olmo swiglu", 3840, 11008, SHORT_RUNGS)]
HOST_BOUND_MS = 0.4
# (a shape's first word is its configuration) the longest whole-prompt rung
# under 2048 rows of any of its cells, where that is not 1024
LONGEST_RUNG = {"nemotron": 512}
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
    ap.add_argument("--short", action="store_true",
                    help="rungs 256, 512, 1024 at segments 128 and 256")
    ap.add_argument("--dtype", choices=("float32", "bfloat16"),
                    default="float32", help="the operands' dtype")
    ap.add_argument("--only", default="",
                    help="the shapes whose name starts with this word")
    ap.add_argument("--segments", default="",
                    help="these segments (rows, comma separated) instead "
                    "of the mode's own")
    args = ap.parse_args(argv)
    shapes, ffns, segments = (SHORT_SHAPES, SHORT_FFNS, SHORT_SEGMENTS) \
        if args.short else (SHAPES, FFNS, SEGMENTS)
    if args.dtype == "bfloat16":
        segments = (256, 512) if args.short else (256, 512, 1024)
    if args.segments:
        segments = tuple(int(s) for s in args.segments.split(","))
    shapes, ffns = ([c for c in cases if c[0].startswith(args.only)]
                    for cases in (shapes, ffns))

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("dense_rows_microbench: no TPU backend, nothing is timed")
        return 2
    from paddle_tpu.ops.math_ops import valid_rows_product, valid_rows_swiglu

    rng = np.random.default_rng(64)
    dtype = jnp.dtype(args.dtype)
    out = {"device": jax.devices()[0].device_kind, "dtype": args.dtype,
           "rows": []}

    def dot(x, w):
        # (op ``mul``'s product: "highest" on float32 operands, one pass
        # on bfloat16 ones, a float32 sum rounded to the operands' dtype)
        return jax.lax.dot_general(
            x, w, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST
            if dtype == jnp.float32 else None).astype(dtype)

    def swiglu(x, w):
        gu = dot(x, w[0])
        width = w[1].shape[0]
        return dot(jax.nn.silu(gu[..., :width]) * gu[..., width:], w[1])

    def draw(k, n):
        return jnp.asarray(rng.normal(size=(k, n)) * k ** -0.5, dtype)

    # (a case draws its matrices when its turn comes: 0.5-0.9 GB each)
    cases = [(what, k, rungs, lambda k=k, n=n: draw(k, n), dot,
              valid_rows_product)
             for what, k, n, rungs in ([] if args.ffn_only else shapes)]
    cases += [(what, k, rungs, lambda k=k, i=i: (draw(k, 2 * i), draw(i, k)),
               swiglu, lambda x, w, v, segment: valid_rows_swiglu(
                   x, w[0], w[1], v, segment))
              for what, k, i, rungs in ffns]
    for what, k, rungs, matrices, whole_fn, seg_fn in cases:
        w = matrices()
        whole = jax.jit(whole_fn)
        for rung in rungs:
            x = jnp.asarray(rng.normal(size=(1, rung, k)), dtype)
            whole_ms, whole_first = timed(lambda: whole(x, w), args.reps)
            want = whole(x, w)
            row = {"product": what, "k": k, "rung": rung,
                   "whole_ms": whole_ms, "whole_first_s": whole_first}
            print(f"{what} rung {rung}: whole {whole_ms:.3f} ms "
                  f"({1e3 * whole_ms / rung:.3f} us a row), first call "
                  f"{whole_first:.2f} s", flush=True)
            for segment in (s for s in segments if s <= rung):
                seg = jax.jit(lambda x, w, v, segment=segment:
                              seg_fn(x, w, v, segment))
                # (the quarters, and what the rule reads: the whole rung
                # and one segment short of it)
                for valid in (range(segment, rung + 1, segment) if args.short
                              else sorted({rung * q // 4 for q in (1, 2, 3, 4)}
                                          | {rung - segment})):
                    v = jnp.asarray(valid, jnp.int32)
                    ms, first = timed(lambda: seg(x, w, v), args.reps)
                    got = seg(x, w, v)
                    off = float(jnp.abs(
                        got[:, :valid].astype(jnp.float32)
                        - want[:, :valid].astype(jnp.float32)).max())
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
    out["rule"] = rule(out["rows"])
    os.makedirs("chiprun_out", exist_ok=True)
    name = "dense_rows_microbench%s%s.json" % (
        "_short" if args.short else "",
        "" if args.dtype == "float32" else "_" + args.dtype)
    with open(os.path.join("chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    return 0


def rule(rows):
    """What ``dense_rows_segment``'s rule asks of every (rung, segment), a
    family of products at a time: ``over``, the most the segmented form
    costs over the whole one at ``valid`` = rung, and ``saved``, the least
    it saves at ``valid`` one segment short of the rung as a share of that
    segment's share of the rows, each with the product it was read at;
    ``taken``: ``over`` <= 5 % and ``saved`` >= half (a rung of one segment
    has nothing to skip).  The families part at the float32 table's
    ``DENSE_MIN_K`` at either dtype (the bfloat16 row takes no single
    product)."""
    import importlib

    # (``paddle_tpu.models`` exports a function of the module's name)
    llama = importlib.import_module("paddle_tpu.models.llama")
    DENSE_MIN_K = llama.DENSE_MIN_K

    def family(r):
        return "fused SwiGLU" if "swiglu" in r["product"] else \
            f"product, K >= {DENSE_MIN_K}" if r["k"] >= DENSE_MIN_K else \
            f"product, K < {DENSE_MIN_K}"

    out = []
    pairs = sorted({(r["rung"], int(key[3:].split("_")[0]), family(r))
                    for r in rows for key in r if key.startswith("seg")})
    for rung, segment, fam in pairs:
        over, saved = (-1.0, None), (9.0, None)
        for r in rows:
            if r["rung"] != rung or family(r) != fam \
                    or r["whole_ms"] < HOST_BOUND_MS \
                    or LONGEST_RUNG.get(r["product"].split()[0], rung) < rung:
                continue
            if f"seg{segment}_valid{rung}" not in r:
                continue
            full = r[f"seg{segment}_valid{rung}"]["ms"] / r["whole_ms"] - 1
            over = max(over, (full, r["product"]))
            if segment < rung:
                short = 1 - r[f"seg{segment}_valid{rung - segment}"]["ms"] \
                    / r["whole_ms"]
                saved = min(saved, (short / (segment / rung), r["product"]))
        if over[1] is None:
            continue
        # (to a tenth of a point: the runs' medians do not repeat closer)
        taken = round(over[0], 3) <= 0.05 and segment < rung \
            and saved[0] >= 0.5
        out.append({"rung": rung, "segment": segment, "family": fam,
                    "over": over, "saved": saved if segment < rung else None,
                    "taken": taken})
        print(f"rung {rung} segment {segment}, {fam}: at the whole rung at "
              f"most {100 * over[0]:+.1f} % ({over[1]}); one segment short "
              "saves " + (f"at least {saved[0]:.2f} of its share ({saved[1]})"
                          if segment < rung else "nothing (one segment)")
              + f": {'taken' if taken else 'not taken'}", flush=True)
    return out


if __name__ == "__main__":
    sys.exit(main())
