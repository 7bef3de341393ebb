#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell, once, on the chip.

    python benchmark/sweep.py --workload mistral7b-chat --rates 2,3,4,5 --seconds 30

One process brings the cell up as ``run.py`` does and then offers the
cell's mix at each rate in turn, a warm phase before every window.  It
prints one row per rate; the knee is the highest rate at which no request
fails, slots are not all full and TTFT does not grow through the window.
The rate chosen (0.8 of the knee) is written into the mix file by hand,
with the table in ``PERF.md``.  Not part of a check: the driver never runs
this.
"""
import time

T_START = time.monotonic()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (HERE, os.path.dirname(HERE)) if p not in sys.path]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0               # a sweep is never traced

    import harness
    import serve

    cell = harness.Cell(args.workload, rehearse=args.rehearse)
    run = harness.Run(cell, args, T_START)
    served = serve.Served(run)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(cell.mix, rate_rps=rate)
            st = served.window(mix, args.seed, args.seconds)
            sm = serve.summary(st)
            third = max(1, len(st["ttft"]) // 3)
            row = dict(rate_rps=rate, due=len(st["due"]),
                       failed=len(st["bad"]), completed=st["completed"],
                       ttft_first_third_p50=harness.quantile(
                           st["ttft"][:third], 0.5),
                       ttft_last_third_p50=harness.quantile(
                           st["ttft"][-third:], 0.5), **sm)
            print("SWEEP " + json.dumps(row), flush=True)
            time.sleep(2.0)      # let the grid drain between rates
    finally:
        served.close()
        run.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
