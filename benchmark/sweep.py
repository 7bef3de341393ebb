#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell, once, on the chip.

    python benchmark/sweep.py --workload mistral7b-chat --rates 2,3,4,5 --seconds 30

One process brings the cell up as ``run.py`` does and then offers the
cell's mix at each rate in turn, a warm phase before every window.  It
prints one row per rate, with the share of slots occupied and the 90th to
99.5th percentiles of the token gaps (``serve.gap_ladder``), so that the
knee and the place of the gate among the gaps come from one run.  The
knee is the highest rate at which no request fails, slots are not all
full and TTFT does not grow through the window.  The rate chosen (0.8 of
the knee, rounded down to a quarter; at least 0.7 of it) is written into
the mix file by hand, with the table in ``PERF.md``; at that rate the gate
has to be ``off_edge``.  Not part of a check: the driver never runs this.

Sweep again whenever an accepted line of the ledger reads
``slot_occupancy_pct.chat`` under 35 or ``stalled_gap_share_pct.chat`` under
8: a gain has then moved the knee, the cell is a lightly loaded replica
and its gate slides off the gaps that carry a prefill towards a plain
decode gap (PR 25 did that; PR 27 swept again).
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
            occ = [g["serving_slot_occupancy"] for g in st["gauges"]]
            fifth = max(1, len(occ) // 5)
            row = dict(rate_rps=rate, due=len(st["due"]),
                       failed=len(st["bad"]), completed=st["completed"],
                       ttft_first_third_p50=harness.quantile(
                           st["ttft"][:third], 0.5),
                       ttft_last_third_p50=harness.quantile(
                           st["ttft"][-third:], 0.5),
                       # has the warm phase filled the slots to their
                       # steady count by the time the window opens?
                       slot_occupancy_first_fifth=sum(occ[:fifth]) / fifth,
                       slot_occupancy_last_fifth=sum(occ[-fifth:]) / fifth,
                       **sm,
                       **serve.gap_ladder(st["gaps"]))
            print("SWEEP " + json.dumps(row), flush=True)
            time.sleep(2.0)      # let the grid drain between rates
    finally:
        served.close()
        run.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
