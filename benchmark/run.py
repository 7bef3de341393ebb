#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process owns the cell's chips: it loads, warms up, measures for
``--seconds`` and prints, last, the one JSON line ``BENCHMARK.json``'s
contract fixes.  Without a TPU backend, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.  ``--rehearse`` runs the
cell's control flow at the toy sizes its files give, on the CPU, and
prints counts only: never a device metric, never the contract's line.
"""
import time

T_START = time.monotonic()          # set-up is timed from process start

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the system under test is the checkout this file sits in
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a non-negative whole number")

    import harness

    cell = harness.Cell(args.workload, rehearse=args.rehearse)
    if args.seconds is None:
        args.seconds = float(cell.bench["run_seconds"])
    run = harness.Run(cell, args, T_START)
    try:
        # the mix names the module under benchmark/ that runs it
        return importlib.import_module(cell.mix["driver"]).run_cell(run)
    finally:
        run.cleanup()


if __name__ == "__main__":
    sys.exit(main())
