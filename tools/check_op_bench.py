"""Per-op perf regression gate (reference
tools/check_op_benchmark_result.py).

Compares an op_bench.py results JSON against the committed baseline and
fails (exit 1) when any op regressed by more than --threshold (default
50% — runs of byte-identical programs were seen to drift +-10%, so a
tight gate would flap; 1.5x catches real lowering
regressions like a fusion break or an accidental f32 fallback).

Usage:
    python tools/op_bench.py --out /tmp/r.json
    python tools/check_op_bench.py /tmp/r.json \
        [--baseline tools/op_bench_baseline.json] [--threshold 1.5]
"""
from __future__ import annotations

import argparse
import json
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("results")
    ap.add_argument("--baseline", default="tools/op_bench_baseline.json")
    ap.add_argument("--threshold", type=float, default=1.5)
    args = ap.parse_args()

    res = json.load(open(args.results))
    base = json.load(open(args.baseline))
    if res.get("device_kind") != base.get("device_kind"):
        print(f"SKIP: device_kind mismatch "
              f"({res.get('device_kind')!r} vs baseline "
              f"{base.get('device_kind')!r}) — baseline only applies to "
              "its own hardware")
        return 0

    failures, improved, missing = [], [], []
    for name, b_us in base["ops"].items():
        r_us = res["ops"].get(name)
        if b_us is None:
            continue
        if r_us is None:
            missing.append(name)
            continue
        ratio = r_us / b_us
        tag = ""
        if ratio > args.threshold:
            failures.append((name, b_us, r_us, ratio))
            tag = "  << REGRESSION"
        elif ratio < 1 / args.threshold:
            improved.append(name)
        print(f"{name:32s} base {b_us:10.1f} us  now {r_us:10.1f} us "
              f"({ratio:5.2f}x){tag}")
    if missing:
        print(f"\nops that now FAIL to run: {missing}")
    if improved:
        print(f"\nimproved >{args.threshold}x: {improved} — consider "
              "refreshing the baseline")
    if failures or missing:
        print(f"\nGATE FAILED: {len(failures)} regression(s), "
              f"{len(missing)} newly-failing op(s)")
        return 1
    print("\nGATE PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
