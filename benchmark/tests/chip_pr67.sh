#!/bin/bash
# PR 67's chip calls, one script (logs under chiprun_out/pr67/):
#
#   chiprun --timeout 3600 -- bash benchmark/tests/chip_pr67.sh standins \
#       [--entry float32|bfloat16] [--seeds a,b,c] [--margins auto|entry|n] \
#       [--tag name] cell...
#     the three stand-ins of each cell's configuration through the cell's
#     comparison (benchmark/tests/standins.py), judged by the named entry
#     of check_tolerance: standins_<cell>[_<tag>].log and .json
#
#   chiprun -- bash benchmark/tests/chip_pr67.sh cells <tree> <cell>[:<trace>]...
#     each cell once from <tree> (a copy of what git would commit, or .),
#     --trace 0 then --trace 1 unless one is named, 40 s a window:
#     cell_<cell>_t<0|1>.log, its last line in .json; BUDGET=<seconds>
#     in the environment starts no run later than that into the call
out=$PWD/chiprun_out/pr67; mkdir -p $out
start=$(date +%s)
mode=$1; shift
if [ "$mode" = standins ]; then
  entry=float32; seeds=6700000001,6700000002,6700000003; margins=auto; tag=
  while [ "${1#--}" != "$1" ]; do
    case $1 in
      --entry) entry=$2;; --seeds) seeds=$2;; --margins) margins=$2;;
      --tag) tag=_$2;;
    esac; shift 2
  done
  for w in "$@"; do
    t0=$(date +%s); m=$margins
    # (SmallThinker's reference prints its router's deviation and hands
    # none back: its margin is the entry's)
    [ $w = smallthinker21b-mixedlen ] && m=entry
    python3 benchmark/tests/standins.py --workload $w --entry $entry \
      --seeds $seeds --margins $m --out $out/standins_$w$tag.json \
      > $out/standins_$w$tag.log 2>&1
    echo "=== $w rc=$? $(( $(date +%s) - t0 )) s"
    grep "^\[stand-in [a-z0-9]*\] [a-z0-9.-]* margin\|Error\|error" \
      $out/standins_$w$tag.log | cut -c1-400 | tail -8
    grep -c "NOT correct$" $out/standins_$w$tag.log
  done
elif [ "$mode" = cells ]; then
  tree=$1; shift
  cd $tree || exit 2
  seed=${SEED0:-6700000200}
  for item in "$@"; do
    cell=${item%%:*}; only=${item#*:}; [ "$only" = "$item" ] && only="0 1"
    for t in $only; do
      seed=$((seed + 1)); t0=$(date +%s)
      # (BUDGET: seconds of the call after which no further run starts)
      [ -n "$BUDGET" ] && [ $((t0 - start)) -gt $BUDGET ] && {
        echo "=== $cell trace $t skipped: $((t0 - start)) s gone"; continue; }
      python3 benchmark/run.py --workload $cell --seed $seed --seconds 40 \
        --trace $t > $out/cell_${cell}_t$t.log 2>&1
      rc=$?
      tail -n 1 $out/cell_${cell}_t$t.log > $out/cell_${cell}_t$t.json
      echo "=== $cell trace $t seed $seed rc=$rc $(( $(date +%s) - t0 )) s"
      python3 - $out/cell_${cell}_t$t.json <<'P'
import json, sys
try:
    d = json.load(open(sys.argv[1]))
except Exception as e:
    print("   no result line:", e); sys.exit(0)
m = {k: round(v["value"], 4) for k, v in d["metrics"].items()
     if "roofline" in k or "mfu" in k or k.startswith("compiles_in_window")
     or k in ("served_tokens_per_s", "itl_p99_ms", "setup_s",
              "train_tokens_per_s_per_chip")}
print("  ", d["correct"], d["attempted"], d["failed"],
      d.get("as_run_observed"), m)
P
    done
  done
else
  echo "usage: chip_pr67.sh standins|cells ..."; exit 2
fi
