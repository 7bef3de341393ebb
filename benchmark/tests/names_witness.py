#!/usr/bin/env python3
"""On the chip: that a renaming of ``per_layer`` changed names and nothing
a cell reads.  One traced run of a cell as the driver makes it, and on
that run's own trace, spans and counters every per-layer value read
twice: as the manifest's entries resolve now (``harness.Cell.reader_of``:
the data file's arguments, then the configuration's, then the mix's) and
as the parent's files gave reader and arguments
(``fixtures/per_layer_at_pr54.json``).  The two readings of a value come
from the same numbers through the same reader, so they have to be equal
to the last bit; a value the one finds and the other does not is a fault.

    python3 benchmark/tests/names_witness.py <cell> <seed> [seconds]

The run's own output and result line are the benchmark's; the pairs go to
``chiprun_out/names/<cell>.json`` and, one line a pair that differs, to
standard error.  Exit code 1 where any pair differs or the run is not
correct.
"""
import io
import json
import math
import os
import sys
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, ROOT) if p not in sys.path]

import harness  # noqa: E402
import run as benchmark_run  # noqa: E402


def main(argv):
    cell, seed = argv[0], int(argv[1])
    with open(os.path.join(HERE, "fixtures", "per_layer_at_pr54.json")) as f:
        rows = [r for r in json.load(f)["rows"] if cell in r["cells"]]
    pairs, kept = [], {}
    read_now, finish = harness.Run.read_per_layer, harness.Run.finish

    def read_both(self, ctx):
        now = read_now(self, ctx)
        for r in rows:
            reader = harness.load_module("readers", r["reader"])
            with redirect_stdout(io.StringIO()):   # the accounts' tables
                then = reader.read(ctx, **r["args"])
            if then is not None and not math.isfinite(then):
                then = None
            value = now.get(r["new"], {}).get("value")
            pairs.append({"at_pr54": r["old"], "now": r["new"],
                          "read_as_at_pr54": then, "read_now": value,
                          "same": then == value})
        kept["values"] = len(now)
        return now

    def finish_and_keep(self, **kw):
        rc = finish(self, **kw)
        kept.update(correct=bool(kw["correct"]), failed=int(kw["failed"]),
                    attempted=int(kw["attempted"]), device=self.device)
        return rc

    harness.Run.read_per_layer = read_both
    harness.Run.finish = finish_and_keep
    rc = benchmark_run.main(["--workload", cell, "--seed", str(seed),
                             "--trace", "1"] + (
        ["--seconds", argv[2]] if len(argv) > 2 else []))
    differ = [p for p in pairs if not p["same"]]
    where = os.path.join(ROOT, "chiprun_out", "names")
    os.makedirs(where, exist_ok=True)
    with open(os.path.join(where, cell + ".json"), "w") as f:
        json.dump(dict(kept, cell=cell, seed=seed, rc=rc, rows=len(rows),
                       differ=len(differ), pairs=pairs), f, indent=1)
    for p in differ:
        print(f"[names_witness {cell}] DIFFERS: {json.dumps(p)}",
              file=sys.stderr)
    print(f"[names_witness {cell}] seed {seed}: {kept.get('values')} values "
          f"of {len(rows)} entries, {len(differ)} pairs differ, correct "
          f"{kept.get('correct')}, failed {kept.get('failed')}",
          file=sys.stderr)
    return int(bool(rc or differ or len(pairs) != len(rows)
                    or not kept.get("correct") or kept.get("failed")))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
