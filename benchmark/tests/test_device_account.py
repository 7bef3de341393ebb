"""The readers of the engine's own account (PR 36) on hand-built spans,
every new metric's file read through its reader, a program that lacks
the account (the parent) reading nothing, the cross-check against a
hand-built reduced trace, and one serving cell rehearsed on the CPU.

    python -m pytest benchmark/tests/test_device_account.py -s
"""
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, ROOT) if p not in sys.path]

import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)

from test_manifest import TABLE  # noqa: E402

FAMILIES = ("device_starved_pct", "device_idle_known_pct",
            "device_idle_slack_pct", "iter_offcpu_ms", "iter_unnamed_ms",
            "stream_cpu_pct", "pass_max_ms")
# the closed-loop serving cells, by the one table of pins
POOL = [cell for cell, row in TABLE.items() if "closed loop" in row[1]]


def span(name, start, end, tid=1, **attrs):
    return types.SimpleNamespace(name=name, start=start, end=end, tid=tid,
                                 attrs=attrs)


def read(metric, ctx):
    spec = harness.load_json("metrics", metric + ".json")
    return harness.load_module("readers", spec["reader"]).read(
        ctx, **spec["args"])


class Say:
    def __init__(self):
        self.lines = []

    def say(self, msg):
        self.lines.append(msg)


# the scheduler thread (tid 1): a steady pass of 20 ms (launch behind a
# running step, a fetch that waits 8 ms), then a pass of 30 ms that
# settles first and launches into a chip that has been dry for 9 ms for
# certain and 10 at most; a handler thread (tid 2) that must not count
SPANS = [
    span("generation/iteration", 10.000, 10.020, active=4, cpu_ms=9.0,
         stream_cpu_ms=2.0, stream_write_ms=1.0),
    span("generation/claim", 10.000, 10.001, cpu_ms=1.0),
    span("generation/decode_feeds", 10.001, 10.004, cpu_ms=3.0),
    span("generation/decode_step", 10.004, 10.016, ahead=1),
    span("generation/decode_dispatch", 10.004, 10.008, drained=0),
    span("executor/step", 10.005, 10.008, cpu_ms=2.5),
    span("generation/token_fetch", 10.008, 10.016, ready=0),
    span("generation/book_tokens", 10.016, 10.019, cpu_ms=2.0),
    span("generation/publish", 10.019, 10.0195, cpu_ms=0.4),
    span("generation/iteration", 10.020, 10.050, active=4, cpu_ms=14.0,
         stream_cpu_ms=4.0, stream_write_ms=2.0),
    span("generation/claim", 10.020, 10.021, cpu_ms=1.0),
    span("generation/decode_step", 10.021, 10.031),
    span("generation/token_fetch", 10.021, 10.031, ready=0),
    span("generation/book_tokens", 10.031, 10.035, cpu_ms=3.0),
    span("generation/decode_feeds", 10.035, 10.038, cpu_ms=3.0),
    span("generation/decode_step", 10.038, 10.044, ahead=0),
    span("generation/decode_dispatch", 10.038, 10.044, drained=1,
         idle_known_ms=9.0, idle_slack_ms=10.0),
    span("executor/step", 10.039, 10.043, cpu_ms=3.5),
    span("generation/publish", 10.044, 10.045, cpu_ms=0.9),
    span("generation/sequence", 10.0005, 10.400, queue_wait_ms=7.0),
    span("generation/decode_dispatch", 10.000, 10.050, tid=2, drained=1,
         idle_known_ms=50.0, idle_slack_ms=50.0),
]


def test_fourteen_entries_seven_families_for_the_serving_cells():
    """A family is two entries: ``.chat`` moves ``itl_p99_ms`` in the
    one open-loop cell, ``.pool`` ``served_tokens_per_s`` in every
    closed-loop cell (four at PR 36, eight since PR 51: one entry a
    family, not a copy a cell), and every entry has its data file."""
    assert len(POOL) == 8
    mine = [m for m in SPEC["per_layer"]
            if m["name"].rsplit(".", 1)[0] in FAMILIES]
    assert [m["name"] for m in mine] == [
        f + s for f in FAMILIES for s in (".chat", ".pool")]
    for m in mine:
        chat = m["name"].endswith(".chat")
        assert m["workloads"] == (["mistral7b-chat"] if chat else POOL)
        assert m["moves"] == ("itl_p99_ms" if chat
                              else "served_tokens_per_s")
        spec = harness.load_json("metrics", m["name"] + ".json")
        assert spec["unit"] == m["unit"] and spec["moves"] == m["moves"]
        assert spec["layer"] == m["layer"]
        assert spec["reader"] in ("device_account", "iter_account")
    for cell in ["mistral7b-chat"] + POOL:
        assert sum(cell in m["workloads"] for m in mine) == 7


@pytest.mark.parametrize("suffix", [".chat", ".pool"])
def test_every_family_reads_its_number_off_the_spans(suffix):
    say = Say()
    ctx = {"spans": SPANS, "run": say}
    got = {f: read(f + suffix, ctx) for f in FAMILIES}
    wall = 50.0                         # ms, first start to last end
    assert got["device_starved_pct"] == pytest.approx(50.0)
    assert got["device_idle_known_pct"] == pytest.approx(100 * 9 / wall)
    assert got["device_idle_slack_pct"] == pytest.approx(100 * 1 / wall)
    # wall less the fetch spans less the thread's CPU time
    assert got["iter_offcpu_ms"] == pytest.approx(
        ((20 - 8 - 9) + (30 - 10 - 14)) / 2)
    # wall less the direct children (the publish spans end early)
    assert got["iter_unnamed_ms"] == pytest.approx((0.5 + 5.0) / 2)
    assert got["stream_cpu_pct"] == pytest.approx(100 * 6 / wall)
    assert got["pass_max_ms"] == pytest.approx(20.0)
    text = "\n".join(say.lines)
    assert "mean pass by phase" in text and "the longest pass" in text
    # once a run, however many metrics read it
    assert text.count("mean pass by phase") == 1


def test_a_program_without_the_account_reads_nothing_and_does_not_raise():
    """The parent's spans: no ``drained``, no stream attributes; ``cpu_ms``
    on the iteration alone (PR 24)."""
    bare = [span(s.name, s.start, s.end, s.tid,
                 **({"cpu_ms": s.attrs["cpu_ms"]}
                    if s.name == "generation/iteration" else {}))
            for s in SPANS]
    ctx = {"spans": bare, "run": Say()}
    for f in ("device_starved_pct", "device_idle_known_pct",
              "device_idle_slack_pct", "stream_cpu_pct"):
        assert read(f + ".pool", ctx) is None
    # what only needs start, end and PR 24's cpu_ms reads on the parent
    assert read("iter_unnamed_ms.pool", ctx) == pytest.approx(2.75)
    assert read("iter_offcpu_ms.pool", ctx) == pytest.approx(4.5)
    assert read("pass_max_ms.pool", ctx) == pytest.approx(20.0)
    assert read("iter_unnamed_ms.pool", {"spans": []}) is None
    assert read("device_starved_pct.chat", {}) is None


def test_the_account_over_the_traced_seconds_goes_beside_the_traces_idle():
    """Trace clock = host clock - 10: two program runs, 0.004-0.016 and
    0.039-0.049; between them the chip ran nothing for 23 of 45 ms."""
    say = Say()
    trace = {"window": (0.0, 0.050), "window_s": 0.050, "busy_s": 0.020,
             "to_monotonic": 10.0,
             "modules": {"jit_decode": [(0.004, 0.016), (0.039, 0.049)]}}
    ctx = {"spans": SPANS, "trace_spans": SPANS, "trace": trace,
           "run": say}
    assert read("device_idle_known_pct.pool", ctx) is not None
    line, = [m for m in say.lines if m.startswith("device account over")]
    assert "2 launches, 1 drained" in line
    assert f"idle known {100 * 9 / 45:.3f} %" in line
    assert f"at most {100 * 10 / 45:.3f} %" in line
    assert f"no program ran {100 * 23 / 45:.3f} %" in line
    assert "no operation ran 60.000 %" in line
    read("device_idle_slack_pct.pool", ctx)
    assert sum(m.startswith("device account over") for m in say.lines) == 1


def test_a_serving_cell_rehearses_with_the_new_entries_declared():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mistral7b-chat", "--rehearse", "--seed", "3600000007",
         "--seconds", "2"], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    cell = harness.Cell("mistral7b-chat", rehearse=True)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert [n for n in names if n.rsplit(".", 1)[0] in FAMILIES] \
        == [f + ".chat" for f in FAMILIES]
