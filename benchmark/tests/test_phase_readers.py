"""The readers of the program's phase spans (PR 24) on hand-built spans
and a hand-built reduced trace, and both serving cells rehearsed on the
CPU with every new metric read by name."""
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

NEW = ("iter_host_ms", "decode_feeds_ms", "book_tokens_ms",
       "queue_wait_p50_ms", "executor_run_host_ms", "idle_decode_host_pct",
       "idle_prefill_host_pct", "idle_no_work_pct", "idle_unattributed_pct")
SERVING = {"mistral7b-chat": "chat", "mistral7b-longprompt": "pool"}


def span(name, start, end, tid=1, **attrs):
    return types.SimpleNamespace(name=name, start=start, end=end, tid=tid,
                                 attrs=attrs)


def args(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)["args"]


def new_metrics(cell):
    return [m["name"] for m in SPEC["per_layer"]
            if m["name"].rsplit(".", 1)[0] in NEW
            and cell in m["workloads"]]


def test_both_serving_cells_list_the_new_metrics():
    assert len(new_metrics("mistral7b-chat")) == 9
    assert len(new_metrics("mistral7b-longprompt")) == 8    # no open loop
    assert "idle_no_work_pct.pool" not in new_metrics(
        "mistral7b-longprompt")


# one scheduler thread (tid 1): two iterations, the first with a prefill;
# a handler thread (tid 2) whose spans must not count
SPANS = [
    span("generation/iteration", 10.000, 10.100),
    span("generation/claim", 10.000, 10.004),
    span("generation/prefill_prepare", 10.004, 10.006),
    span("generation/prefill", 10.006, 10.016),
    span("executor/step", 10.007, 10.015),
    span("generation/prefill_fetch", 10.016, 10.046),
    span("generation/decode_feeds", 10.046, 10.049),
    span("generation/decode_step", 10.049, 10.095),
    span("generation/decode_dispatch", 10.049, 10.055),
    span("executor/step", 10.050, 10.054),
    span("executor/dispatch", 10.051, 10.053),
    span("generation/token_fetch", 10.055, 10.095),
    span("generation/book_tokens", 10.095, 10.099),
    span("generation/iteration", 10.100, 10.160),
    span("generation/decode_feeds", 10.101, 10.102),
    span("generation/decode_step", 10.102, 10.150),
    span("generation/decode_dispatch", 10.102, 10.110),
    span("executor/step", 10.103, 10.109),
    span("generation/token_fetch", 10.110, 10.150),
    span("generation/book_tokens", 10.150, 10.160),
    span("generation/sequence", 10.003, 10.400, queue_wait_ms=7.0),
    span("generation/sequence", 10.003, 10.300, queue_wait_ms=1.0),
    span("generation/sequence", 10.003, 10.200, queue_wait_ms=3.0),
    span("generation/token_fetch", 10.000, 10.100, tid=2),
    span("generation/decode_step", 10.000, 10.200, tid=2),
    span("executor/step", 10.120, 10.130, tid=3),
]


def test_iter_host_is_the_iteration_less_its_device_waits():
    reader = harness.load_module("readers", "iter_host")
    got = reader.read({"spans": SPANS}, **args("iter_host_ms.chat"))
    # 100 - 30 - 40 = 30 ms and 60 - 40 = 20 ms
    assert got == pytest.approx(25.0)
    assert reader.read({"spans": []}, **args("iter_host_ms.pool")) is None


def test_span_means_and_the_queue_wait_median():
    mean = harness.load_module("readers", "host_span_mean")
    ctx = {"spans": SPANS}
    assert mean.read(ctx, **args("decode_feeds_ms.chat")) \
        == pytest.approx(2.0)
    assert mean.read(ctx, **args("book_tokens_ms.pool")) \
        == pytest.approx(7.0)
    q = harness.load_module("readers", "span_attr_quantile")
    assert q.read(ctx, **args("queue_wait_p50_ms.chat")) == 3.0
    # the parent commit's sequence spans carry no such attribute
    bare = [span("generation/sequence", 1.0, 2.0)]
    assert q.read({"spans": bare}, **args("queue_wait_p50_ms.pool")) is None
    inside = harness.load_module("readers", "span_inside_mean")
    # the executor steps under the two decode steps of tid 1 (4 and 6
    # ms); not the prefill's, not the other thread's stray one
    assert inside.read(ctx, **args("executor_run_host_ms.chat")) \
        == pytest.approx(5.0)
    assert inside.read({"spans": bare},
                       **args("executor_run_host_ms.pool")) is None


def traced_ctx(spans):
    """A reduced trace on a clock 100 s behind the host's: the window is
    [-90.01, -89.83]; four program runs, so that the device idles from
    -90.000 to -89.990 (claim .. part of prefill), -89.960 to -89.948
    (end of the prefill fetch .. inside the decode dispatch: 2 ms under
    executor/dispatch), -89.908 to -89.890 (token fetch tail, book_tokens,
    second iteration up to its dispatch) and runs to -89.850; the edges
    are 5 and 20 ms."""
    said = []
    trace = {"to_monotonic": 100.0, "window": (-90.010, -89.830),
             "window_s": 0.180, "busy_s": 0.118,
             "modules": {"jit_prefill": [(-89.990, -89.960)],
                         "jit_decode": [(-90.005, -90.000),
                                        (-89.948, -89.908),
                                        (-89.890, -89.850)]}}
    run = types.SimpleNamespace(say=said.append)
    return {"trace": trace, "trace_spans": spans, "run": run}, said


def test_idle_is_charged_to_the_deepest_span_pro_rata():
    reader = harness.load_module("readers", "idle_by_span")
    ctx, said = traced_ctx(SPANS)
    decode = reader.read(ctx, **args("idle_decode_host_pct.chat"))
    prefill = reader.read(ctx, **args("idle_prefill_host_pct.chat"))
    no_work = reader.read(ctx, **args("idle_no_work_pct.chat"))
    rest = reader.read(ctx, **args("idle_unattributed_pct.chat"))
    rows = ctx["idle_by_span"]

    def ms(*key):
        return round(1e3 * rows[key], 6)

    # first gap, 10.000-10.010: claim 4, prepare 2, prefill 1 + 3 under
    # its executor step
    assert ms("generation/claim", "generation/claim") == 4.0
    assert ms("generation/prefill_prepare",
              "generation/prefill_prepare") == 2.0
    assert ms("generation/prefill", "generation/prefill") == 1.0
    assert ms("generation/prefill", "executor/step") == 3.0
    # second gap, 10.040-10.052: prefill fetch 6, feeds 3, then the
    # dispatch: 1 its own, 1 under executor/step, 1 under
    # executor/dispatch
    assert ms("generation/prefill_fetch", "generation/prefill_fetch") == 6.0
    assert ms("generation/decode_dispatch", "executor/dispatch") == 1.0
    # third gap, 10.092-10.110: token fetch 3, book 4, the bare second
    # iteration 1, feeds 1 (4 with the first iteration's), the dispatch
    assert ms("generation/token_fetch", "generation/token_fetch") == 3.0
    assert ms("generation/book_tokens", "generation/book_tokens") == 4.0
    assert ms("generation/iteration", "generation/iteration") == 2.0
    assert ms("generation/decode_feeds", "generation/decode_feeds") == 4.0
    assert ms("(window edge)", "(window edge)") == 25.0
    gaps_ms = 10.0 + 12.0 + 18.0
    assert 1e3 * sum(rows.values()) == pytest.approx(gaps_ms + 25.0)
    # the four shares are the gaps, as a share of the traced window
    assert no_work == 0.0
    assert rest == pytest.approx(100 * 2.0 / 180.0)
    assert prefill == pytest.approx(100 * 12.0 / 180.0)
    assert decode + prefill + no_work + rest \
        == pytest.approx(100 * gaps_ms / 180.0)
    # the table is printed once, whole, by span name
    text = "\n".join(said)
    assert text.count("device idle by phase span") == 1
    assert "generation/decode_dispatch > executor/dispatch" in text
    assert "generation/book_tokens" in text and "(window edge)" in text


def test_idle_under_wait_work_and_under_no_span():
    reader = harness.load_module("readers", "idle_by_span")
    spans = [span("generation/wait_work", 10.010, 10.030),
             span("generation/iteration", 10.030, 10.040),
             span("generation/decode_step", 10.0395, 10.040),
             span("generation/wait_work", 10.0, 10.2, tid=7)]
    ctx, _ = traced_ctx(spans)
    ctx["trace"]["modules"] = {"jit_decode": [(-89.995, -89.990),
                                              (-89.960, -89.950)]}
    # one gap, 10.010-10.040: 20 ms waiting for work, 9.5 bare iteration
    assert reader.read(ctx, **args("idle_no_work_pct.chat")) \
        == pytest.approx(100 * 20.0 / 180.0)
    assert reader.read(ctx, **args("idle_unattributed_pct.pool")) \
        == pytest.approx(100 * 9.5 / 180.0)
    # a program that records none of the phases (the parent commit):
    # everything is unattributed, nothing raises
    ctx, _ = traced_ctx([span("serving/request", 10.0, 10.2, tid=3)])
    assert reader.read(ctx, **args("idle_decode_host_pct.pool")) == 0.0
    assert reader.read(ctx, **args("idle_unattributed_pct.pool")) \
        == pytest.approx(100 * 40.0 / 180.0)


@pytest.mark.parametrize("metric", [
    "idle_decode_host_pct.chat", "idle_prefill_host_pct.pool",
    "idle_no_work_pct.chat", "idle_unattributed_pct.pool"])
def test_idle_readers_return_none_without_a_trace(metric):
    reader = harness.load_module("readers", "idle_by_span")
    assert reader.read({"trace": None, "trace_spans": SPANS},
                       **args(metric)) is None


def test_the_decode_program_is_told_by_more_than_a_run_count(monkeypatch):
    """A traced window that held no finished prefill: the decode step and
    the one-microsecond reshape that carries its tokens (PR 45) ran
    equally often, and the reshape stands first on the trace's line.
    ``split`` takes the most-run module of a millisecond or more, so a
    roofline over the step's time does not read millions of percent; with
    a prefill in the window, and on a trace of tiny programs alone, it
    chooses as it did."""
    module_time = harness.load_module("readers", "module_time")
    steps = [(1.0 + 0.03 * i, 1.025 + 0.03 * i) for i in range(5)]
    tiny = [(e + 1e-4, e + 1e-4 + 1e-6) for _, e in steps]
    tie = {"modules": {"jit_reshape": tiny, "jit_decode": steps}}
    assert module_time.split(tie) == (steps, [])
    assert module_time.read({"trace": tie}, which="decode") \
        == pytest.approx(25.0)
    assert module_time.read({"trace": tie}, which="prefill") is None
    # the reshape ran MORE often (a joiner's merge): still the step
    more = {"modules": {"jit_reshape": tiny + [(2.0, 2.000001)],
                        "jit_decode": steps}}
    assert module_time.split(more)[0] == steps
    # with prefills: every other module's runs of a millisecond or more
    rung = [(1.2, 1.6)]
    full = {"modules": {"jit_prefill_2048": rung, "jit_reshape": tiny,
                        "jit_decode": steps}}
    assert module_time.split(full) == (steps, rung)
    # nothing of a millisecond: the most-run module, as before
    assert module_time.split({"modules": {"a": tiny, "b": tiny[:2]}}) \
        == (tiny, [])
    assert module_time.split({"modules": {}}) == ([], [])
    # what the tie did to a roofline: the step's share, not 25,000 times it
    roofline_span = harness.load_module("readers", "roofline_span")
    run = types.SimpleNamespace(trace_t0=0.0, trace_t1=10.0,
                                peaks={"hbm_bytes_per_s": 819e9})
    ctx = {"trace": tie, "run": run, "cfg": {"as_run": {"dtype": "float32"}},
           "trace_spans": [span("generation/decode_step", 1.0, 1.03,
                                state_slots=4)]}
    monkeypatch.setattr(harness, "half_a_step", raising=False,
                        value=lambda cfg, slots, itemsize: 819e9 * 0.0125)
    assert roofline_span.read(
        ctx, fn="harness.half_a_step", peak="hbm_bytes_per_s",
        attrs=["state_slots"]) == pytest.approx(50.0)


# -- both serving cells rehearsed, every new metric read by name ------------------

REHEARSE = r"""
import json, sys, time
T0 = time.monotonic()
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import argparse
import harness, serve

cell = harness.Cell(sys.argv[3], rehearse=True)
run = harness.Run(cell, argparse.Namespace(
    workload=sys.argv[3], seed=3000000019, seconds=3.0, trace=0,
    rehearse=True), T0)
kept = {}
finish = run.finish
run.finish = lambda **kw: kept.update(kw) or finish(**kw)
try:
    serve.run_cell(run)
finally:
    run.cleanup()
out = {}
for name in sys.argv[4:]:
    reader, args = cell.reader_of(name)
    out[name] = harness.load_module("readers", reader).read(
        kept["ctx"], **args) is not None
print(json.dumps({"read": out}))
"""


@pytest.mark.parametrize("cell", sorted(SERVING))
def test_rehearsal_reads_every_new_metric_by_name(cell):
    """The rehearsal's own last line holds counts only
    (``harness.Run.finish``), so the readers are driven here on what it
    handed over: every span metric finds its spans; the trace metrics,
    with no trace on the CPU, report nothing.  Presence only: a CPU time
    is never printed under a device metric's name."""
    names = new_metrics(cell)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, "-c", REHEARSE, BENCH, ROOT, cell] + names,
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    read = json.loads(out.stdout.strip().splitlines()[-1])["read"]
    assert sorted(read) == sorted(names)
    for name, found in read.items():
        assert found == (not name.startswith("idle_")), (name, read)
