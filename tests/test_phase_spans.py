"""Phase spans of a serving iteration (PR 24): the span tree from the
scheduler loop down through ``Executor.run``, its mirror on the
profiler's timeline, the two histograms that read it, and the HBM
sampler's probe.

The engine is a tiny paged llama on the CPU; every number here is a
count or an ordering, never a device time.
"""
import glob
import os
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, observatory, promtext, telemetry
from paddle_tpu.serving import GenerationEngine, ServingEngine
from paddle_tpu.serving.server import ServingServer

MODEL = dict(vocab_size=61, hidden=32, num_layers=2, num_heads=4,
             num_kv_heads=2, intermediate=64)
PROMPT = list(range(1, 12))

EXECUTOR_PHASES = ["executor/prepare", "executor/gather_state",
                   "executor/stage_feed", "executor/dispatch",
                   "executor/commit_state"]


@pytest.fixture(scope="module")
def engine():
    eng = GenerationEngine(MODEL, num_slots=2, max_seq_len=64,
                           attn_impl="xla", seed=0,
                           page_tokens=8, prefix_reuse=False)
    eng.warmup()
    yield eng
    eng.close()


@pytest.fixture()
def traced(engine):
    """One request of five tokens through the warm engine: ``(result,
    spans)``, the spans in completion order."""
    pt.set_flags({"FLAGS_telemetry": True})
    telemetry.clear_spans()
    res = engine.generate(PROMPT, 5, timeout=120)
    # one pass more than decode steps: the last only settles (PR 29)
    return res, _spans_after(res["steps"] + 1)


def _spans_after(iterations: int):
    """The ring once ``iterations`` scheduler passes are in it: a
    request's future resolves inside the last pass, a moment before
    that pass's own spans close."""
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        spans = telemetry.get_spans()
        if len(_named(spans, "generation/iteration")) >= iterations:
            return spans
        time.sleep(0.005)
    raise AssertionError(f"fewer than {iterations} iterations recorded")


def _children(spans, parent):
    return [s for s in spans if s.parent_id == parent.span_id]


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def _less_cpu(span):
    """A phase span's attributes without its ``cpu_ms`` (PR 36): every
    phase that times the thread's CPU must carry one."""
    attrs = dict(span.attrs)
    assert attrs.pop("cpu_ms") >= 0.0
    return attrs


def test_decode_iteration_span_tree(traced):
    """Every scheduler pass is a root ``generation/iteration`` whose
    children, in order, are the phases of the table in README "Serving
    observability"; the decode dispatch reaches down to the executor's
    own phases.  One decode step is kept in flight (PR 29): the first
    pass only dispatches, each pass after it dispatches the next step
    and then fetches and books the one before, the last only settles."""
    res, spans = traced
    iters = _named(spans, "generation/iteration")
    decoding = [it for it in iters
                if _named(_children(spans, it), "generation/decode_step")]
    assert res["steps"] == 4 and len(decoding) == res["steps"] + 1
    for it in iters:
        assert it.parent_id is None
        assert it.trace_id != res["trace_id"]
        assert set(it.attrs) == {"active", "claimed", "queued", "cpu_ms",
                                 "stream_write_ms", "stream_cpu_ms"}
    for i, it in enumerate(decoding):
        first, last = i == 0, i == len(decoding) - 1
        kids = sorted(_children(spans, it), key=lambda s: s.start)
        assert [k.name for k in kids if k.name != "generation/publish"] \
            == ["generation/claim", "generation/decode_feeds",
                "generation/decode_step"] \
            + ([] if first else ["generation/book_tokens",
                                 "generation/release"])
        assert kids[-1].name == "generation/publish"
        assert all(k.tid == it.tid and _inside(k, it) for k in kids)
        step = _named(kids, "generation/decode_step")[0]
        inner = sorted(_children(spans, step), key=lambda s: s.start)
        assert [k.name for k in inner] == \
            ([] if last else ["generation/decode_dispatch"]) \
            + ([] if first else ["generation/token_fetch"])
        # `ahead`: the dispatch went out before the step before it was
        # fetched; the first has none before it, the last no dispatch
        assert step.attrs == ({"active": 1} if last else
                              {"active": 1, "ahead": int(not first)})
        if last:
            continue
        exe_step, = _children(spans, inner[0])
        assert exe_step.name == "executor/step"
        assert [k.name for k in sorted(_children(spans, exe_step),
                                       key=lambda s: s.start)] \
            == EXECUTOR_PHASES
    feeds = _named(spans, "generation/decode_feeds")
    book = _named(spans, "generation/book_tokens")
    assert [_less_cpu(f) for f in feeds] \
        == [{"active": 1}] * 4 + [{"active": 0}]
    assert _less_cpu(book[0]) == {"tokens": 1, "finished": 0}
    assert _less_cpu(book[-1]) == {"tokens": 1, "finished": 1}
    assert _named(spans, "generation/claim")[0].attrs == {"claimed": 1}


def test_sequence_span_keeps_request_trace_and_queue_wait(traced):
    """The request's own trace stays whole: ``generation/sequence`` is a
    root under the request's trace id (not a child of the scheduler's
    open ``generation/claim``), the per-request prefill phases hang
    under it, and its ``queue_wait_ms`` is the result's."""
    res, spans = traced
    seq, = _named(spans, "generation/sequence")
    assert seq.parent_id is None
    assert seq.trace_id == res["trace_id"]
    assert seq.attrs["queue_wait_ms"] == res["queue_wait_ms"]
    for name in ("generation/prefill_prepare", "generation/prefill",
                 "generation/prefill_fetch"):
        s, = _named(spans, name)
        assert s.parent_id == seq.span_id and s.trace_id == seq.trace_id
    # the phases that serve many sequences link to them instead
    for name in ("generation/decode_feeds", "generation/decode_step",
                 "generation/book_tokens"):
        assert _named(spans, name)[0].links == (seq.context(),)


def test_existing_spans_keep_start_end_and_attributes(traced):
    """``generation/decode_step`` and ``generation/prefill`` are read by
    the benchmark (``decode_step_mean_ms.*``, ``readers/roofline.py``):
    same attributes, same start and end rules as before the phases
    were added around them."""
    _, spans = traced
    prepare, = _named(spans, "generation/prefill_prepare")
    prefill, = _named(spans, "generation/prefill")
    fetch, = _named(spans, "generation/prefill_fetch")
    # (PR 36: the span that holds a launch also says whether the device
    # had run dry; the idle engine's first launch always finds it so)
    assert prefill.attrs == {
        "tokens": len(PROMPT), "bucket": 16, "rows_run": 16,
        "slot": prefill.attrs["slot"], "drained": 1, "idle_known_ms": prefill.attrs["idle_known_ms"],
        "idle_slack_ms": prefill.attrs["idle_slack_ms"]}
    assert prepare.attrs == {"slot": prefill.attrs["slot"], "bucket": 16}
    # the prefill span opens at the executor call and closes at
    # dispatch; the blocking read of its token is the fetch span's
    assert prepare.end <= prefill.start and prefill.end <= fetch.start
    exe_step, = _children(spans, prefill)
    assert exe_step.name == "executor/step"
    steps = _named(spans, "generation/decode_step")
    for step in steps:
        assert set(step.attrs) <= {"active", "ahead"}
        assert step.attrs["active"] == 1
        kids = _children(spans, step)
        assert 1 <= len(kids) <= 2 and all(_inside(k, step) for k in kids)
    feeds = _named(spans, "generation/decode_feeds")
    books = _named(spans, "generation/book_tokens")
    # a pass builds its feeds, then talks to the device, then books the
    # step it fetched: the first pass has nothing to book yet
    for f, s in zip(feeds, steps):
        assert f.end <= s.start
    for s, b in zip(steps[1:], books):
        assert s.end <= b.start and _named(_children(spans, s),
                                           "generation/token_fetch")


def test_wait_work_span_only_when_the_loop_waited(engine):
    pt.set_flags({"FLAGS_telemetry": True})
    engine.generate(PROMPT, 2, timeout=120)     # leaves the loop idle
    time.sleep(0.1)     # ... once the pass that resolved it has closed
    telemetry.clear_spans()
    engine.generate(PROMPT, 3, timeout=120)
    spans = _spans_after(3)
    waits = _named(spans, "generation/wait_work")
    iters = _named(spans, "generation/iteration")
    # the idle stretch before the request is one span, however many
    # 20 ms polls it took; back-to-back iterations record none (two
    # decode steps are three passes: dispatch, dispatch + settle, settle)
    assert len(waits) == 1 and len(iters) == 3
    assert waits[0].attrs == {"queued": 0}
    assert waits[0].parent_id is None
    assert waits[0].end <= min(it.start for it in iters)


def test_executor_run_phase_spans_in_order():
    pt.set_flags({"FLAGS_telemetry": True})
    x = layers.data("x", [4])
    loss = layers.mean(layers.fc(x, 8, act="relu"))
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    xv = np.ones((2, 4), "float32")
    telemetry.clear_spans()
    exe.run(feed={"x": xv}, fetch_list=[loss])
    exe.run(feed={"x": xv}, fetch_list=[loss])
    spans = telemetry.get_spans()
    first, second = _named(spans, "executor/step")

    def phases(step):
        return [s.name for s in sorted(_children(spans, step),
                                       key=lambda s: s.start)]

    # a miss adds the jit build (``startup/step_build``, until PR 53 a
    # first ``executor/compile``) and the AOT compile
    extra = ("startup/step_build", "executor/compile")
    assert [n for n in phases(first) if n not in extra] \
        == EXECUTOR_PHASES + ["executor/fetch"]
    assert [n for n in phases(first) if n in extra] == list(extra)
    assert phases(second) == EXECUTOR_PHASES + ["executor/fetch"]
    gather, = [s for s in _children(spans, second)
               if s.name == "executor/gather_state"]
    commit, = [s for s in _children(spans, second)
               if s.name == "executor/commit_state"]
    assert gather.attrs["vars"] >= 2 and "vars" in commit.attrs


def test_iteration_host_ms_is_the_iteration_less_its_device_waits(engine):
    pt.set_flags({"FLAGS_telemetry": True})
    hist = telemetry.metrics.histogram("serving_iteration_host_ms")
    before = hist.summary()
    telemetry.clear_spans()
    engine.generate(PROMPT, 4, timeout=120)
    spans = _spans_after(4)
    after = hist.summary()
    iters = _named(spans, "generation/iteration")
    assert after["count"] - before["count"] == len(iters)
    waits = [s for s in spans if s.name in ("generation/token_fetch",
                                            "generation/prefill_fetch")]
    assert len(waits) == 4      # one prefill, three decode steps
    want = sum(it.duration_ms for it in iters) \
        - sum(w.duration_ms for w in waits)
    assert after["sum"] - before.get("sum", 0.0) \
        == pytest.approx(want, abs=1e-3 * len(iters))


def test_new_histograms_on_live_metrics_pass_strict_validator():
    pt.set_flags({"FLAGS_telemetry": True})
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        x = layers.data("x", [8])
        y = layers.fc(x, 8)
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    from paddle_tpu.inference import Predictor

    eng = ServingEngine(Predictor(main, ["x"], [y], scope=scope),
                        workers=1, warmup_shapes={"x": (8,)})
    gen = GenerationEngine(MODEL, num_slots=2, max_seq_len=64,
                           attn_impl="xla", seed=0,
                           page_tokens=8, prefix_reuse=False)
    eng.attach_generator(gen)
    gen.warmup()
    srv = ServingServer(eng).start()
    try:
        res = gen.generate(PROMPT, 3, timeout=120)
        with urllib.request.urlopen(srv.url + "/metrics", timeout=30) as r:
            text = r.read().decode()
    finally:
        srv.close()
    assert promtext.validate_lines(text) == []
    fams = promtext.parse_exposition(text)
    assert fams["paddle_tpu_serving_iteration_host_ms"] \
        .histogram_count() >= 3
    assert fams["paddle_tpu_serving_generate_queue_wait_ms"] \
        .histogram_count() >= 1
    # the queue-wait histogram names requests, like serving_ttft_ms
    # (its exemplars are the slowest of the recent window)
    ex = telemetry.metrics.histogram(
        "serving_generate_queue_wait_ms").exemplars()
    assert ex and all(len(e["trace_id"]) == len(res["trace_id"])
                      for e in ex)


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the order
    of enters and exits."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture()
def fake_annotation(monkeypatch):
    import jax

    _FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    return _FakeAnnotation.log


def test_telemetry_off_records_no_span_and_enters_no_annotation(
        engine, fake_annotation):
    pt.set_flags({"FLAGS_telemetry": False})
    try:
        # the idle loop sits in a wait_work span begun while telemetry
        # was on; the first request ends it
        engine.generate(PROMPT, 1, timeout=120)
        telemetry.clear_spans()
        hist = telemetry.metrics.histogram("serving_iteration_host_ms")
        count = hist.summary()["count"]
        res = engine.generate(PROMPT, 3, timeout=120)
        assert len(res["tokens"]) == 3 and res["trace_id"] is None
        assert telemetry.get_spans() == []
        assert fake_annotation == []
        assert hist.summary()["count"] == count
    finally:
        pt.set_flags({"FLAGS_telemetry": True})


def test_span_end_unwinding_leaves_no_annotation_open(fake_annotation):
    pt.set_flags({"FLAGS_telemetry": True})
    telemetry.clear_spans()
    outer = telemetry.span_begin("t/outer")
    with pytest.raises(RuntimeError):
        with telemetry.trace_span("t/mid"):
            telemetry.span_begin("t/leaked")    # never ended by its owner
            raise RuntimeError("boom")
    # t/mid's exit unwound t/leaked above it, innermost first
    assert fake_annotation == [
        ("enter", "t/outer"), ("enter", "t/mid"), ("enter", "t/leaked"),
        ("exit", "t/leaked"), ("exit", "t/mid")]
    telemetry.span_end(outer)
    telemetry.span_end(outer)                   # a double end is a no-op
    assert fake_annotation[-1] == ("exit", "t/outer")
    assert len(fake_annotation) == 6
    assert all(s._annotation is None and s.end is not None
               for s in telemetry.get_spans())
    # a detached span may end on another thread: it never gets one
    telemetry.span_end(telemetry.span_begin("t/detached", detached=True))
    assert len(fake_annotation) == 6


def test_profiler_trace_carries_the_program_spans(engine, tmp_path):
    """A device trace taken by any means shows the program's spans on
    the profiler's own timeline: here ``jax.profiler.start_trace`` on
    the CPU backend around a few engine iterations."""
    import jax
    from jax.profiler import ProfileData

    pt.set_flags({"FLAGS_telemetry": True})
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.generate(PROMPT, 4, timeout=120)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    names = set()
    for plane in ProfileData.from_file(found[-1]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.update(e.name for e in line.events)
    for want in ("generation/iteration", "generation/decode_feeds",
                 "generation/token_fetch", "executor/gather_state",
                 "executor/commit_state"):
        assert want in names, sorted(n for n in names if "/" in n)[:40]


class _StatsDevice:
    def __init__(self, idx, stats):
        self.id, self._stats = idx, stats

    def memory_stats(self):
        return self._stats


def test_device_live_bytes_reads_allocator_stats_where_kept(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "local_devices", lambda: [
        _StatsDevice(0, {"bytes_in_use": 1000, "peak_bytes_in_use": 9999}),
        _StatsDevice(1, {"bytes_in_use": 24})])
    monkeypatch.setattr(jax, "live_arrays", lambda: pytest.fail(
        "the live-array walk must not run where the allocator counts"))
    assert observatory.device_live_bytes() == {
        "total": 1024, "per_device": {0: 1000, 1: 24}}


def test_device_live_bytes_falls_back_to_the_live_array_walk(monkeypatch):
    import jax
    import jax.numpy as jnp

    keep = jnp.ones((256, 4), "float32")         # 4096 bytes, device 0
    assert jax.local_devices()[0].memory_stats() is None   # this CPU
    snap = observatory.device_live_bytes()
    assert snap["total"] >= keep.nbytes
    assert snap["per_device"][keep.devices().pop().id] >= keep.nbytes
    # one device without statistics sends the whole probe to the walk
    monkeypatch.setattr(jax, "local_devices", lambda: [
        _StatsDevice(0, {"bytes_in_use": 7}), _StatsDevice(1, None)])
    assert observatory.device_live_bytes()["total"] >= keep.nbytes


# -- the device account and a pass's own account of its time (PR 36) -------

from paddle_tpu.framework.executor import FetchHandle  # noqa: E402
from paddle_tpu.monitor import stat_get  # noqa: E402
from paddle_tpu.serving import generation  # noqa: E402
from paddle_tpu.serving.generation import DeviceAccount  # noqa: E402
from paddle_tpu.serving.streams import stream_meter  # noqa: E402


class _Program:
    """A launched program as the account sees it: its first output,
    which reads ready from the instant the device finishes it."""

    def __init__(self, clock, done_at=None):
        self.clock, self.done_at = clock, done_at

    def ready(self):
        return self.done_at is not None and self.clock() >= self.done_at


class _Device:
    """A scripted device behind a fake clock: runs what it is given in
    order, ``step`` seconds each, and keeps its own idle time: the truth
    the account's floor and ceiling must enclose."""

    def __init__(self, step):
        self.now, self.step = 0.0, step
        self.free_at = self.idle = 0.0
        self.acct = DeviceAccount(clock=lambda: self.now)

    def clock(self):
        return self.now

    def host(self, seconds):
        self.now += seconds

    def launch(self):
        self.acct.probe()
        start = max(self.now, self.free_at)
        self.idle += start - self.free_at if self.free_at else 0.0
        prog = _Program(self.clock, start + self.step)
        self.free_at = prog.done_at
        return prog, self.acct.launched(prog)

    def fetch(self, prog):
        start = self.now
        ready = self.acct.fetch_begin(prog, start)
        self.now = max(self.now, prog.done_at)
        self.acct.fetch_end(start, self.now)
        return ready


def test_account_books_nothing_while_the_device_sets_the_pace():
    """One step kept in flight under a host half shorter than the
    device's step: every probe reads not ready, every fetch waits."""
    dev = _Device(step=0.017)
    ahead, first = dev.launch()
    assert first == {"drained": 1, "idle_known_ms": 0.0,
                     "idle_slack_ms": 0.0}       # nothing is known yet
    for _ in range(50):
        dev.host(0.004)                          # feeds
        nxt, attrs = dev.launch()
        assert attrs == {"drained": 0}
        assert dev.fetch(ahead) == 0             # it waited
        dev.host(0.006)                          # booking
        dev.acct.probe()
        ahead = nxt
    a = dev.acct
    assert (a.dispatches, a.drained) == (51, 1)
    assert a.idle_known_s == a.idle_slack_s == dev.idle == 0.0
    # the first fetch waits out a whole step less the feeds, the others
    # a step less the host's half
    assert a.wait_s == pytest.approx(0.013 + 49 * 0.007, abs=1e-9)


def test_account_books_a_settle_pass_exactly():
    """A pass that settles first: the fetch waits for the only program
    outstanding, so the gap is the launch less the fetch's end, known to
    the last microsecond, floor and ceiling alike."""
    dev = _Device(step=0.017)
    prog, _ = dev.launch()
    dev.host(0.002)
    assert dev.fetch(prog) == 0
    fetch_end = dev.now
    dev.host(0.0093)                             # book, feeds, prepare
    _, attrs = dev.launch()
    assert attrs == {"drained": 1, "idle_known_ms": 9.3,
                     "idle_slack_ms": 9.3}
    assert dev.acct.idle_known_s == pytest.approx(dev.now - fetch_end)
    assert dev.idle == pytest.approx(0.0093)


def test_account_encloses_the_truth_when_the_host_sets_the_pace():
    """A host half longer than the device's step: the chip runs dry
    somewhere between two probes, so the truth lies between the floor
    (since the probe that found it dry) and the ceiling (since it was
    last seen busy), and the two are a probe's distance apart."""
    dev = _Device(step=0.005)
    ahead, _ = dev.launch()
    for _ in range(40):
        dev.host(0.004)
        nxt, attrs = dev.launch()
        dev.fetch(ahead)
        dev.host(0.003)
        dev.acct.probe()
        dev.host(0.003)
        dev.acct.probe()
        ahead = nxt
    a = dev.acct
    assert a.drained > 30 and dev.idle > 0.1
    assert a.idle_known_s <= dev.idle <= a.idle_slack_s
    # no stretch between two probes is longer than 4 ms
    assert a.idle_slack_s - a.idle_known_s <= a.drained * 0.004 + 1e-9


def test_account_takes_a_fetch_that_found_its_program_ready():
    """A fetch that did not wait says only that the program finished
    before it: idle for certain from the fetch's entry, possibly since
    the launch (the last time the chip was seen busy)."""
    dev = _Device(step=0.002)
    prog, _ = dev.launch()
    t_launch = dev.now
    dev.host(0.010)
    assert dev.fetch(prog) == 1
    t_fetch = dev.now
    dev.host(0.003)
    _, attrs = dev.launch()
    assert attrs["drained"] == 1
    assert attrs["idle_known_ms"] == pytest.approx(
        (dev.now - t_fetch) * 1e3)
    assert attrs["idle_slack_ms"] == pytest.approx(
        (dev.now - t_launch) * 1e3)
    assert attrs["idle_known_ms"] <= dev.idle * 1e3 <= attrs["idle_slack_ms"]


def test_fetch_handle_ready_neither_waits_nor_counts_a_host_sync():
    import jax.numpy as jnp

    before = stat_get("host_syncs")
    h = FetchHandle(jnp.arange(4) + 1)
    while not h.ready():        # a probe, however often, is no sync
        time.sleep(0.001)
    assert stat_get("host_syncs") == before
    assert h.numpy().tolist() == [1, 2, 3, 4] and h.ready()
    assert stat_get("host_syncs") == before + 1


def test_launch_and_fetch_spans_carry_the_account(traced, engine):
    """Every span that holds a launch says ``drained``; a fetch span says
    whether its program was ``ready``; the sums on the spans are the
    account's own and the counters' (`/metrics`)."""
    _, spans = traced
    launches = [s for s in spans if s.name in (
        "generation/prefill", "generation/decode_dispatch")]
    assert len(launches) == 5           # one prefill, four decode steps
    for s in launches:
        assert s.attrs["drained"] in (0, 1)
        if s.attrs["drained"]:
            assert 0.0 <= s.attrs["idle_known_ms"] <= s.attrs["idle_slack_ms"]
        else:
            assert "idle_known_ms" not in s.attrs
    fetches = [s for s in spans if s.name in (
        "generation/prefill_fetch", "generation/token_fetch")]
    assert len(fetches) == 5
    assert all(s.attrs["ready"] in (0, 1) for s in fetches)
    # the first decode step goes out after the prefill was fetched: the
    # chip is dry for certain, the whole gap known
    first = _named(spans, "generation/decode_dispatch")[0]
    assert first.attrs["drained"] == 1
    assert first.attrs["idle_known_ms"] > 0.0


def test_account_sums_reach_the_counters(engine):
    pt.set_flags({"FLAGS_telemetry": True})
    names = ("serving_device_dispatches", "serving_device_dispatches_drained",
             "serving_device_idle_known_ms", "serving_device_idle_slack_ms")
    before = [stat_get(n) for n in names]
    a = engine._account
    mine = [a.dispatches, a.drained, a.idle_known_s, a.idle_slack_s]
    res = engine.generate(PROMPT, 4, timeout=120)
    _spans_after(1)
    d = [stat_get(n) - b for n, b in zip(names, before)]
    assert d[0] == a.dispatches - mine[0] == res["steps"] + 1
    assert d[1] == a.drained - mine[1] >= 1
    # (the spans and counters carry whole microseconds)
    assert d[2] == pytest.approx((a.idle_known_s - mine[2]) * 1e3,
                                 abs=1e-3 * d[0])
    assert d[3] == pytest.approx((a.idle_slack_s - mine[3]) * 1e3,
                                 abs=1e-3 * d[0])
    assert d[2] <= d[3]


def test_warmup_from_another_thread_books_nothing():
    """Warm-up runs the scheduler's programs from its caller's thread:
    the account hears of none of them."""
    pt.set_flags({"FLAGS_telemetry": True})
    gen = GenerationEngine(MODEL, num_slots=2, max_seq_len=64,
                           attn_impl="xla", seed=0, page_tokens=8,
                           prefix_reuse=False)
    try:
        telemetry.clear_spans()
        assert gen.warmup() > 0
        a = gen._account
        assert (a.dispatches, a.drained, a.wait_s) == (0, 0, 0.0)
        mine = [s for s in telemetry.get_spans()
                if s.name.startswith("generation/")]
        assert mine and all("drained" not in s.attrs
                            and "ready" not in s.attrs for s in mine)
    finally:
        gen.close()


def test_telemetry_off_probes_nothing_and_reads_no_thread_clock(
        engine, monkeypatch):
    pt.set_flags({"FLAGS_telemetry": False})
    try:
        engine.generate(PROMPT, 1, timeout=120)  # ends the idle wait span
        time.sleep(0.1)
        calls = {"ready": 0, "thread_time": 0}
        real_ready, real_tt = FetchHandle.ready, time.thread_time

        def ready(self):
            calls["ready"] += 1
            return real_ready(self)

        def thread_time():
            calls["thread_time"] += 1
            return real_tt()

        monkeypatch.setattr(FetchHandle, "ready", ready)
        monkeypatch.setattr(time, "thread_time", thread_time)
        a = engine._account
        mine = (a.dispatches, a.wait_s, stream_meter.totals())
        res = engine.generate(PROMPT, 4, timeout=120)
        time.sleep(0.1)
        assert len(res["tokens"]) == 4
        assert calls == {"ready": 0, "thread_time": 0}
        assert (a.dispatches, a.wait_s, stream_meter.totals()) == mine
        # ... and with it on, the same request does both
        pt.set_flags({"FLAGS_telemetry": True})
        engine.generate(PROMPT, 4, timeout=120)
        time.sleep(0.1)
        assert calls["ready"] >= 5 and calls["thread_time"] >= 10
    finally:
        pt.set_flags({"FLAGS_telemetry": True})


def test_phases_time_the_threads_cpu_inside_the_iterations(traced):
    """``cpu_ms`` on the iteration, on the phases that are a pass's host
    work (``decode_feeds``, ``book_tokens``, ``executor/step``) and on
    ``release``, whose wall time is a wait; a phase's CPU time lies
    inside the iteration's (one thread clock, nested reads).  The short
    phases carry none: a reading costs microseconds where the thread
    clock is a system call."""
    _, spans = traced
    timed = ("generation/decode_feeds", "generation/book_tokens",
             "generation/release", "executor/step")
    for name in ("generation/iteration",) + timed:
        found = _named(spans, name)
        assert found and all(s.attrs["cpu_ms"] >= 0.0 for s in found), name
    for name in ("generation/claim", "generation/publish",
                 "generation/decode_step", "generation/token_fetch",
                 "generation/prefill", "generation/prefill_fetch"):
        assert all("cpu_ms" not in s.attrs for s in _named(spans, name))
    for it in _named(spans, "generation/iteration"):
        inner = [s for s in spans if s.name in timed and s.tid == it.tid
                 and _inside(s, it)]
        # (each reading is rounded to a microsecond)
        assert sum(s.attrs["cpu_ms"] for s in inner) \
            <= it.attrs["cpu_ms"] + 1e-3 * (len(inner) + 1)


def test_span_cpu_ms_is_written_only_by_the_thread_that_began_it():
    import threading

    pt.set_flags({"FLAGS_telemetry": True})
    telemetry.clear_spans()
    with telemetry.trace_span("t/cpu", cpu=True) as mine:
        sum(range(20000))
    assert mine.attrs["cpu_ms"] > 0.0
    plain = telemetry.span_begin("t/plain")
    telemetry.span_end(plain)
    assert "cpu_ms" not in plain.attrs
    other = telemetry.span_begin("t/elsewhere", cpu=True, detached=True)
    t = threading.Thread(target=telemetry.span_end, args=(other,))
    t.start()
    t.join()
    assert other.end is not None and "cpu_ms" not in other.attrs


def _bench_reader(name):
    import importlib.util
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(bench, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_phases_leave_little_of_a_pass_unnamed(engine):
    """The benchmark's reader of an iteration's self time
    (``iter_unnamed_ms.*``) over real passes of the CPU engine: what no
    direct child span covers is the spans' own cost, under a tenth of a
    2 ms pass when nothing else runs; the median pass of twelve must
    leave under a fifth unnamed (a share of one clock's readings, and
    the median so that a pass the machine interrupted does not count)."""
    pt.set_flags({"FLAGS_telemetry": True})
    telemetry.clear_spans()
    engine.generate(PROMPT, 12, timeout=120)
    spans = _spans_after(12)    # eleven decode steps and the last settle
    reader = _bench_reader("iter_account")
    table = reader.passes(reader.scheduler_spans(spans))
    assert len(table) >= 12
    unnamed = reader.read({"spans": spans}, what="unnamed_ms")
    shares = sorted(reader.self_ms(it, inner) / ((it.end - it.start) * 1e3)
                    for it, inner in table)
    assert unnamed >= 0.0 and 0.0 <= shares[0]
    assert shares[len(shares) // 2] < 0.2
    # every phase of the README's table is a direct child
    direct = {path[0] for _, inner in table for path, _ in inner}
    assert {"generation/claim", "generation/decode_feeds",
            "generation/decode_step", "generation/book_tokens",
            "generation/release", "generation/publish",
            "generation/prefill"} <= direct


def test_stream_cpu_ms_sums_to_the_writers_accumulator():
    """Streamed tokens are metered by the process's stream writer (its
    thread CPU seconds and its seconds inside ``send``, added before a
    stream's end is signalled); every pass writes what was added since
    the pass before it, so the iterations' ``stream_cpu_ms`` and
    ``stream_write_ms`` sum to the accumulators' growth."""
    import json
    import threading

    pt.set_flags({"FLAGS_telemetry": True})
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        x = layers.data("x", [8])
        y = layers.fc(x, 8)
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    from paddle_tpu.inference import Predictor

    eng = ServingEngine(Predictor(main, ["x"], [y], scope=scope),
                        workers=1, warmup_shapes={"x": (8,)})
    gen = GenerationEngine(MODEL, num_slots=2, max_seq_len=64,
                           attn_impl="xla", seed=0,
                           page_tokens=8, prefix_reuse=False)
    eng.attach_generator(gen)
    gen.warmup()
    srv = ServingServer(eng).start()

    def stream(n):
        body = json.dumps({"prompt": PROMPT, "max_new_tokens": n,
                           "stream": True}).encode()
        req = urllib.request.Request(
            srv.url + "/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            lines = r.read().decode().splitlines()
        assert len(lines) == n + 1

    try:
        gen.generate(PROMPT, 1, timeout=120)    # a pass reads the totals
        time.sleep(0.1)
        telemetry.clear_spans()
        write0, cpu0 = stream_meter.totals()
        workers = [threading.Thread(target=stream, args=(9,))
                   for _ in range(2)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        write1, cpu1 = stream_meter.totals()
        # the streams are done: one more pass picks up what the last
        # streamed tokens added after their own pass had closed
        done = len(_named(telemetry.get_spans(), "generation/iteration"))
        gen.generate(PROMPT, 1, timeout=120)
        iters = _named(_spans_after(done + 1), "generation/iteration")
    finally:
        srv.close()
    assert cpu1 > cpu0 and write1 > write0      # 18 tokens were metered
    n = len(iters)
    assert sum(it.attrs["stream_cpu_ms"] for it in iters) \
        == pytest.approx((cpu1 - cpu0) * 1e3, abs=1e-3 * n)
    assert sum(it.attrs["stream_write_ms"] for it in iters) \
        == pytest.approx((write1 - write0) * 1e3, abs=1e-3 * n)
