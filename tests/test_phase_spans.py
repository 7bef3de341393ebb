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
        assert set(it.attrs) == {"active", "claimed", "queued", "cpu_ms"}
    for i, it in enumerate(decoding):
        first, last = i == 0, i == len(decoding) - 1
        kids = sorted(_children(spans, it), key=lambda s: s.start)
        assert [k.name for k in kids if k.name != "generation/publish"] \
            == ["generation/claim", "generation/decode_feeds",
                "generation/decode_step"] \
            + ([] if first else ["generation/book_tokens"])
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
    assert [f.attrs for f in feeds] == [{"active": 1}] * 4 + [{"active": 0}]
    assert book[0].attrs == {"tokens": 1, "finished": 0}
    assert book[-1].attrs == {"tokens": 1, "finished": 1}
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
    assert prefill.attrs == {"tokens": len(PROMPT), "bucket": 16,
                             "slot": prefill.attrs["slot"]}
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

    # compile appears twice on a miss: the jit build and the AOT compile
    assert [n for n in phases(first) if n != "executor/compile"] \
        == EXECUTOR_PHASES + ["executor/fetch"]
    assert "executor/compile" in phases(first)
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
