"""Serving-layer tests: dynamic batching bit-exactness, predictor pool
throughput, admission control / overload shedding, SIGTERM drain, fault
matrix, and the HTTP front end.

The bit-exactness contract is the serving analog of the fault-matrix
resume tests: a caller must not be able to tell whether their request
rode a padded micro-batch, a partial deadline-triggered batch, or a
chunked oversized batch — against a one-at-a-time `Predictor.run`, at
every bucket boundary, to the accumulation order of one matmul
(`conftest.assert_logits_match`: XLA:CPU orders it by the batch shape).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
from conftest import assert_logits_match

import paddle_tpu as pt
from paddle_tpu import fault, layers, telemetry
from paddle_tpu.inference import Predictor
from paddle_tpu.monitor import stat_get
from paddle_tpu.serving import (OverloadedError, RequestFailed,
                                ServingEngine, batcher, serve)

from conftest import retry_flaky

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_faults():
    fault.reset()
    yield
    fault.reset()
    pt.set_flags({"FLAGS_fault_inject": "", "FLAGS_telemetry": True,
                  "FLAGS_metrics_dir": "", "FLAGS_trace_sample": 1.0,
                  "FLAGS_trace_tail_keep": 8, "FLAGS_tracez_recent": 32,
                  "FLAGS_serving_access_log": ""})


def _build_mlp(feat=6, hidden=16, classes=3, depth=1, seed=0):
    """Fresh in-process MLP predictor (own program + scope)."""
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    startup.random_seed = main.random_seed = seed
    with pt.program_guard(main, startup):
        x = layers.data("x", [feat])
        h = x
        for i in range(depth):
            h = layers.fc(h, hidden, act="relu", name=f"sv_fc{i}_{seed}")
        out = layers.fc(h, classes, name=f"sv_head_{seed}")
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    return Predictor(main, ["x"], [out], scope=scope)


@pytest.fixture(scope="module")
def small_model():
    """Shared small predictor + deterministic inputs + per-row reference
    outputs (module-scoped: compiled signatures are reused across
    tests)."""
    p = _build_mlp()
    rng = np.random.RandomState(0)
    xs = rng.rand(64, 6).astype("float32")
    return p, xs


# ---------------------------------------------------------------------------
# batcher (pure)
# ---------------------------------------------------------------------------

def test_bucket_policy():
    assert batcher.bucket_sizes(8) == (1, 2, 4, 8)
    assert batcher.bucket_sizes(6) == (1, 2, 4, 6)
    assert batcher.bucket_sizes(1) == (1,)
    assert batcher.bucket_for(3, (1, 2, 4, 8)) == 4
    assert batcher.bucket_for(8, (1, 2, 4, 8)) == 8
    assert batcher.bucket_for(9, (1, 2, 4, 8)) is None
    with pytest.raises(ValueError):
        batcher.bucket_sizes(0)


def test_pad_stack_split_roundtrip():
    rng = np.random.RandomState(1)
    reqs = [[rng.rand(n, 5).astype("float32"),
             rng.randint(0, 9, (n, 2)).astype("int64")]
            for n in (1, 3, 2)]
    padded, rows = batcher.pad_stack(reqs, 8)
    assert rows == 6
    assert padded[0].shape == (8, 5) and padded[1].shape == (8, 2)
    # pad rows replicate row 0 (in-domain, never zeros)
    np.testing.assert_array_equal(padded[0][6], padded[0][0])
    outs = [padded[0] * 2.0, padded[1] + 1]  # row-independent "model"
    split = batcher.split_rows(outs, [1, 3, 2])
    off = 0
    for req, got in zip(reqs, split):
        n = req[0].shape[0]
        np.testing.assert_array_equal(got[0], outs[0][off:off + n])
        assert got[0].shape[0] == n and got[1].shape[0] == n
        off += n
    with pytest.raises(ValueError):
        batcher.pad_stack(reqs, 4)  # 6 rows don't fit bucket 4


# ---------------------------------------------------------------------------
# bit-exactness across bucket boundaries
# ---------------------------------------------------------------------------

def test_batched_bit_exact_across_bucket_boundaries(small_model):
    """Engine outputs must be those of one-at-a-time
    Predictor.run for sizes 1, bucket-1, bucket, bucket+1 at every
    bucket, plus oversized (chunked) requests."""
    p, xs = small_model
    sizes = {1}
    for b in batcher.bucket_sizes(8):
        sizes.update({max(b - 1, 1), b, b + 1})
    with ServingEngine(p, workers=2, max_batch=8, max_delay_ms=2.0,
                       deadline_ms=60000) as eng:
        for n in sorted(sizes):
            feed = {"x": xs[:n]}
            got = eng.predict(feed, timeout=60)
            ref = p.run(feed)
            assert len(got) == len(ref)
            for g, r in zip(got, ref):
                assert_logits_match(g, r, f"size {n}")


def test_deadline_triggered_partial_batch_bit_exact(small_model):
    """Requests that can't fill a bucket dispatch padded when max_delay
    expires — and are still bit-exact."""
    p, xs = small_model
    with ServingEngine(p, workers=1, max_batch=8, max_delay_ms=10.0,
                       deadline_ms=60000) as eng:
        before = eng.stats()["counters"]["pad_rows"]
        # 3 single-row requests: pads to bucket 4, never reaches 8
        futs = [eng.submit({"x": xs[i:i + 1]}) for i in range(3)]
        ref = p.run({"x": xs[:3]})
        for i, f in enumerate(futs):
            out = f.result(60)
            for g, r in zip(out, ref):
                assert_logits_match(g, r[i:i + 1])
        stats = eng.stats()
        assert stats["counters"]["pad_rows"] > before  # really padded


def test_concurrent_submitters_get_batched(small_model):
    """Also the runtime lock-order sanitizer's serving leg
    (FLAGS_debug_lock_order semantics): the engine's locks are
    constructed under locksan, the full submit/dispatch/respond
    traffic runs order-checked, and the observed acquisition graph
    must stay acyclic — zero recorded inversions."""
    from paddle_tpu import locksan

    p, xs = small_model
    # an env-enabled session sanitizer (FLAGS_debug_lock_order=1) is
    # left exactly as found: no clearing its accumulated state, no
    # disabling it afterwards — this leg only asserts it recorded
    # nothing NEW
    was_enabled = locksan.enabled()
    before = locksan.violations()
    if not was_enabled:
        locksan.clear_violations()
        locksan.enable(raise_on_violation=False)
    try:
        with ServingEngine(p, workers=2, max_batch=8, max_delay_ms=5.0,
                           deadline_ms=60000) as eng:
            futs = [eng.submit({"x": xs[i:i + 1]}) for i in range(32)]
            ref = p.run({"x": xs[:32]})[0]
            for i, f in enumerate(futs):
                assert_logits_match(f.result(60)[0], ref[i:i + 1])
            stats = eng.stats()
            assert stats["counters"]["batches"] \
                < stats["counters"]["requests"]
            assert stats["counters"]["requests"] == 32
    finally:
        if not was_enabled:
            locksan.disable()
    assert locksan.violations() == ([] if not was_enabled else before)


def test_feed_validation(small_model):
    p, _xs = small_model
    with ServingEngine(p, workers=1, max_batch=4) as eng:
        with pytest.raises(ValueError, match="missing feed"):
            eng.submit({"y": np.zeros((1, 6), "float32")})
        with pytest.raises(ValueError, match="batch dim"):
            eng.submit({"x": np.float32(3.0)})


# ---------------------------------------------------------------------------
# throughput: batching + pool vs serial batch-1
# ---------------------------------------------------------------------------

@retry_flaky()
def test_throughput_2x_vs_serial_batch1():
    """The acceptance bar: >=2x closed-loop throughput vs serial
    batch-size-1 submission on a compute-bound model with 2+ workers.

    The model is weight-heavy (batch-1 inference is memory-bound on
    streaming the weights), so micro-batching amortizes exactly the
    cost serial submission pays per request.  Measured on this harness:
    ~2.5-9x; asserted >=2x, best of 3 attempts (shared CI boxes
    wander).  Documented in-suite flake on core-bound 2-core hosts
    (passes in isolation AND flakes ~50% on the pristine tree under
    suite load — PR 12/13 notes): one bounded retry via
    ``retry_flaky`` plus a load-aware skip guard (cores/loadavg) keep
    the suite signal trustworthy without masking a deterministic
    regression on healthy hosts."""
    lg = _load_loadgen()
    predictor, shapes = lg.build_synthetic(feat=256, hidden=2048, depth=4)
    make_feed = lg.feed_maker(shapes, rows=1)
    predictor.warmup({"x": (1, 256)})

    best = 0.0
    with ServingEngine(predictor.clone(), workers=2, max_batch=8,
                       max_delay_ms=2.0, queue_cap=4096,
                       deadline_ms=60000, warmup_shapes=shapes) as eng:
        for _attempt in range(3):
            t0 = time.perf_counter()
            n_serial = 32
            for i in range(n_serial):
                predictor.run(make_feed(i))
            serial_qps = n_serial / (time.perf_counter() - t0)

            rep = lg.run_closed_loop(eng, make_feed, n_requests=160,
                                     concurrency=16)
            assert rep["ok"] == 160 and rep["failed"] == 0
            best = max(best, rep["qps"] / serial_qps)
            if best >= 2.0:
                break
    if best < 2.0:
        # load-aware guard: with fewer usable cores than the 2 workers
        # + serial baseline + the rest of the suite need, the ratio
        # measures the scheduler's contention, not the engine's
        # batching win — skip loudly instead of flaking the suite
        cores = os.cpu_count() or 1
        try:
            load1 = os.getloadavg()[0]
        except OSError:
            load1 = 0.0
        if cores < 4 or load1 > cores:
            pytest.skip(f"core-bound host (cores={cores}, "
                        f"load1={load1:.1f}): throughput ratio "
                        f"{best:.2f}x is contention-bound — the test "
                        f"passes in isolation (documented in-suite "
                        f"flake, PR 12/13 notes)")
    assert best >= 2.0, f"batched throughput only {best:.2f}x serial"


def _load_loadgen():
    import importlib.util

    path = os.path.join(REPO, "tools", "serving_loadgen.py")
    spec = importlib.util.spec_from_file_location("serving_loadgen", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# admission control / overload
# ---------------------------------------------------------------------------

def test_bounded_queue_sheds_with_explicit_error(small_model):
    """A full queue sheds at submit() with OverloadedError(queue_full);
    admitted requests still complete."""
    p, xs = small_model
    eng = ServingEngine(p, workers=1, max_batch=4, max_delay_ms=1.0,
                        queue_cap=4, deadline_ms=60000, autostart=False)
    try:
        shed_before = stat_get("serving_requests_shed")
        futs = [eng.submit({"x": xs[i:i + 1]}) for i in range(4)]
        with pytest.raises(OverloadedError) as ei:
            eng.submit({"x": xs[:1]})
        assert ei.value.reason == "queue_full"
        assert stat_get("serving_requests_shed") == shed_before + 1
        eng.start()  # workers drain the 4 admitted requests
        ref = p.run({"x": xs[:4]})[0]
        for i, f in enumerate(futs):
            assert_logits_match(f.result(60)[0], ref[i:i + 1])
        assert eng.stats()["counters"]["shed"] == 1
    finally:
        eng.close()


def test_deadline_shed_bounds_admission_latency(small_model):
    """Requests older than the deadline are refused, not served stale:
    every SERVED request's queue wait is bounded by deadline+delay, and
    expired ones get an explicit OverloadedError(deadline)."""
    p, xs = small_model
    deadline_ms, delay_ms = 80.0, 2.0
    eng = ServingEngine(p, workers=1, max_batch=4, max_delay_ms=delay_ms,
                        queue_cap=64, deadline_ms=deadline_ms,
                        autostart=False)
    try:
        stale = [eng.submit({"x": xs[i:i + 1]}) for i in range(3)]
        time.sleep(2.5 * deadline_ms / 1e3)  # outlive the deadline
        fresh = [eng.submit({"x": xs[i:i + 1]}) for i in range(3, 6)]
        eng.start()
        for f in stale:
            with pytest.raises(OverloadedError, match="deadline"):
                f.result(60)
        ref = p.run({"x": xs[3:6]})[0]
        for i, f in enumerate(fresh):
            assert_logits_match(f.result(60)[0], ref[i:i + 1])
        waits = eng.stats()["queue_wait_ms"]
        # p99 admission latency bounded: nothing served waited past the
        # deadline (+ batch-formation delay + scheduling slack)
        assert waits["count"] == 3
        assert waits["max"] <= deadline_ms + delay_ms + 150.0
        assert eng.stats()["counters"]["shed"] == 3
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# fault matrix
# ---------------------------------------------------------------------------

def test_serve_batch_fail_hits_only_that_batch(small_model):
    """serve_batch:fail@2 — exactly the second batch's requests error,
    the engine keeps serving, serving_batch_failures increments."""
    p, xs = small_model
    fault.configure("serve_batch:fail@2")
    fails_before = stat_get("serving_batch_failures")
    with ServingEngine(p, workers=1, max_batch=4, max_delay_ms=1.0,
                       deadline_ms=60000) as eng:
        ref = p.run({"x": xs[:4]})[0]
        # full-bucket requests -> one batch each, in submission order
        outs = []
        for k in range(3):
            outs.append(eng.submit({"x": xs[:4]}))
            outs[-1]._event.wait(60)  # serialize -> deterministic batches
        ok0 = outs[0].result(60)[0]
        assert_logits_match(ok0, ref)
        with pytest.raises(RequestFailed, match="injected"):
            outs[1].result(60)
        assert_logits_match(outs[2].result(60)[0], ref)  # still serving
        assert eng.stats()["counters"]["batch_failures"] == 1
    assert stat_get("serving_batch_failures") == fails_before + 1


def test_serve_request_fault_sheds_at_admission(small_model):
    p, xs = small_model
    fault.configure("serve_request:shed@1,serve_request:fail@2")
    with ServingEngine(p, workers=1, max_batch=4) as eng:
        with pytest.raises(OverloadedError, match="injected"):
            eng.submit({"x": xs[:1]})
        # 'fail' stays inside the serving error taxonomy (no raw OSError)
        with pytest.raises(RequestFailed, match="injected"):
            eng.submit({"x": xs[:1]})
        # next request is admitted and served
        assert eng.predict({"x": xs[:1]}, timeout=60) is not None
        n = eng.stats()["counters"]
        assert n["requests"] == 3 and n["served"] == 1 and n["shed"] == 1


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------

def test_sigterm_drains_in_flight_then_rejects(small_model):
    p, xs = small_model
    eng = ServingEngine(p, workers=2, max_batch=4, max_delay_ms=2.0,
                        deadline_ms=60000)
    eng.install_sigterm()
    try:
        futs = [eng.submit({"x": xs[i:i + 1]}) for i in range(12)]
        os.kill(os.getpid(), signal.SIGTERM)
        ref = p.run({"x": xs[:12]})[0]
        # every in-flight request completes with a real answer
        for i, f in enumerate(futs):
            assert_logits_match(f.result(60)[0], ref[i:i + 1])
        # drain runs on a background thread; wait for workers to exit
        deadline = time.monotonic() + 30
        while any(t.is_alive() for t in eng._threads):
            assert time.monotonic() < deadline, "drain did not finish"
            time.sleep(0.01)
        with pytest.raises(OverloadedError, match="draining"):
            eng.submit({"x": xs[:1]})
    finally:
        eng.close()
        signal.signal(signal.SIGTERM, signal.SIG_DFL)


# ---------------------------------------------------------------------------
# request-scoped tracing
# ---------------------------------------------------------------------------

def test_request_trace_is_one_trace_across_threads(small_model):
    """The tentpole contract: one request = one trace_id, with
    admit/queue_wait/predict/respond child spans under the
    serving/request root, crossing the admission thread → dispatch
    thread hop; the batch span links the request trace."""
    p, xs = small_model
    telemetry.clear_spans()
    with ServingEngine(p, workers=1, max_batch=4, max_delay_ms=1.0,
                       deadline_ms=60000) as eng:
        fut = eng.submit({"x": xs[:2]})
        fut.result(60)
        tid = fut.trace["trace_id"]
        assert fut.trace["status"] == "ok" and fut.trace["sampled"]
        spans = [s for s in telemetry.get_spans() if s.trace_id == tid]
        by = {s.name: s for s in spans}
        assert {"serving/request", "serving/admit", "serving/queue_wait",
                "serving/predict", "serving/respond"} <= set(by)
        root = by["serving/request"]
        for name in ("serving/admit", "serving/queue_wait",
                     "serving/predict", "serving/respond"):
            assert by[name].parent_id == root.span_id, name
        # the trace crosses >= 2 threads: admit on the submitter,
        # predict/respond on the dispatch worker; queue_wait BEGAN on
        # the submitter and ENDED on the worker
        assert by["serving/admit"].tid != by["serving/predict"].tid
        assert len({s.tid for s in spans}) >= 2
        # batch span: its own trace, fan-in link to this request
        batches = [s for s in telemetry.get_spans()
                   if s.name == "serving/batch"]
        linked = [s for s in batches
                  if any(l.trace_id == tid for l in s.links)]
        assert linked and linked[0].trace_id != tid
        # phases + exemplar plumbing
        assert fut.trace["phases"]["queue_wait_ms"] >= 0
        assert fut.trace["phases"]["predict_ms"] > 0
        # the engine-local latency histogram holds the request's trace
        # id as an exemplar (the global one shares its top-5 window
        # with every other engine in the process)
        ex = eng.stats()["request_ms"]["exemplars"]
        assert any(e["trace_id"] == tid for e in ex)
        # /tracez store has the full span tree
        tz = eng.tracez()
        rec = [t for t in tz["recent_sampled"]
               if t["trace_id"] == tid][0]
        assert len({s["tid"] for s in rec["spans"]}) >= 2


def test_head_sampling_and_tail_capture(small_model):
    """FLAGS_trace_sample=0.25 records every 4th request's span tree;
    FLAGS_trace_sample=0 records none — but the slowest-N tail still
    captures phase records with trace ids."""
    p, xs = small_model
    pt.set_flags({"FLAGS_trace_sample": 0.25})
    with ServingEngine(p, workers=1, max_batch=4, max_delay_ms=1.0,
                       deadline_ms=60000) as eng:
        for i in range(8):
            eng.predict({"x": xs[:1]}, timeout=60)
        n = eng.stats()["counters"]
        assert n["sampled"] == 2  # deterministic: every 4th of 8
        tz = eng.tracez()
        assert len(tz["recent_sampled"]) == 2
        assert tz["sample_rate"] == 0.25

    pt.set_flags({"FLAGS_trace_sample": 0.0, "FLAGS_trace_tail_keep": 3})
    telemetry.clear_spans()
    with ServingEngine(p, workers=1, max_batch=4, max_delay_ms=1.0,
                       deadline_ms=60000) as eng:
        futs = [eng.submit({"x": xs[:1]}) for i in range(6)]
        for f in futs:
            f.result(60)
        assert eng.stats()["counters"]["sampled"] == 0
        assert not [s for s in telemetry.get_spans()
                    if s.name == "serving/request"]
        tz = eng.tracez()
        assert tz["recent_sampled"] == []
        # tail capture is sampling-independent: slowest 3 kept, with
        # trace ids and phase breakdowns, slowest first
        assert len(tz["slowest"]) == 3
        durs = [t["duration_ms"] for t in tz["slowest"]]
        assert durs == sorted(durs, reverse=True)
        for t in tz["slowest"]:
            assert t["trace_id"] and not t["sampled"]
            assert t["phases"]["queue_wait_ms"] is not None


def test_queue_depth_recorded_at_enqueue_with_high_watermark(small_model):
    """The satellite contract: serving_queue_depth updates at enqueue
    time and serving_queue_depth_peak holds the burst high watermark
    even after the queue drains."""
    p, xs = small_model
    eng = ServingEngine(p, workers=1, max_batch=4, max_delay_ms=1.0,
                        queue_cap=64, deadline_ms=60000, autostart=False)
    try:
        for i in range(5):
            eng.submit({"x": xs[i:i + 1]})
        # workers never started: the only updates were enqueue-time
        assert telemetry.metrics.gauge("serving_queue_depth").get() == 5
        assert eng.stats()["queue_depth"] == 5
        eng.start()
        deadline = time.monotonic() + 60
        while eng.stats()["queue_depth"] > 0:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        stats = eng.stats()
        assert stats["queue_depth_peak"] >= 5  # survives the drain
        assert telemetry.metrics.gauge(
            "serving_queue_depth_peak").get() >= 5
    finally:
        eng.close()


def test_serving_telemetry_off_constant_time(small_model):
    """FLAGS_telemetry=0 serving-path contract (the serving analog of
    test_telemetry_off_emits_nothing): requests serve fine, zero spans
    are recorded, the global latency histograms see nothing, no trace
    records or access log exist, and /metrics //tracez degrade to 503
    while /statusz and /predict stay up."""
    p, xs = small_model
    pt.set_flags({"FLAGS_telemetry": 0})
    telemetry.clear_spans()
    h0 = telemetry.metrics.histogram("serving_request_ms").summary()
    eng = ServingEngine(p, workers=1, max_batch=4, max_delay_ms=1.0,
                        deadline_ms=60000)
    srv = serve(eng)
    try:
        code, doc = _post(srv.url + "/predict",
                          {"inputs": {"x": xs[:2].tolist()}})
        assert code == 200 and doc["trace_id"] is None
        fut = eng.submit({"x": xs[:1]})
        fut.result(60)
        assert fut.trace is None
        assert telemetry.get_spans() == []
        h1 = telemetry.metrics.histogram("serving_request_ms").summary()
        assert h1["count"] == h0["count"]
        assert eng.tracez()["recent_sampled"] == []
        assert eng.tracez()["slowest"] == []
        assert srv.access_log.path() is None

        for path in ("/metrics", "/tracez"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + path, timeout=30)
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["error"] \
                == "telemetry disabled"
        with urllib.request.urlopen(srv.url + "/statusz",
                                    timeout=30) as r:
            st = json.loads(r.read())
        assert r.status == 200
        assert st["telemetry"]["enabled"] is False
        assert st["engine"]["stats"]["counters"]["requests"] >= 2
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# HTTP front end
# ---------------------------------------------------------------------------

def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_http_predict_healthz_and_errors(small_model):
    p, xs = small_model
    eng = ServingEngine(p, workers=1, max_batch=4, max_delay_ms=2.0,
                        deadline_ms=60000)
    srv = serve(eng)
    try:
        code, doc = _post(srv.url + "/predict",
                          {"inputs": {"x": xs[:3].tolist()}})
        assert code == 200
        ref = p.run({"x": xs[:3]})
        got = np.asarray(doc["outputs"][0], dtype=ref[0].dtype)
        assert_logits_match(got, ref[0])
        assert doc["shapes"] == [list(r.shape) for r in ref]

        with urllib.request.urlopen(srv.url + "/healthz", timeout=30) as r:
            hz = json.loads(r.read())
        assert r.status == 200 and hz["status"] == "ok"
        assert hz["serving"]["counters"]["requests"] >= 1
        assert hz["pid"] == os.getpid()

        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.url + "/predict", {"nope": 1})
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.url + "/predict", {"inputs": {"y": [[1.0]]}})
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/nothere", timeout=30)
        assert ei.value.code == 404

        # keep-alive: a 404'd POST must drain its body so the SAME
        # connection still serves the next request cleanly
        import http.client
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=30)
        body = json.dumps({"inputs": {"x": xs[:1].tolist()}})
        conn.request("POST", "/wrong", body=body)
        assert conn.getresponse().read() and True  # consume 404
        conn.request("POST", "/predict", body=body)
        r2 = conn.getresponse()
        assert r2.status == 200 and json.loads(r2.read())["outputs"]
        conn.close()

        # drained engine -> explicit 503 backpressure, healthz flips
        eng.close()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.url + "/predict", {"inputs": {"x": xs[:1].tolist()}})
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["reason"] == "draining"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/healthz", timeout=30)
        assert ei.value.code == 503
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Predictor satellites: thread safety + warmup
# ---------------------------------------------------------------------------

def test_predictor_run_thread_safe_4_concurrent_callers(small_model):
    """4 threads hammering ONE predictor with a COLD compile cache
    across mixed shapes (racing the per-shape compile path): no
    exceptions, no duplicate/torn cache entries, and every racing
    result equals a post-race rerun of the (now settled) executable."""
    _p, xs = small_model
    q = _build_mlp(seed=1)  # cold cache: the race covers compilation
    sizes = (1, 2, 3, 5)
    results, errors = {}, []

    def hammer(tid):
        try:
            for i in range(12):
                n = sizes[(tid + i) % len(sizes)]
                results[(tid, i, n)] = q.run({"x": xs[:n]})[0]
        except Exception as e:  # noqa: BLE001 — surfaced to the assert
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors
    assert len(q._cache) == len(sizes)  # one entry per signature
    refs = {n: q.run({"x": xs[:n]})[0] for n in sizes}
    for (tid, i, n), out in results.items():
        assert np.array_equal(out, refs[n]), \
            f"thread {tid} iter {i}: result diverged at size {n}"


def test_predictor_warmup_precompiles(small_model):
    _p, xs = small_model
    q = _build_mlp(seed=2)
    assert q.warmup([{"x": (1, 6)}, {"x": (4, 6)}]) == 2
    assert len(q._cache) == 2
    assert q.warmup({"x": (4, 6)}) == 0  # cached: free
    # warmed signature serves with no new compile
    out4 = q.run({"x": xs[:4]})[0]
    assert len(q._cache) == 2
    # a warm executable agrees row-for-row with a cold-compiled one
    out2 = q.run({"x": xs[:2]})[0]  # (2, 6): compiled on demand
    assert len(q._cache) == 3
    assert_logits_match(out4[:2], out2)


# ---------------------------------------------------------------------------
# loadgen CLI
# ---------------------------------------------------------------------------

def test_loadgen_open_loop_against_live_http_server(tmp_path):
    """E2E satellite: serving_loadgen open-loop mode over real sockets
    against a live ThreadingHTTPServer — the JSON report carries
    qps/p99/shed, and /metrics agrees with the access log on request
    counts (every POST /predict = one counter bump = one log line)."""
    lg = _load_loadgen()
    mdir = str(tmp_path / "serve_metrics")
    pt.set_flags({"FLAGS_metrics_dir": mdir,
                  "FLAGS_metrics_interval": 0.0})
    predictor, shapes = lg.build_synthetic(feat=8, hidden=16, depth=1)
    eng = ServingEngine(predictor, workers=2, max_batch=4,
                        max_delay_ms=2.0, deadline_ms=60000,
                        warmup_shapes=shapes)
    srv = serve(eng)
    try:
        def scrape_http_count():
            with urllib.request.urlopen(srv.url + "/metrics",
                                        timeout=30) as r:
                text = r.read().decode()
            line = [l for l in text.splitlines()
                    if l.startswith("paddle_tpu_serving_http_requests ")]
            return int(line[0].split()[1]) if line else 0, text

        before, _ = scrape_http_count()
        make_feed = lg.feed_maker(shapes, rows=1)
        rep = lg.run_open_loop_http(srv.url, make_feed, qps=120,
                                    duration_s=0.5)
        assert rep["mode"] == "open" and rep["url"] == srv.url
        assert rep["requests"] > 0 and rep["ok"] > 0
        assert rep["failed"] == 0
        assert rep["qps"] > 0 and rep["target_qps"] == 120
        assert {"p50", "p95", "p99"} <= set(rep["latency_ms"])
        assert rep["shed"] == 0 and rep["shed_rate"] == 0.0
        # the report embeds a /statusz snapshot instead of engine stats
        assert rep["engine"] is None
        assert rep["statusz"]["engine"]["stats"]["counters"]["served"] \
            >= rep["ok"]

        after, text = scrape_http_count()
        access = os.path.join(mdir, "access.jsonl")
        lines = [json.loads(l) for l in open(access) if l.strip()]
        # /metrics and the access log agree on request counts
        assert after - before == rep["requests"] == len(lines)
        assert all(l["status"] == 200 and l["trace_id"]
                   and l["phases"]["queue_wait_ms"] is not None
                   for l in lines)
        # the live scrape includes the serving stats and is strictly
        # valid Prometheus exposition
        assert "paddle_tpu_serving_request_ms_count" in text
        assert "paddle_tpu_serving_queue_depth_peak" in text
        csc = _load_tool("check_stat_catalog")
        assert csc.validate_exposition(text) == []

        # acceptance: a complete request trace crossing >= 2 threads
        # under one trace_id, visible in /tracez ...
        with urllib.request.urlopen(srv.url + "/tracez",
                                    timeout=30) as r:
            tz = json.loads(r.read())
        recs = [t for t in tz["recent_sampled"] if t.get("spans")]
        assert recs
        rec = recs[-1]
        names = {s["name"] for s in rec["spans"]}
        assert {"serving/request", "serving/admit", "serving/queue_wait",
                "serving/predict", "serving/respond"} <= names
        assert len({s["tid"] for s in rec["spans"]}) >= 2
        srv.close()  # flush writes trace.json into mdir

        # ... and in the merged Perfetto export (trainer dir + serving
        # dir -> distinct track groups, trace_id preserved)
        other = str(tmp_path / "trainer_metrics")
        telemetry.export_chrome_trace(
            os.path.join(other, "trace.json"),
            spans=[s for s in telemetry.get_spans()
                   if s.name.startswith("executor/")])
        out = str(tmp_path / "merged.json")
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "trace_export.py"),
             "--metrics-dir", other, "--metrics-dir", mdir, out],
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        with open(out) as f:
            evs = json.load(f)["traceEvents"]
        tid = rec["trace_id"]
        merged = [e for e in evs
                  if e.get("args", {}).get("trace_id") == tid]
        assert {e["name"] for e in merged} >= {"serving/request",
                                               "serving/predict"}
        assert len({e["tid"] for e in merged}) >= 2
    finally:
        srv.close()
        pt.set_flags({"FLAGS_metrics_dir": "",
                      "FLAGS_metrics_interval": 10.0})


def _load_tool(name):
    import importlib.util

    path = os.path.join(REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_loadgen_cli(tmp_path):
    out = str(tmp_path / "report.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serving_loadgen.py"),
         "--synthetic", "--feat", "8", "--hidden", "16", "--depth", "1",
         "--mode", "both", "--requests", "24", "--concurrency", "4",
         "--qps", "120", "--duration", "0.4", "--workers", "2",
         "--max-batch", "4", "--out", out],
        capture_output=True, text=True, timeout=240, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(open(out).read())
    assert report["mode"] == "both"
    for mode in ("closed", "open"):
        leg = report[mode]
        assert leg["ok"] > 0 and leg["failed"] == 0
        assert {"p50", "p95", "p99"} <= set(leg["latency_ms"])
        assert "batch_fill_pct" in leg["engine"]
    assert report["closed"]["ok"] == 24
