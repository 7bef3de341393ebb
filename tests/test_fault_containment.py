"""Serving fault-containment matrix: poison-request bisection,
end-to-end deadline propagation, hung-actor watchdogs, and the fleet
chaos harness.

Three tiers:

* engine-level (in-process): bisection isolates exactly the poisoned
  request(s) while every rider is served what one-at-a-time
  ``Predictor.run`` gives it (``conftest.assert_logits_match``: a
  batch and its bisected half are two batch shapes of one matmul — the
  standing serving invariant), deadline budgets shed hopeless
  requests at the queue, the stuck-worker watchdog flips
  ``/healthz`` to degraded;
* tier-to-tier (in-process servers + router): the
  ``X-PaddleTPU-Deadline-Ms`` header mints/decrements/sheds across
  the hop, ``Retry-After`` rides every backpressure 503, a hung
  replica costs one bounded forward (timeout → health strike → retry
  → 504 only when no alternate exists);
* fleet (subprocess replicas): a SIGSTOP'd replica — PID alive,
  invisible to exit-code monitoring — is ejected by the router,
  SIGKILLed by the supervisor's liveness deadline, and respawned; the
  chaos harness (tools/chaos.py) runs crash+hang+slow+poison against
  a 3-replica fleet under load with zero collateral failures, plus the
  paged-generation poison scenario (a poisoned prompt sharing a cached
  prefix is isolated without evicting or corrupting the shared pages).
"""
import importlib.util
import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest
from conftest import assert_logits_match

import paddle_tpu as pt
from paddle_tpu import fault, layers
from paddle_tpu.inference import Predictor
from paddle_tpu.monitor import stat_get
from paddle_tpu.serving import (FleetSupervisor, OverloadedError,
                                RequestFailed, Router, RouterServer,
                                ServingEngine, serve)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_containment_tests",
        os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lg = _load_tool("serving_loadgen")


@pytest.fixture(autouse=True)
def _reset_faults_and_flags():
    fault.reset()
    yield
    fault.reset()
    pt.set_flags({"FLAGS_fault_inject": "", "FLAGS_telemetry": True,
                  "FLAGS_serving_poison_value": "",
                  "FLAGS_serving_bisect": True,
                  "FLAGS_serving_worker_stuck_ms": 10000.0,
                  "FLAGS_router_default_deadline_ms": 0.0,
                  "FLAGS_router_forward_timeout_ms": 0.0})


def _build_mlp(feat=6, hidden=16, classes=3, depth=1, seed=0):
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    startup.random_seed = main.random_seed = seed
    with pt.program_guard(main, startup):
        x = layers.data("x", [feat])
        h = x
        for i in range(depth):
            h = layers.fc(h, hidden, act="relu", name=f"fc_fc{i}_{seed}")
        out = layers.fc(h, classes, name=f"fc_head_{seed}")
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    return Predictor(main, ["x"], [out], scope=scope)


POISON = 1e30


def _poisoned_rows(p, poison_idx, n=8, feat=6, seed=1):
    """n single-row feeds, the ones at poison_idx carrying the
    sentinel; returns (rows, per-row reference outputs for the clean
    ones — computed BEFORE the flag is set, one at a time)."""
    rng = np.random.RandomState(seed)
    xs = rng.rand(n, feat).astype("float32")
    refs = {i: p.run({"x": xs[i:i + 1]})[0] for i in range(n)
            if i not in poison_idx}
    for i in poison_idx:
        xs[i, 0] = POISON
    return xs, refs


def _run_bisection(p, eng, xs, poison_idx):
    """Submit every row as its own request against a stopped engine,
    then start it (one deterministic full batch); returns
    {idx: result-or-RequestFailed}."""
    futs = [eng.submit({"x": xs[i:i + 1]}) for i in range(len(xs))]
    eng.start()
    out = {}
    for i, f in enumerate(futs):
        try:
            out[i] = f.result(60)[0]
        except RequestFailed as e:
            out[i] = e
    return out


# ---------------------------------------------------------------------------
# poison bisection (engine level)
# ---------------------------------------------------------------------------

def test_bisection_isolates_one_poison_row_in_batch_of_8():
    """1 poison row in a batch of 8 → exactly 1 RequestFailed, the 7
    riders answer bit-exact; counters record the bisection."""
    p = _build_mlp(seed=11)
    xs, refs = _poisoned_rows(p, {3})
    pt.set_flags({"FLAGS_serving_poison_value": str(POISON)})
    bis_before = stat_get("serving_batch_bisections")
    with ServingEngine(p, workers=1, max_batch=8, max_delay_ms=50.0,
                       deadline_ms=60000, autostart=False) as eng:
        out = _run_bisection(p, eng, xs, {3})
        assert isinstance(out[3], RequestFailed)
        assert "isolated by bisection" in str(out[3])
        assert "Poisoned" in str(out[3])
        for i, ref in refs.items():
            assert_logits_match(out[i], ref, f"rider row {i}")
        n = eng.stats()["counters"]
        assert n["served"] == 7 and n["poison_rows"] == 1
        assert n["bisections"] == 1 and n["batch_failures"] == 1
    assert stat_get("serving_batch_bisections") == bis_before + 1


def test_bisection_isolates_two_poison_rows():
    """2 poison rows → exactly those 2 fail, 6 riders bit-exact."""
    p = _build_mlp(seed=12)
    xs, refs = _poisoned_rows(p, {1, 6})
    pt.set_flags({"FLAGS_serving_poison_value": str(POISON)})
    with ServingEngine(p, workers=1, max_batch=8, max_delay_ms=50.0,
                       deadline_ms=60000, autostart=False) as eng:
        out = _run_bisection(p, eng, xs, {1, 6})
        for i in (1, 6):
            assert isinstance(out[i], RequestFailed), out[i]
        for i, ref in refs.items():
            assert_logits_match(out[i], ref, f"rider row {i}")
        n = eng.stats()["counters"]
        assert n["served"] == 6 and n["poison_rows"] == 2


def test_bisection_in_deadline_triggered_partial_batch():
    """Poison in a partial (non-bucket-full) batch: the live engine
    dispatches 3 requests on the max_delay trigger; only the poisoned
    one fails."""
    p = _build_mlp(seed=13)
    xs, refs = _poisoned_rows(p, {1}, n=3)
    pt.set_flags({"FLAGS_serving_poison_value": str(POISON)})
    with ServingEngine(p, workers=1, max_batch=8, max_delay_ms=30.0,
                       deadline_ms=60000) as eng:
        futs = [eng.submit({"x": xs[i:i + 1]}) for i in range(3)]
        with pytest.raises(RequestFailed):
            futs[1].result(60)
        for i in (0, 2):
            assert_logits_match(futs[i].result(60)[0], refs[i],
                                f"rider row {i}")


def test_bisection_disabled_fails_the_whole_batch():
    """FLAGS_serving_bisect=0 restores the old containment: every
    rider in the poisoned batch errors."""
    p = _build_mlp(seed=14)
    xs, _refs = _poisoned_rows(p, {0}, n=4)
    pt.set_flags({"FLAGS_serving_poison_value": str(POISON),
                  "FLAGS_serving_bisect": 0})
    with ServingEngine(p, workers=1, max_batch=4, max_delay_ms=50.0,
                       deadline_ms=60000, autostart=False) as eng:
        out = _run_bisection(p, eng, xs, {0})
        assert all(isinstance(v, RequestFailed) for v in out.values())
        assert eng.stats()["counters"]["bisections"] == 0


def test_bisection_containment_in_replica_group_engine():
    """The sharded front end inherits the same containment: a poison
    row in a ReplicaGroupEngine batch fails alone, riders bit-exact
    vs the UNSHARDED predictor."""
    from paddle_tpu.serving import ReplicaGroupEngine

    p = _build_mlp(seed=15)
    xs, refs = _poisoned_rows(p, {2}, n=6)
    pt.set_flags({"FLAGS_serving_poison_value": str(POISON)})
    eng = ReplicaGroupEngine(p, groups=2, mp=1, ep=1, max_batch=8,
                             max_delay_ms=30.0, deadline_ms=60000,
                             autostart=False)
    try:
        out = _run_bisection(p, eng, xs, {2})
        assert isinstance(out[2], RequestFailed)
        for i, ref in refs.items():
            assert_logits_match(out[i], ref, f"rider row {i}")
        assert eng.stats()["counters"]["poison_rows"] == 1
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# generation containment: poisoned prompts, decode-step failures
# ---------------------------------------------------------------------------

GEN_MODEL = dict(vocab_size=32, hidden=16, num_layers=1, num_heads=2,
                 intermediate=32)


@pytest.fixture(scope="module")
def gen_engine():
    from paddle_tpu.serving import GenerationEngine

    eng = GenerationEngine(GEN_MODEL, num_slots=2, max_seq_len=32,
                           max_new_tokens=4, deadline_ms=60000)
    try:
        yield eng
    finally:
        eng.close()


def test_poison_prompt_in_prefill_bucket_is_isolated(gen_engine):
    """A poisoned prompt fails ITS prefill (RequestFailed) while
    prompts sharing the bucket/grid keep generating."""
    pt.set_flags({"FLAGS_serving_poison_value": "29"})
    f_ok1 = gen_engine.submit([1, 2, 3])
    f_poison = gen_engine.submit([4, 29, 5])
    f_ok2 = gen_engine.submit([6, 7])
    assert f_ok1.result(120)["tokens"]
    with pytest.raises(RequestFailed, match="[Pp]oison"):
        f_poison.result(120)
    assert f_ok2.result(120)["tokens"]


def test_decode_step_failure_fails_active_but_not_scheduler(gen_engine):
    """decode_step:fail@N — the active request(s) fail with their
    cache state unknowable; the next request prefills into a clean
    slot and the scheduler keeps serving."""
    # fault.configure resets the site's hit counter, so @2 is the
    # second decode step from here — inside fa's 8-token budget
    fault.configure("decode_step:fail@2")
    fa = gen_engine.submit([1, 2, 3], max_new_tokens=8)
    with pytest.raises(RequestFailed, match="decode step failed"):
        fa.result(120)
    fault.configure("")
    fb = gen_engine.submit([4, 5], max_new_tokens=3)
    assert fb.result(120)["tokens"]
    assert gen_engine.stats()["counters"]["failed"] >= 1


def test_generation_deadline_budget_sheds_at_queue(gen_engine):
    with pytest.raises(OverloadedError) as ei:
        gen_engine.submit([1, 2], deadline_ms=0)
    assert ei.value.reason == "deadline"


# ---------------------------------------------------------------------------
# end-to-end deadlines + Retry-After (engine + HTTP + router hop)
# ---------------------------------------------------------------------------

def test_engine_deadline_budget_sheds_hopeless_and_queued():
    p = _build_mlp(seed=16)
    x = np.random.rand(1, 6).astype("float32")
    shed_before = stat_get("requests_shed_deadline")
    eng = ServingEngine(p, workers=1, max_batch=4, deadline_ms=60000,
                        autostart=False)
    try:
        # spent budget: shed at submit, never queued
        with pytest.raises(OverloadedError) as ei:
            eng.submit({"x": x}, deadline_ms=0)
        assert ei.value.reason == "deadline"
        # tight budget + a stopped engine: shed at pickup
        fut = eng.submit({"x": x}, deadline_ms=50)
        time.sleep(0.15)
        eng.start()
        with pytest.raises(OverloadedError, match="deadline"):
            fut.result(30)
        # a generous budget serves normally
        assert eng.predict({"x": x}, timeout=60) is not None
        assert eng.stats()["counters"]["shed_deadline"] == 2
    finally:
        eng.close()
    assert stat_get("requests_shed_deadline") == shed_before + 2


def _post_raw(url, body=b'{"inputs": {"x": [[0.1,0.2,0.3,0.4,0.5,0.6]]}}',
              headers=None, timeout=30.0):
    """POST returning (status, parsed_body, headers) — errors too."""
    req = urllib.request.Request(url + "/predict", data=body,
                                 headers={"Content-Type":
                                          "application/json",
                                          **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_http_deadline_header_and_retry_after_on_503():
    p = _build_mlp(seed=17)
    eng = ServingEngine(p, workers=1, max_batch=4, queue_cap=1,
                        deadline_ms=60000, autostart=False)
    srv = serve(eng)
    try:
        # spent deadline header → 503 deadline + Retry-After
        code, body, headers = _post_raw(
            srv.url, headers={"X-PaddleTPU-Deadline-Ms": "0"})
        assert code == 503 and body["reason"] == "deadline"
        assert int(headers["Retry-After"]) >= 1
        assert body["retry_after_s"] > 0
        # full queue → 503 queue_full + Retry-After
        eng.submit({"x": np.random.rand(1, 6).astype("float32")})
        code, body, headers = _post_raw(srv.url)
        assert code == 503 and body["reason"] == "queue_full"
        assert int(headers["Retry-After"]) >= 1
        # a generous budget serves once the engine runs
        eng.start()
        code, body, _ = _post_raw(
            srv.url, headers={"X-PaddleTPU-Deadline-Ms": "60000"})
        assert code == 200 and body["outputs"]
    finally:
        srv.close()


class _CaptureReplica(BaseHTTPRequestHandler):
    """Fake always-healthy replica that records forwarded headers."""

    protocol_version = "HTTP/1.1"
    seen = None          # class attr: list of header dicts
    predict_sleep_s = 0.0

    def log_message(self, *a):
        pass

    def _send(self, code, payload):
        body = json.dumps(payload).encode()
        try:
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        except OSError:
            pass  # ok: the router timed out and closed the socket —
            # exactly the hang-containment behavior under test

    def do_GET(self):
        self._send(200, {"status": "ok", "ready": True,
                         "serving": {"queue_depth": 0,
                                     "inflight_rows": 0,
                                     "queue_cap": 64}})

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0) or 0)
        self.rfile.read(n)
        # lowercase keys: urllib normalizes header casing on the wire
        type(self).seen.append({k.lower(): v
                                for k, v in self.headers.items()})
        if type(self).predict_sleep_s:
            time.sleep(type(self).predict_sleep_s)
        self._send(200, {"outputs": [[0.0]]})


def _capture_replica(sleep_s=0.0):
    handler = type("Cap", (_CaptureReplica,),
                   {"seen": [], "predict_sleep_s": sleep_s})
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    httpd.daemon_threads = True
    t = threading.Thread(target=httpd.serve_forever,
                         kwargs={"poll_interval": 0.1}, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    return httpd, handler, url


def test_router_mints_decrements_and_sheds_deadlines():
    httpd, handler, url = _capture_replica()
    router = Router([url], autostart=False)
    server = RouterServer(router).start()
    try:
        router.poll_once()
        # client header propagates, decremented by router elapsed time
        code, _, _ = _post_raw(
            server.url, headers={"X-PaddleTPU-Deadline-Ms": "5000"})
        assert code == 200
        fwd = handler.seen[-1]["x-paddletpu-deadline-ms"]
        assert 4000.0 < float(fwd) <= 5000.0
        # no header + default flag → router mints one
        pt.set_flags({"FLAGS_router_default_deadline_ms": 4000.0})
        code, _, _ = _post_raw(server.url)
        assert code == 200
        minted = handler.seen[-1]["x-paddletpu-deadline-ms"]
        assert 3000.0 < float(minted) <= 4000.0
        # spent budget sheds AT the router: no forward happens
        forwards_before = len(handler.seen)
        code, body, _ = _post_raw(
            server.url, headers={"X-PaddleTPU-Deadline-Ms": "0"})
        assert code == 503 and body["reason"] == "deadline"
        assert len(handler.seen) == forwards_before
        assert router.stats()["counters"]["deadline_sheds"] == 1
    finally:
        server.close()
        httpd.shutdown()
        httpd.server_close()


def test_router_no_ready_replicas_503_carries_retry_after():
    router = Router([], autostart=False)
    server = RouterServer(router).start()
    try:
        code, body, headers = _post_raw(server.url)
        assert code == 503 and body["reason"] == "no_ready_replicas"
        assert int(headers["Retry-After"]) >= 1
    finally:
        server.close()


# ---------------------------------------------------------------------------
# hung-actor watchdogs
# ---------------------------------------------------------------------------

def test_router_forward_timeout_hung_replica_504_and_health_strike():
    """A hung replica (accepts, never answers): the forward times out
    at the configured bound, strikes the replica's health, and — with
    no alternate — answers 504 with the trace id.  The listener keeps
    answering throughout."""
    httpd, handler, url = _capture_replica(sleep_s=3.0)
    router = Router([url], autostart=False, forward_timeout_ms=250.0)
    server = RouterServer(router).start()
    try:
        router.poll_once()
        t0 = time.monotonic()
        code, body, _ = _post_raw(
            server.url, headers={"X-PaddleTPU-Trace": "deadbeef01"})
        elapsed = time.monotonic() - t0
        assert code == 504 and body["error"] == "forward_timeout"
        assert body["trace_id"] == "deadbeef01"
        assert elapsed < 2.5  # bounded: not the replica's 3s hang
        n = router.stats()["counters"]
        assert n["forward_timeouts"] == 1
        assert router._replicas[url].poll_failures >= 1  # struck
        # the router's own plane stayed responsive
        with urllib.request.urlopen(server.url + "/statusz",
                                    timeout=5) as r:
            assert r.status == 200
    finally:
        server.close()
        httpd.shutdown()
        httpd.server_close()


def test_deadline_bound_timeout_is_a_shed_not_a_replica_strike():
    """When the socket timeout was the CLIENT's remaining budget (not
    the hang bound), running it out is a deadline shed: 503
    ``deadline``, no health strike, no forward_timeout — a healthy-
    but-slower-than-the-budget replica must not get ejected or blamed
    for hanging."""
    httpd, _handler, url = _capture_replica(sleep_s=1.0)
    router = Router([url], autostart=False, forward_timeout_ms=5000.0)
    server = RouterServer(router).start()
    try:
        router.poll_once()
        code, body, _ = _post_raw(
            server.url, headers={"X-PaddleTPU-Deadline-Ms": "300"})
        assert code == 503 and body["reason"] == "deadline"
        n = router.stats()["counters"]
        assert n["forward_timeouts"] == 0
        assert n["deadline_sheds"] == 1
        assert router._replicas[url].poll_failures == 0  # not struck
    finally:
        server.close()
        httpd.shutdown()
        httpd.server_close()


def test_router_forward_timeout_retries_once_on_alternate():
    """With an alternate replica, a timed-out forward retries there
    (inference is idempotent) and the client still gets 200.  The
    timeout is an order above a warmed replica's latency under the
    suite's load and the hung replica sleeps well past it, so what is
    counted does not depend on how busy the machine is."""
    hang_httpd, _hang_handler, hang_url = _capture_replica(sleep_s=10.0)
    p = _build_mlp(feat=6, seed=18)
    eng = ServingEngine(p, workers=1, max_batch=4, max_delay_ms=1.0,
                        deadline_ms=60000)
    good_srv = serve(eng)
    router = Router([hang_url, good_srv.url], autostart=False,
                    forward_timeout_ms=2000.0)
    server = RouterServer(router).start()
    try:
        # the good replica compiles its program now, not inside the
        # routed request's forward timeout
        assert _post_raw(good_srv.url)[0] == 200
        router.poll_once()
        # bias placement to the hung replica (load 0 vs 5)
        router._replicas[good_srv.url].health["serving"][
            "queue_depth"] = 5
        code, body, _ = _post_raw(server.url)
        assert code == 200 and body["outputs"]
        n = router.stats()["counters"]
        assert n["forward_timeouts"] == 1 and n["retries"] == 1
    finally:
        server.close()
        good_srv.close()
        hang_httpd.shutdown()
        hang_httpd.server_close()


def test_replica_health_fault_site_drives_ejection_and_recovery():
    """replica_health:fail@N+ — the replica's /healthz answers 500,
    the router's polls strike it to ejection; lifting the fault
    recovers it on the next successful poll."""
    p = _build_mlp(seed=19)
    eng = ServingEngine(p, workers=1, max_batch=4)
    srv = serve(eng)
    router = Router([srv.url], autostart=False, eject_after=2)
    try:
        router.poll_once()
        assert router.stats()["routable"] == 1
        fault.configure("replica_health:fail@1+")
        router.poll_once()
        router.poll_once()
        rep = router._replicas[srv.url]
        assert rep.ejected
        assert router.stats()["counters"]["ejections"] == 1
        fault.configure("")
        router.poll_once()
        assert not rep.ejected
        assert router.stats()["counters"]["recoveries"] == 1
    finally:
        router.close()
        srv.close()


def test_stuck_worker_watchdog_degrades_and_recovers():
    """serve_batch:delay — the dispatch worker stalls mid-batch; past
    FLAGS_serving_worker_stuck_ms the worker reports ``stuck`` (live
    stuck_ms) and /healthz degrades; when the batch finally lands the
    status recovers."""
    p = _build_mlp(seed=20)
    pt.set_flags({"FLAGS_serving_worker_stuck_ms": 100.0})
    fault.configure("serve_batch:delay:1200@1")
    eng = ServingEngine(p, workers=1, max_batch=4, max_delay_ms=1.0,
                        deadline_ms=60000)
    try:
        fut = eng.submit({"x": np.random.rand(1, 6).astype("float32")})
        time.sleep(0.5)  # inside the injected 1.2s stall
        wh = eng.worker_health()
        assert wh[0]["status"] == "stuck"
        assert wh[0]["stuck_ms"] >= 100.0
        assert eng.health()["status"] == "degraded"
        # the batch lands; the worker is healthy again
        assert fut.result(30) is not None
        assert eng.worker_health()[0]["status"] == "ok"
        assert eng.health()["status"] == "ok"
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# fleet: SIGSTOP'd replica e2e + the chaos harness
# ---------------------------------------------------------------------------

TINY_ARGV = ["--feat", "4", "--hidden", "8", "--depth", "1",
             "--classes", "2", "--workers", "1", "--max-batch", "4",
             "--max-delay-ms", "1", "--deadline-ms", "60000"]


def test_sigstop_replica_router_reroutes_and_supervisor_recovers():
    """The full hung-replica story: SIGSTOP one of two replicas under
    open-loop traffic.  The router detects (forward timeouts strike →
    ejection) and reroutes with ZERO failed requests; the supervisor's
    liveness deadline SIGKILLs the stopped PID and respawns it ready
    at the same URL."""
    sup = FleetSupervisor(replicas=2, replica_argv=TINY_ARGV,
                          max_restarts=3, backoff_ms=100.0,
                          liveness_timeout_ms=1200.0)
    server = None
    try:
        urls = sup.wait_ready(timeout_s=240)
        router = Router(urls, poll_interval_ms=60.0, stale_ms=1500.0,
                        eject_after=2, forward_timeout_ms=500.0)
        server = RouterServer(router).start()
        deadline = time.monotonic() + 30.0
        while router.stats()["routable"] < 2:
            assert time.monotonic() < deadline, "fleet never routable"
            router.poll_once()
            time.sleep(0.1)

        make_feed = lg.feed_maker({"x": (4,)}, rows=1)
        box = {}

        def _traffic():
            box["rep"] = lg.run_open_loop_http(server.url, make_feed,
                                               qps=25.0, duration_s=5.0)

        t = threading.Thread(target=_traffic, daemon=True)
        t.start()
        time.sleep(0.8)
        victim = sup._replicas[0]
        old_pid = victim.proc.pid
        os.kill(old_pid, signal.SIGSTOP)
        t.join(timeout=90.0)
        assert not t.is_alive()
        rep = box["rep"]
        # containment contract: timed-out forwards retried onto the
        # surviving replica — zero failed requests through the hang
        assert rep["failed"] == 0, rep
        assert rep["ok"] >= 0.9 * rep["requests"], rep
        n = router.stats()["counters"]
        assert n["ejections"] >= 1, n
        assert n["forward_timeouts"] >= 1, n
        # supervisor: liveness SIGKILL + respawn at the same URL
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if victim.hung_kills >= 1 and victim.proc.pid != old_pid \
                    and victim.proc.poll() is None:
                try:
                    with urllib.request.urlopen(
                            victim.url + "/healthz", timeout=2) as r:
                        if json.loads(r.read()).get("ready"):
                            break
                except OSError:
                    pass  # ok: successor still binding/warming
            time.sleep(0.2)
        else:
            raise AssertionError("hung replica never SIGKILLed + "
                                 "respawned ready")
        assert victim.hung_kills == 1
        assert stat_get("fleet_hung_kills") >= 1
        router.poll_once()
        code, _, _ = _post_raw(
            server.url,
            body=json.dumps(
                {"inputs": {"x": [[0.1, 0.2, 0.3, 0.4]]}}).encode())
        assert code == 200
    finally:
        if server is not None:
            server.close()
        sup.close()


def test_chaos_harness_smoke_three_replica_fleet():
    """The acceptance scenario: crash + hang + slow + poison injected
    against a 3-replica fleet under open-loop load — zero collateral
    (non-injected) failures, zero poison leaks, availability >= 99%,
    and every recovery path actually fired."""
    chaos = _load_tool("chaos")
    # the classic six explicitly: disagg_crash and hot_swap (both in
    # DEFAULT_SCENARIOS for the CLI) each spawn their own
    # multi-replica fleet — far too heavy for a tier-1 smoke on a
    # core-bound host; `python tools/chaos.py` runs them, and their
    # page-leak / torn-version counts land in the report's totals
    report = chaos.run_chaos(replicas=3, qps=30.0, duration_s=2.5,
                             availability_pct=99.0,
                             liveness_timeout_ms=1200.0,
                             forward_timeout_ms=600.0,
                             scenarios=("baseline", "crash", "hang",
                                        "slow", "poison",
                                        "poison_paged"),
                             log=lambda *a: None)
    assert report["errors"] == {}, report["errors"]
    totals = report["totals"]
    assert totals["collateral_failures"] == 0, report
    assert totals["poison_leaks"] == 0, report
    assert report["availability_pct"] >= 99.0, report
    assert report["ok"] is True
    scen = report["scenarios"]
    assert set(scen) == {"baseline", "crash", "hang", "slow", "poison",
                         "poison_paged"}
    # burn-rate alert contract: clean scenarios silent, every fault
    # window saw an alert fire and clear (errors == {} above already
    # rules out violations; these check the recorded evidence)
    assert totals["alert_errors"] == 0
    assert scen["baseline"]["alerts"]["fired"] == []
    for fault_scen in ("crash", "hang"):
        al = scen[fault_scen]["alerts"]
        assert al["fired_in_window"], (fault_scen, al)
        assert al["cleared"] is True, (fault_scen, al)
    # poison scenario proved bisection end-to-end: the poisoned
    # requests failed (injected), their batchmates did not
    assert scen["poison"]["injected_failures"] >= 1
    assert scen["poison"]["collateral_failures"] == 0
    # paged-path poison containment: every poisoned prompt sharing a
    # cached prefix failed at the prefill check; zero collateral means
    # no clean stream drifted and no shared page was evicted or
    # corrupted (the scenario errors on either, which report["errors"]
    # == {} above already rules out)
    assert scen["poison_paged"]["injected_failures"] >= 1
    assert scen["poison_paged"]["collateral_failures"] == 0
    assert scen["poison_paged"]["poison_leaks"] == 0
    assert scen["poison_paged"]["notes"]["page_evictions"] == 0
    # both process-level faults recovered
    assert scen["crash"]["recovery_s"] > 0
    assert scen["hang"]["recovery_s"] > 0
    # the slow scenario: delays are not failures
    assert scen["slow"]["injected_failures"] == 0
    assert scen["slow"]["collateral_failures"] == 0
