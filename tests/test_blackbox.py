"""Black-box flight recorder: ring bounds, dump triggers, the
never-raise dump discipline, supervisor harvest + death attribution,
and the one-shot /debugz bundles (replica + federated router).

Three tiers of test: pure in-process ring/attribution units,
subprocess crash labs (a child installs the recorder and dies by
SIGSEGV / an uncaught thread exception — the parent reads the
artifacts exactly like the fleet supervisor would), and a live
subprocess fleet whose SIGKILLed replica must come back attributed,
with its postmortems booked on /statusz and forensics().
"""
import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

import paddle_tpu as pt
from paddle_tpu import blackbox, fault, telemetry
from paddle_tpu.monitor import stat_add, stat_get
from paddle_tpu.serving import (FleetSupervisor, Router, RouterServer,
                                ServingEngine)
from paddle_tpu.serving.server import ServingServer

from conftest import retry_flaky

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_blackbox_tests", os.path.join(REPO, "tools",
                                               f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lg = _load_tool("serving_loadgen")


@pytest.fixture(autouse=True)
def _blackbox_defaults():
    blackbox.reset()
    fault.reset()
    telemetry.clear_spans()
    yield
    pt.set_flags({"FLAGS_blackbox": True, "FLAGS_blackbox_events": 256,
                  "FLAGS_blackbox_requests": 64,
                  "FLAGS_telemetry": True, "FLAGS_metrics_dir": "",
                  "FLAGS_metrics_interval": 10.0,
                  "FLAGS_fault_inject": ""})
    fault.reset()
    blackbox.reset()
    telemetry.clear_spans()


# ---------------------------------------------------------------------------
# rings
# ---------------------------------------------------------------------------

def test_event_ring_bounded_and_evicts_oldest():
    pt.set_flags({"FLAGS_blackbox_events": 4})
    blackbox.reset()  # capacity is read at recorder build
    for i in range(10):
        blackbox.record_event("tick", i=i)
    ring = blackbox.snapshot()
    assert ring["enabled"] is True
    assert ring["capacity"]["events"] == 4
    assert [e["i"] for e in ring["events"]] == [6, 7, 8, 9]


def test_request_ring_cap_drops_and_counts():
    pt.set_flags({"FLAGS_blackbox_requests": 2})
    blackbox.reset()
    t1 = blackbox.request_begin("tid-1", "predict", rows=1)
    t2 = blackbox.request_begin("tid-2", "predict", rows=2)
    assert t1 is not None and t2 is not None
    # over cap: not recorded (None token), counted, nothing raises
    assert blackbox.request_begin("tid-3", "predict") is None
    ring = blackbox.snapshot()
    assert len(ring["live_requests"]) == 2
    assert ring["requests_dropped"] == 1
    # retiring frees a slot; phase/end on a None token are no-ops
    blackbox.request_end(t1)
    blackbox.request_phase(None, "executing")
    blackbox.request_end(None)
    assert blackbox.request_begin("tid-4", "generate") is not None
    live = blackbox.snapshot()["live_requests"]
    assert sorted(r["trace_id"] for r in live) == ["tid-2", "tid-4"]


def test_request_phase_and_age_in_snapshot():
    tok = blackbox.request_begin("tid-9", "generate", prompt_len=7)
    blackbox.request_phase(tok, "prefill", slot=3)
    [rec] = blackbox.snapshot()["live_requests"]
    assert rec["phase"] == "prefill" and rec["slot"] == 3
    assert rec["endpoint"] == "generate" and rec["prompt_len"] == 7
    assert rec["age_ms"] >= 0.0 and "t_admit" not in rec


def test_log_event_tap_mirrors_without_metrics_dir():
    # no FLAGS_metrics_dir: events.jsonl is off, the ring still fills
    telemetry.log_event("ckpt_publish", step=12)
    evs = blackbox.snapshot()["events"]
    assert any(e["event"] == "ckpt_publish" and e["step"] == 12
               for e in evs)


def test_flush_tap_snapshots_metrics_and_rolls_dump(tmp_path):
    mdir = str(tmp_path / "m")
    pt.set_flags({"FLAGS_metrics_dir": mdir,
                  "FLAGS_metrics_interval": 0.0})
    stat_add("bb_test_counter", 5)
    telemetry.flush(force=True)
    snaps = blackbox.snapshot()["metric_snapshots"]
    assert snaps and "bb_test_counter" in snaps[-1]["counters"]
    rolling = os.path.join(mdir, "postmortem",
                           f"{os.getpid()}-rolling.json")
    assert os.path.isfile(rolling)
    doc = json.load(open(rolling))
    assert doc["schema"] == "paddle_tpu.postmortem.v1"
    assert doc["reason"] == "rolling" and doc["pid"] == os.getpid()


# ---------------------------------------------------------------------------
# zero-work when off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flags", [{"FLAGS_blackbox": False},
                                   {"FLAGS_telemetry": False}])
def test_disabled_means_zero_work_and_no_files(tmp_path, flags):
    mdir = str(tmp_path / "m")
    pt.set_flags(dict(flags, FLAGS_metrics_dir=mdir))
    assert blackbox.enabled() is False
    assert blackbox.request_begin("tid", "predict") is None
    blackbox.record_event("ignored")
    assert blackbox.dump("testing") is None
    assert blackbox.snapshot() == {"enabled": False}
    assert blackbox.install() is False
    assert not os.path.isdir(os.path.join(mdir, "postmortem"))
    # nothing was buffered while off: re-enabling starts empty
    pt.set_flags({"FLAGS_blackbox": True, "FLAGS_telemetry": True})
    assert blackbox.snapshot()["events"] == []


# ---------------------------------------------------------------------------
# dump document + the never-raise discipline
# ---------------------------------------------------------------------------

def test_dump_document_schema(tmp_path):
    pt.set_flags({"FLAGS_metrics_dir": str(tmp_path)})
    blackbox.record_event("last_words", n=1)
    tok = blackbox.request_begin("tid-d", "predict", rows=2)
    try:
        raise ValueError("engine exploded")
    except ValueError as e:
        path = blackbox.dump_exception("unit_test", e)
    assert path and os.path.isfile(path)
    assert os.path.basename(path) == \
        f"{os.getpid()}-uncaught_unit_test.json"
    doc = json.load(open(path))
    assert doc["schema"] == "paddle_tpu.postmortem.v1"
    assert doc["reason"] == "uncaught_unit_test"
    assert doc["exception"]["type"] == "ValueError"
    assert "engine exploded" in doc["exception"]["message"]
    assert any(e["event"] == "last_words"
               for e in doc["blackbox"]["events"])
    assert any(r["trace_id"] == "tid-d"
               for r in doc["blackbox"]["live_requests"])
    assert doc["flags"]["FLAGS_blackbox"] is True
    assert isinstance(doc["trace_events"], list)
    assert "counters" in doc["metrics"]
    blackbox.request_end(tok)


def test_injected_dump_fault_never_raises(tmp_path):
    pt.set_flags({"FLAGS_metrics_dir": str(tmp_path)})
    fault.configure("blackbox_dump:raise@1")
    before = stat_get("blackbox_dump_failures")
    assert blackbox.dump("doomed") is None  # swallowed, not raised
    assert stat_get("blackbox_dump_failures") == before + 1
    # the fault fired before any file was created (dir included)
    assert not os.path.exists(os.path.join(str(tmp_path),
                                           "postmortem"))
    # the site is per-hit: the next dump (hit 2) succeeds
    path = blackbox.dump("survivor")
    assert path and os.path.isfile(path)


def test_dump_reason_is_sanitized(tmp_path):
    pt.set_flags({"FLAGS_metrics_dir": str(tmp_path)})
    path = blackbox.dump("../../../etc/passwd !")
    assert os.path.dirname(path) == os.path.join(str(tmp_path),
                                                 "postmortem")
    assert "/etc/" not in os.path.basename(path)


# ---------------------------------------------------------------------------
# subprocess crash labs: die for real, read the artifacts like the
# supervisor would
# ---------------------------------------------------------------------------

def _crash_child(tmp_path, body, timeout=120):
    code = ("import os, signal, sys, threading\n"
            "from paddle_tpu import blackbox, telemetry\n"
            "assert blackbox.install()\n"
            "telemetry.log_event('child_alive', pid=os.getpid())\n"
            + body)
    env = dict(os.environ, FLAGS_metrics_dir=str(tmp_path),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=timeout, capture_output=True)
    return proc.returncode


def test_fatal_signal_dumps_and_exit_code_names_signal(tmp_path):
    rc = _crash_child(tmp_path,
                      "os.kill(os.getpid(), signal.SIGSEGV)\n")
    assert rc == -signal.SIGSEGV  # the dump didn't launder the death
    pids = {int(n.split("-")[0])
            for n in os.listdir(tmp_path / "postmortem")}
    assert len(pids) == 1
    arts = blackbox.harvest(str(tmp_path), pids.pop())
    reasons = {a["reason"] for a in arts}
    assert {"rolling", "signal_SIGSEGV", "faulthandler"} <= reasons
    assert blackbox.attribute_death(rc, arts) == "signal:SIGSEGV"
    [sig_art] = [a for a in arts if a["reason"] == "signal_SIGSEGV"]
    doc = json.load(open(sig_art["path"]))
    # fault-window evidence: the ring rode into the dump
    assert any(e["event"] == "child_alive"
               for e in doc["blackbox"]["events"])


def test_uncaught_thread_exception_dumps_via_excepthook(tmp_path):
    rc = _crash_child(tmp_path, (
        "def boom():\n"
        "    raise RuntimeError('scheduler died')\n"
        "t = threading.Thread(target=boom, name='sched')\n"
        "t.start(); t.join()\n"
        "sys.exit(3)\n"))
    assert rc == 3
    pids = {int(n.split("-")[0])
            for n in os.listdir(tmp_path / "postmortem")}
    arts = blackbox.harvest(str(tmp_path), pids.pop())
    [art] = [a for a in arts
             if a["reason"].startswith("uncaught_thread_")]
    assert art["exception"] == "RuntimeError"
    # rc>0 + a self-dump naming the thread = explained crash
    assert blackbox.attribute_death(rc, arts) \
        == "crash:uncaught_thread_sched"


def test_sigkill_leaves_only_the_seeded_rolling_dump(tmp_path):
    rc = _crash_child(tmp_path,
                      "os.kill(os.getpid(), signal.SIGKILL)\n")
    assert rc == -signal.SIGKILL
    pids = {int(n.split("-")[0])
            for n in os.listdir(tmp_path / "postmortem")}
    arts = blackbox.harvest(str(tmp_path), pids.pop())
    # no handler ran (SIGKILL is uncatchable) — but install() seeded
    # the rolling dump, so the death still left its flight recorder
    assert "rolling" in {a["reason"] for a in arts}
    assert blackbox.attribute_death(rc, arts) == "signal:SIGKILL"


# ---------------------------------------------------------------------------
# supervisor half: kill marks, harvest, the attribution matrix
# ---------------------------------------------------------------------------

def test_write_kill_mark_and_harvest(tmp_path):
    path = blackbox.write_kill_mark(str(tmp_path), 4242, replica=1,
                                    stale_s=9.7)
    assert path and os.path.basename(path) == "4242-hung_kill.json"
    doc = json.load(open(path))
    assert doc["written_by"] == "supervisor" and doc["replica"] == 1
    [art] = blackbox.harvest(str(tmp_path), 4242)
    assert art["reason"] == "hung_kill"
    assert art["written_by"] == "supervisor"
    # the mark explains the death regardless of the SIGKILL rc
    assert blackbox.attribute_death(-signal.SIGKILL, [art]) \
        == "hung_kill"
    assert blackbox.harvest(str(tmp_path), 9999) == []  # other pid


def test_attribution_matrix():
    roll = {"path": "p", "reason": "rolling", "written_by": "self"}
    fh = {"path": "p", "reason": "faulthandler"}
    crash = {"path": "p", "reason": "uncaught_generation_scheduler",
             "written_by": "self"}
    mark = {"path": "p", "reason": "hung_kill",
            "written_by": "supervisor"}
    attr = blackbox.attribute_death
    assert attr(0, []) == "clean_exit"
    assert attr(0, [roll]) == "clean_exit"
    assert attr(-signal.SIGKILL, [roll]) == "signal:SIGKILL"
    assert attr(-signal.SIGSEGV, []) == "signal:SIGSEGV"
    assert attr(-signal.SIGKILL, [mark, roll]) == "hung_kill"
    assert attr(1, [crash, roll]) \
        == "crash:uncaught_generation_scheduler"
    # rc>0 with only context artifacts (or none) is the bad bucket
    assert attr(1, []) == "unexplained"
    assert attr(1, [roll, fh]) == "unexplained"
    assert attr(None, [roll]) == "unexplained"
    # a torn self-dump is not an explanation
    torn = dict(crash, torn=True)
    assert attr(1, [torn, roll]) == "unexplained"


def test_signal_name_decoding():
    assert blackbox.signal_name(-signal.SIGKILL) == "SIGKILL"
    assert blackbox.signal_name(-signal.SIGSEGV) == "SIGSEGV"
    assert blackbox.signal_name(0) is None
    assert blackbox.signal_name(3) is None
    assert blackbox.signal_name(None) is None


# ---------------------------------------------------------------------------
# /debugz: replica bundle, federated router bundle, loadgen auto-fetch
# ---------------------------------------------------------------------------

@pytest.fixture()
def mini_server():
    pred, shapes = lg.build_synthetic(feat=4, hidden=8, depth=1,
                                      classes=2)
    eng = ServingEngine(pred, workers=1, max_batch=2,
                        max_delay_ms=1.0, deadline_ms=60000.0)
    eng.warmup(shapes)
    srv = ServingServer(eng).start()
    yield eng, srv
    srv.close()


def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        assert r.status == 200
        return json.loads(r.read())


def test_replica_debugz_bundle(mini_server, tmp_path):
    eng, srv = mini_server
    pt.set_flags({"FLAGS_metrics_dir": str(tmp_path)})
    body = json.dumps({"inputs": {"x": [[0.1, 0.2, 0.3, 0.4]]}})
    req = urllib.request.Request(
        srv.url + "/predict", data=body.encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 200
    doc = _get_json(srv.url + "/debugz")
    assert doc["bundle"] == "paddle_tpu.debugz.v1"
    assert doc["statusz"]["pid"] == os.getpid()
    assert "engine" in doc["statusz"]
    assert doc["tracez"] is not None and doc["metrics"] is not None
    bb = doc["blackbox"]
    assert bb["enabled"] is True
    # the served request was admitted AND retired: no live last words
    assert bb["live_requests"] == []
    # ?dump=1 writes the postmortem and reports where
    doc2 = _get_json(srv.url + "/debugz?dump=1")
    assert doc2["dump_path"] and os.path.isfile(doc2["dump_path"])
    assert json.load(open(doc2["dump_path"]))["reason"] == "requested"


def test_replica_debugz_degrades_when_disabled(mini_server):
    eng, srv = mini_server
    pt.set_flags({"FLAGS_blackbox": False})
    doc = _get_json(srv.url + "/debugz")
    assert doc["blackbox"] == {"enabled": False}
    assert doc["statusz"]  # the bundle itself still answers 200


def test_router_debugz_federates(mini_server):
    eng, srv = mini_server
    router = Router([srv.url], poll_interval_ms=200.0,
                    autostart=False)
    rserver = RouterServer(router).start()
    try:
        router.poll_once()
        doc = _get_json(rserver.url + "/debugz")
        assert doc["tier"] == "router"
        assert doc["bundle"] == "paddle_tpu.debugz.v1"
        assert "fleetz" in doc and "statusz" in doc
        sub = doc["replicas"][srv.url]
        assert sub["bundle"] == "paddle_tpu.debugz.v1"
        assert "statusz" in sub and "blackbox" in sub
    finally:
        rserver.close()


def test_router_debugz_degrades_on_dead_replica(mini_server):
    eng, srv = mini_server
    dead = "http://127.0.0.1:1"  # nothing listens on port 1
    router = Router([srv.url, dead], poll_interval_ms=200.0,
                    autostart=False)
    try:
        doc = router.debugz(timeout=2.0)
        assert "error" in doc["replicas"][dead]
        assert doc["replicas"][srv.url]["bundle"] \
            == "paddle_tpu.debugz.v1"
    finally:
        router.close()


def test_loadgen_slo_violation_autofetches_debugz(
        mini_server, tmp_path, capsys):
    eng, srv = mini_server
    out = str(tmp_path / "report.json")
    rc = lg.main(["--url", srv.url, "--feat", "4", "--mode", "closed",
                  "--requests", "3", "--concurrency", "1",
                  "--slo-p99-ms", "0.000001", "--out", out])
    assert rc == 1  # nothing real answers in a nanosecond
    report = json.load(open(out))
    assert not report["slo"]["ok"]
    bundle_path = report["slo"]["debugz"]
    assert bundle_path and os.path.isfile(bundle_path)
    assert json.load(open(bundle_path))["bundle"] \
        == "paddle_tpu.debugz.v1"
    assert "SLO VIOLATION" in capsys.readouterr().err


def test_loadgen_slo_pass_skips_debugz(mini_server, tmp_path):
    eng, srv = mini_server
    out = str(tmp_path / "report.json")
    rc = lg.main(["--url", srv.url, "--feat", "4", "--mode", "closed",
                  "--requests", "3", "--concurrency", "1",
                  "--slo-p99-ms", "60000", "--out", out])
    assert rc == 0
    assert "debugz" not in json.load(open(out))["slo"]


# ---------------------------------------------------------------------------
# live fleet: a SIGKILLed replica comes back attributed
# ---------------------------------------------------------------------------

TINY_ARGV = ["--feat", "4", "--hidden", "8", "--depth", "1",
             "--classes", "2", "--workers", "1", "--max-batch", "2",
             "--max-delay-ms", "1", "--deadline-ms", "60000"]


@retry_flaky()
def test_fleet_books_sigkill_death_with_postmortems():
    sup = FleetSupervisor(replicas=1, replica_argv=TINY_ARGV,
                          max_restarts=3, backoff_ms=100.0)
    try:
        sup.wait_ready(timeout_s=240)
        rep = sup._replicas[0]
        old_pid = rep.proc.pid
        os.kill(old_pid, signal.SIGKILL)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            death = rep.last_death
            if death is not None and death["pid"] == old_pid:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("supervisor never booked the death")
        assert death["attribution"] == "signal:SIGKILL"
        assert death["signal"] == "SIGKILL"
        assert death["rc"] == -signal.SIGKILL
        # the seeded rolling dump means even an instant SIGKILL
        # leaves at least one artifact
        assert death["postmortems"]
        assert all(os.path.isfile(p) for p in death["postmortems"])
        [st] = sup.statusz()["replicas"]
        assert st["last_death"]["attribution"] == "signal:SIGKILL"
        assert st["postmortems_collected"] >= 1
        assert st["unexplained_deaths"] == 0
        fz = sup.forensics()
        assert fz["unexplained_deaths"] == 0
        assert fz["postmortems_collected"] >= 1
        [d] = fz["deaths"]
        assert d["replica"] == 0 and d["attribution"] \
            == "signal:SIGKILL"
        # the respawn came back serving (it is spawned a backoff after
        # the death is booked, and wait_ready gives up on a replica
        # whose process is dead: wait for the new process first)
        while rep.proc.pid == old_pid and time.monotonic() < deadline:
            time.sleep(0.05)
        sup.wait_ready(timeout_s=240)
    finally:
        sup.close()


def test_trace_export_ingests_dead_pids_postmortem_ring(tmp_path):
    te = _load_tool("trace_export")
    live = {"name": "executor/step", "ph": "X", "ts": 10.0,
            "dur": 5.0, "pid": 111, "tid": 1}
    mdir = tmp_path / "m"
    (mdir / "postmortem").mkdir(parents=True)
    (mdir / "trace.json").write_text(
        json.dumps({"traceEvents": [live]}))

    def _pm(pid, reason, n_events):
        doc = {"schema": "paddle_tpu.postmortem.v1", "pid": pid,
               "reason": reason,
               "trace_events": [
                   {"name": "serving/request", "ph": "X",
                    "ts": 20.0 + i, "dur": 1.0, "pid": pid, "tid": 1}
                   for i in range(n_events)]}
        (mdir / "postmortem" / f"{pid}-{reason}.json").write_text(
            json.dumps(doc))

    _pm(111, "rolling", 9)   # the live pid's own dump: excluded
    _pm(222, "rolling", 1)   # superseded by the crash dump below
    _pm(222, "signal_SIGSEGV", 3)
    out = str(tmp_path / "out.json")
    info = te.export(str(mdir), out)
    assert info["postmortems"] == 1
    evs = json.load(open(out))["traceEvents"]
    labels = [e["args"]["name"] for e in evs if e["ph"] == "M"]
    assert any("postmortem pid 222 (signal_SIGSEGV)" in x
               for x in labels)
    assert not any("111" in x for x in labels)
    # the dead pid rides as its own re-pidded track group: exactly
    # the crash dump's 3 spans (not the superseded rolling ring's 1)
    dead = [e for e in evs
            if e["name"] == "serving/request" and e["ph"] != "M"]
    assert len(dead) == 3
    assert {e["pid"] for e in dead} != {222}  # re-pidded, not raw


def test_attach_router_surfaces_supervision_on_fleetz(mini_server):
    eng, srv = mini_server

    class _StubSup:  # forensics-only stand-in, no subprocesses
        def forensics(self):
            return {"deaths": [], "postmortems_collected": 2,
                    "unexplained_deaths": 0}

    sup = _StubSup()
    router = Router([srv.url], poll_interval_ms=200.0,
                    autostart=False)
    try:
        # attach_router is just wiring; fleetz then carries forensics
        assert router.supervisor is None
        FleetSupervisor.attach_router(sup, router)
        assert router.supervisor is sup
        router.poll_once()
        fz = router.fleetz()
        assert fz["supervision"]["postmortems_collected"] == 2
        assert fz["supervision"]["unexplained_deaths"] == 0
    finally:
        router.close()
