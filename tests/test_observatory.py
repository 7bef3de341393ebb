"""Device-observatory tests (paddle_tpu/costmodel.py +
paddle_tpu/observatory.py).

Covers: executable-manifest capture and determinism (same signature =>
identical flops/peak-HBM across two processes), live efficiency gauges
(device_mfu / device_bw_util), the HBM watermark + Perfetto counter
track (incl. the acceptance artifact: a 20-step guarded run whose
trace.json carries the HBM timeline alongside the host spans), the
``/profilez`` on-demand capture contract, loadgen SLO assertions, and
per-device collective-stat attribution.
"""
import gc
import importlib.util
import json
import os
import signal
import subprocess
import sys
import textwrap
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import costmodel, layers, observatory, optimizer, telemetry
from paddle_tpu.monitor import stat_get

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _observatory_defaults():
    telemetry.clear_spans()
    yield
    pt.set_flags({"FLAGS_telemetry": True, "FLAGS_metrics_dir": "",
                  "FLAGS_metrics_interval": 10.0,
                  "FLAGS_hbm_sample_interval": 0.25,
                  "FLAGS_profilez_sec": 2.0,
                  "FLAGS_device_peak_flops": 0.0,
                  "FLAGS_device_peak_bw": 0.0})
    telemetry.clear_spans()


def _net():
    x = layers.data("x", [4])
    y = layers.data("y", [1])
    pred = layers.fc(x, 1)
    loss = layers.mean(pt.layers.square_error_cost(pred, y))
    optimizer.SGDOptimizer(0.1).minimize(loss)
    return loss


def _feed(seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(8, 4).astype("float32")
    return {"x": x, "y": (x.sum(1, keepdims=True) * 0.5).astype("float32")}


# ---------------------------------------------------------------------------
# costmodel: peaks + manifests
# ---------------------------------------------------------------------------

def test_peak_table_and_overrides():
    p = costmodel.device_peaks("TPU v5 lite")
    assert p["peak_flops"] == 197.0e12 and p["peak_bw"] == 819.0e9
    assert costmodel.device_peaks("TPU v5p")["peak_flops"] == 459.0e12
    # a device the table does not list has no peak and no utilization
    # (it used to be handed a v4's)
    unknown = costmodel.device_peaks("mystery chip")
    assert unknown["source"] == "unknown"
    assert unknown["peak_flops"] is None and unknown["peak_bw"] is None
    assert costmodel.mfu(1e12, "mystery chip") is None
    assert costmodel.bw_util(1e9, "cpu") is None
    pt.set_flags({"FLAGS_device_peak_flops": 100.0,
                  "FLAGS_device_peak_bw": 500.0})
    try:
        p = costmodel.device_peaks("TPU v5 lite")
        assert p["peak_flops"] == 100.0e12 and p["peak_bw"] == 500.0e9
        assert p["source"] == "FLAGS_device_peak_flops"
    finally:
        pt.set_flags({"FLAGS_device_peak_flops": 0.0,
                      "FLAGS_device_peak_bw": 0.0})
    assert costmodel.mfu(197.0e12 / 2, peak=197.0e12) == 0.5
    assert costmodel.bw_util(819.0e9 / 4, peak=819.0e9) == 0.25


def test_executor_entry_carries_manifest_and_feeds_gauges():
    loss = _net()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    # the CPU has no entry in the peak table: without a declared peak
    # a step publishes no utilization at all
    assert costmodel.publish_achieved(
        {"flops": 1e9, "bytes_accessed": 1e6}, 10.0) is None
    pt.set_flags({"FLAGS_device_peak_flops": 1.0,
                  "FLAGS_device_peak_bw": 1.0})
    try:
        for i in range(3):
            exe.run(pt.default_main_program(), feed=_feed(i),
                    fetch_list=[loss])
    finally:
        pt.set_flags({"FLAGS_device_peak_flops": 0.0,
                      "FLAGS_device_peak_bw": 0.0})
    info = exe.cache_info()
    assert info["compiled"] >= 2  # startup + train step
    step_entries = [e for e in info["entries"]
                    if e["signature"] and "x" in e["signature"]]
    assert step_entries and step_entries[0]["aot"]
    man = step_entries[0]["manifest"]
    assert man is not None and man["flops"] > 0
    assert man["peak_hbm_bytes"] > 0
    # live efficiency gauges: achieved rate over the peak table
    assert telemetry.metrics.gauge("device_mfu").get() > 0
    assert telemetry.metrics.gauge("device_bw_util").get() > 0
    exe.close()


_DETERMINISM_SCRIPT = textwrap.dedent("""\
    import json, sys
    sys.path.insert(0, {repo!r})
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "serving_loadgen", {lg!r})
    lg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lg)
    from paddle_tpu.costmodel import manifest_summary
    predictor, shapes = lg.build_synthetic(feat=8, hidden=16, depth=1,
                                           classes=4, seed=0)
    import numpy as np
    predictor.run({{"x": np.zeros((2, 8), "float32")}})
    info = predictor.cache_info()
    print(json.dumps(info["manifests"]))
""")


def test_manifest_determinism_across_processes():
    """Same program + same feed signature => identical flops and
    peak-HBM in two separate processes (the manifest is a property of
    the compiled program, not of the run)."""
    lg_path = os.path.join(REPO, "tools", "serving_loadgen.py")
    script = _DETERMINISM_SCRIPT.format(repo=REPO, lg=lg_path)
    outs = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", script], capture_output=True,
            text=True, timeout=240,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert outs[0].keys() == outs[1].keys() and outs[0]
    for sig, man in outs[0].items():
        assert man is not None and man["flops"] > 0, (sig, man)
        assert man == outs[1][sig]


def test_predictor_cache_info_has_manifests_in_process():
    lg = _load_tool("serving_loadgen")
    predictor, shapes = lg.build_synthetic(feat=8, hidden=16, depth=1,
                                           classes=4)
    predictor.run({"x": np.zeros((2, 8), "float32")})
    info = predictor.cache_info()
    assert info["compiled"] == 1
    man = next(iter(info["manifests"].values()))
    assert man["flops"] > 0 and man["peak_hbm_bytes"] > 0


# ---------------------------------------------------------------------------
# HBM timeline
# ---------------------------------------------------------------------------

def test_hbm_watermark_monotonic_under_grow_then_free():
    import jax.numpy as jnp

    telemetry.metrics.gauge("hbm_peak_bytes").set(0.0)
    sampler = observatory.HbmSampler()
    held = [jnp.ones((128, 128), "float32")]
    sampler._tick()
    peak1 = telemetry.metrics.gauge("hbm_peak_bytes").get()
    assert peak1 > 0
    held.append(jnp.ones((512, 512), "float32"))
    sampler._tick()
    peak2 = telemetry.metrics.gauge("hbm_peak_bytes").get()
    assert peak2 >= peak1 + 512 * 512 * 4 * 0.9
    live_at_peak = telemetry.metrics.gauge("hbm_live_bytes").get()
    held.clear()
    gc.collect()
    sampler._tick()
    # live drops, the watermark must NOT (monotonic high water)
    assert telemetry.metrics.gauge("hbm_live_bytes").get() < live_at_peak
    assert telemetry.metrics.gauge("hbm_peak_bytes").get() >= peak2
    # and the counter track recorded the curve
    samples = [s for s in telemetry.get_counter_samples()
               if s[0] == "hbm_live_bytes"]
    assert len(samples) >= 3
    values = [s[2]["total"] for s in samples[-3:]]
    assert values[1] > values[2]  # the free is visible on the timeline


def test_trace_artifact_carries_hbm_track_alongside_spans(tmp_path):
    """The acceptance artifact: a 20-step guarded training run whose
    Perfetto export shows the HBM timeline counter track next to the
    existing host spans."""
    from paddle_tpu.train_guard import TrainGuard

    mdir = str(tmp_path / "metrics")
    pt.set_flags({"FLAGS_metrics_dir": mdir,
                  "FLAGS_metrics_interval": 0.0,
                  "FLAGS_hbm_sample_interval": 0.01})
    loss = _net()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    g = TrainGuard(exe, loss, checkpoint_dir=str(tmp_path / "ckpts"),
                   interval_steps=10, handle_sigterm=False)
    try:
        for i in range(20):
            g.step(_feed(i), fetch_list=[loss])
    finally:
        g.close()
    telemetry.flush()
    with open(os.path.join(mdir, "trace.json")) as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events}
    assert "executor/step" in names and "executor/dispatch" in names
    counters = [e for e in events
                if e["ph"] == "C" and e["name"] == "hbm_live_bytes"]
    assert counters, "no HBM counter track in the trace export"
    assert all(e["args"]["total"] > 0 for e in counters)
    # the merged trace_export tool passes the counter track through
    out = str(tmp_path / "merged.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_export.py"),
         mdir, out], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        merged = json.load(f)["traceEvents"]
    assert any(e["ph"] == "C" and e["name"] == "hbm_live_bytes"
               for e in merged)


def test_hbm_sampler_refcounting():
    pt.set_flags({"FLAGS_hbm_sample_interval": 0.01})
    assert observatory.start_hbm_sampler()
    assert observatory.start_hbm_sampler()  # second holder
    assert observatory._sampler is not None
    observatory.stop_hbm_sampler()
    assert observatory._sampler is not None  # one holder left
    observatory.stop_hbm_sampler()
    assert observatory._sampler is None
    pt.set_flags({"FLAGS_hbm_sample_interval": 0.0})
    assert not observatory.start_hbm_sampler()  # disabled


# ---------------------------------------------------------------------------
# on-demand profiler capture
# ---------------------------------------------------------------------------

def test_capture_profile_writes_artifact(tmp_path):
    pt.set_flags({"FLAGS_metrics_dir": str(tmp_path)})
    rep = observatory.capture_profile(0.1)
    assert rep["dir"].startswith(str(tmp_path))
    assert rep["files"] and rep["bytes"] > 0
    assert stat_get("profile_captures") >= 1


def test_capture_profile_disabled_and_busy(tmp_path):
    pt.set_flags({"FLAGS_telemetry": False})
    with pytest.raises(observatory.CaptureDisabled):
        observatory.capture_profile(0.05)
    pt.set_flags({"FLAGS_telemetry": True,
                  "FLAGS_metrics_dir": str(tmp_path)})
    t = observatory.capture_profile_async(0.5)
    import time
    deadline = time.monotonic() + 2.0
    while not observatory._capture_active[0] \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert observatory._capture_active[0]
    with pytest.raises(observatory.CaptureBusy):
        observatory.capture_profile(0.05)
    t.join(10.0)
    assert not observatory._capture_active[0]


def test_profilez_endpoint_contract(tmp_path):
    lg = _load_tool("serving_loadgen")
    from paddle_tpu.serving import ServingEngine, serve

    pt.set_flags({"FLAGS_telemetry": True,
                  "FLAGS_metrics_dir": str(tmp_path)})
    predictor, shapes = lg.build_synthetic(feat=4, hidden=8, depth=1,
                                           classes=2)
    eng = ServingEngine(predictor, workers=1, max_batch=2,
                        max_delay_ms=1.0, deadline_ms=60000)
    srv = serve(eng)
    try:
        with urllib.request.urlopen(srv.url + "/profilez?sec=0.15",
                                    timeout=60) as r:
            assert r.status == 200
            rep = json.loads(r.read())
        assert rep["files"] and rep["bytes"] > 0
        assert os.path.isdir(rep["dir"])
        # malformed duration -> 400
        try:
            urllib.request.urlopen(srv.url + "/profilez?sec=abc",
                                   timeout=30)
            assert False, "sec=abc should 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
            e.read()
        # telemetry off -> 503 (the capture surface goes away)
        pt.set_flags({"FLAGS_telemetry": False})
        try:
            urllib.request.urlopen(srv.url + "/profilez?sec=0.1",
                                   timeout=30)
            assert False, "telemetry off should 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            e.read()
        finally:
            pt.set_flags({"FLAGS_telemetry": True})
        # one served request compiles one bucket -> manifests appear
        make_feed = lg.feed_maker(shapes, rows=1)
        outcome, _version = lg._http_predict(
            srv.url + "/predict",
            lg._encode_bodies(make_feed, 1)[0], 60.0)
        assert outcome == "ok"
        # /statusz grew the device block (peaks + hbm snapshot)
        with urllib.request.urlopen(srv.url + "/statusz",
                                    timeout=30) as r:
            statusz = json.loads(r.read())
        dev = statusz["device"]
        # served from the CPU: a device the peak table does not know
        assert dev["peaks"]["source"] == "unknown"
        assert dev["peaks"]["peak_flops"] is None
        assert dev["hbm"]["live_bytes"] is None \
            or dev["hbm"]["live_bytes"] >= 0
        # manifests ride the executable inventory
        execs = statusz["engine"]["executables"]
        assert any(e.get("manifests") for e in execs if e)
    finally:
        srv.close()


def test_trainguard_sigusr2_capture(tmp_path):
    from paddle_tpu.train_guard import TrainGuard

    mdir = str(tmp_path / "metrics")
    pt.set_flags({"FLAGS_metrics_dir": mdir,
                  "FLAGS_profilez_sec": 0.1})
    loss = _net()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    g = TrainGuard(exe, loss, handle_sigterm=True)
    try:
        assert signal.getsignal(signal.SIGUSR2) == g._on_sigusr2
        os.kill(os.getpid(), signal.SIGUSR2)  # delivered synchronously
        g.step(_feed(0), fetch_list=[loss])  # training continues
        # the capture runs on its own thread; wait for the artifact
        import time
        deadline = time.monotonic() + 15.0
        prof_root = os.path.join(mdir, "profiles")
        done = False
        while time.monotonic() < deadline and not done:
            done = not observatory._capture_active[0] and \
                os.path.isdir(prof_root) and any(
                    files for _, _, files in os.walk(prof_root))
            time.sleep(0.05)
        assert done, "SIGUSR2 capture artifact never appeared"
    finally:
        g.close()
    assert signal.getsignal(signal.SIGUSR2) in (signal.SIG_DFL,
                                                signal.Handlers.SIG_DFL)


# ---------------------------------------------------------------------------
# per-device attribution
# ---------------------------------------------------------------------------

def test_per_device_collective_stats():
    import jax
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel.mesh import make_mesh, shard_map_compat
    from paddle_tpu.parallel.ring import ulysses_attention

    before = [stat_get(f"collective_all_to_all_calls_dev{i}")
              for i in range(2)]
    mesh = make_mesh({"sp": 2})
    fn = jax.jit(shard_map_compat(
        lambda q, k, v: ulysses_attention(q, k, v, "sp"),
        mesh, in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp")))
    rng = np.random.RandomState(0)
    q = rng.randn(1, 2, 8, 4).astype("float32")
    # tracing alone emits the collectives (trace-time stats)
    fn.lower(q, q, q)
    after = [stat_get(f"collective_all_to_all_calls_dev{i}")
             for i in range(2)]
    deltas = [a - b for a, b in zip(after, before)]
    assert deltas[0] == deltas[1] >= 4  # 3 scatters + 1 gather
    # every shard got the same attribution as the aggregate emit
    assert stat_get("collective_all_to_all_calls") >= deltas[0]


# ---------------------------------------------------------------------------
# loadgen SLO assertions
# ---------------------------------------------------------------------------

def test_loadgen_slo_check():
    lg = _load_tool("serving_loadgen")
    rep = {"mode": "closed", "shed_rate": 0.02,
           "latency_ms": {"p99": 12.0}}
    assert lg.check_slo(rep, p99_ms=20.0, shed_pct=5.0)["ok"]
    assert not lg.check_slo(rep, p99_ms=10.0)["ok"]
    assert not lg.check_slo(rep, shed_pct=1.0)["ok"]
    # both halves of --mode both are held to the SLO
    both = {"mode": "both",
            "closed": {"shed_rate": 0.0, "latency_ms": {"p99": 5.0}},
            "open": {"shed_rate": 0.5, "latency_ms": {"p99": 5.0}}}
    r = lg.check_slo(both, p99_ms=20.0, shed_pct=10.0)
    assert not r["ok"] and any("open" in v for v in r["violations"])
    # a fully-shed run must not pass on a vacuous p99
    empty = {"mode": "open", "shed_rate": 1.0, "latency_ms": {}}
    assert not lg.check_slo(empty, p99_ms=20.0)["ok"]
