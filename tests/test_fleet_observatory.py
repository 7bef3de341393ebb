"""Fleet observatory matrix: tsdb windowed math, burn-rate alerting,
router metrics federation (/fleetz + fleet-labeled /metrics),
per-sequence TTFT/ITL timelines, the streaming /generate contract,
and the loadgen's client-side TTFT/ITL SLO bounds.

In-process throughout: two real ServingServers behind a Router give
real sockets and real scrapes with deterministic control (manual
``poll_once`` sweeps, injectable tsdb timestamps).
"""
import importlib.util
import json
import os
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import promtext, telemetry, tsdb
from paddle_tpu.serving import (GenerationEngine, Router, RouterServer,
                                ServingEngine)
from paddle_tpu.serving.server import ServingServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_loadgen():
    spec = importlib.util.spec_from_file_location(
        "serving_loadgen_observatory_tests",
        os.path.join(REPO, "tools", "serving_loadgen.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lg = _load_loadgen()

TINY_LLAMA = dict(vocab_size=64, hidden=32, num_layers=2, num_heads=4,
                  num_kv_heads=2, intermediate=64)


# ---------------------------------------------------------------------------
# tsdb core
# ---------------------------------------------------------------------------

def test_tsdb_ring_eviction_and_memory_bound():
    db = tsdb.TSDB(points=8, max_series=3)
    for i in range(50):
        db.record("a", i, ts=1000.0 + i)
    assert len(db.points("a")) == 8
    assert [v for _, v in db.points("a")] == list(range(42, 50))
    # series cap: past max_series new names drop, counted, never OOM
    db.record("b", 1, ts=1.0)
    db.record("c", 1, ts=1.0)
    assert db.record("d", 1, ts=1.0) is False
    assert db.stats()["series_dropped"] == 1
    assert db.stats()["series"] == 3
    # non-numeric / non-finite points are refused, not stored
    assert db.record("a", "nope") is False
    assert db.record("a", float("nan")) is False


def test_tsdb_windowed_rate_delta_quantile():
    db = tsdb.TSDB(points=64)
    t0 = 5000.0
    for i in range(11):
        db.record("ctr", 10 * i, ts=t0 + i)     # +10/s counter
        db.record("g", float(i), ts=t0 + i)     # gauge ramp 0..10
    now = t0 + 10
    assert db.delta("ctr", 5.0, now=now) == 50
    assert abs(db.rate("ctr", 5.0, now=now) - 10.0) < 1e-9
    # window scoping: only the trailing points count
    assert db.delta("ctr", 2.0, now=now) == 20
    assert db.quantile("g", 50, 100.0, now=now) == 5.0
    assert db.quantile("g", 100, 100.0, now=now) == 10.0
    assert db.avg("g", 2.0, now=now) == pytest.approx(9.0)
    assert db.minmax("g", 100.0, now=now) == (0.0, 10.0)
    # empty window: None, never 0 (no evidence != no traffic)
    assert db.delta("ctr", 5.0, now=now + 100) is None
    assert db.rate("missing", 5.0) is None
    assert db.quantile("g", 99, 0.0001, now=now + 100) is None


def test_tsdb_monotonic_counter_reset():
    """A replica restart drops its counters to ~0: the post-reset
    value is the increment — the raw negative difference must never
    erase real traffic from a fleet rate."""
    db = tsdb.TSDB(points=16)
    t0 = 0.0
    for i, v in enumerate([100, 150, 200, 5, 30]):  # reset after 200
        db.record("c", v, ts=t0 + i)
    # 50 + 50 + (reset: 5) + 25 = 130
    assert db.delta("c", 100.0, now=t0 + 4) == 130


# ---------------------------------------------------------------------------
# burn-rate monitor
# ---------------------------------------------------------------------------

def _availability_monitor(db, **kw):
    spec = tsdb.SloSpec("avail", "availability", error_series="err",
                        total_series="tot", objective_pct=99.0)
    kw.setdefault("fast_s", 10.0)
    kw.setdefault("slow_s", 30.0)
    kw.setdefault("threshold", 2.0)
    return tsdb.BurnRateMonitor(db, [spec], publish=False, **kw)


def _feed(db, t0, n, err_rate, base_tot=0.0, base_err=0.0, step_s=1.0):
    """n seconds of traffic at 10 req/s with the given error rate."""
    for i in range(n):
        db.record("tot", base_tot + 10 * i, ts=t0 + i * step_s)
        db.record("err", base_err + 10 * i * err_rate,
                  ts=t0 + i * step_s)
    return t0 + (n - 1) * step_s


def test_burn_rate_window_pair_both_must_burn():
    """The multi-window contract: a fast-only spike (slow window still
    healthy) must NOT page; sustained burn over both windows fires."""
    db = tsdb.TSDB(points=256)
    mon = _availability_monitor(db)
    # 30s clean, then a 2s spike at 30% errors: the fast (10s) window
    # burns at ~3x budget, the slow (30s) window still sits at ~1x —
    # no page on a blip
    end = _feed(db, 1000.0, 31, 0.0)
    end = _feed(db, end + 1, 2, 0.3, base_tot=310, base_err=0.0)
    st = mon.evaluate(now=end)
    a = st["alerts"][0]
    assert a["burn_fast"] is not None and a["burn_fast"] >= 2.0
    assert a["burn_slow"] is not None and a["burn_slow"] < 2.0
    assert a["state"] == "ok", a  # slow window hasn't confirmed yet
    # sustain the burn until the slow window agrees -> fires
    end = _feed(db, end + 1, 20, 0.3, base_tot=330, base_err=3.0)
    st = mon.evaluate(now=end)
    a = st["alerts"][0]
    assert a["burn_slow"] >= 2.0 and a["state"] == "firing", a
    assert a["firing_for_s"] is not None
    assert st["firing"] == 1


def test_burn_rate_hysteresis_and_clear():
    db = tsdb.TSDB(points=512)
    mon = _availability_monitor(db, clear_ratio=0.5)
    end = _feed(db, 0.0, 40, 0.5)        # sustained 50% errors
    st = mon.evaluate(now=end)
    assert st["alerts"][0]["state"] == "firing"
    # errors stop; fast burn decays below threshold but above
    # threshold*clear_ratio -> still firing (hysteresis)
    t = end
    cleared_at = None
    for i in range(40):
        t += 1.0
        db.record("tot", 390 + 10 * (i + 1), ts=t)
        db.record("err", 195, ts=t)  # frozen error counter
        st = mon.evaluate(now=t)
        a = st["alerts"][0]
        if a["state"] == "ok":
            cleared_at = i
            break
        if a["burn_fast"] is not None:
            # never cleared while fast burn still >= thr * ratio
            assert a["burn_fast"] >= 0.0
    assert cleared_at is not None, "alert never cleared"
    # transitions recorded (fired once, cleared once)
    assert st["alerts"][0]["transitions"] == 2


def test_burn_rate_budget_exhaustion_and_config_guards():
    db = tsdb.TSDB(points=512)
    mon = _availability_monitor(db, budget_window_s=100.0)
    # 2% errors sustained = 2x the 1% budget -> exhausted over the
    # budget-integration window
    end = _feed(db, 0.0, 60, 0.02)
    st = mon.evaluate(now=end)
    a = st["alerts"][0]
    assert a["budget_spent_pct"] == pytest.approx(200.0, rel=0.1)
    assert a["exhausted"] is True
    # latency spec units: share of samples over threshold / budget
    for i in range(100):
        db.record("lat", 10.0 if i % 20 else 500.0, ts=end + i)
    lat = tsdb.SloSpec("p99", "latency", latency_series="lat",
                       threshold_ms=250.0, objective_pct=99.0)
    frac = lat.bad_fraction(db, 1000.0, now=end + 99)
    assert frac == pytest.approx(0.05)     # 5 of 100 over
    # 5% over a 1% budget = burn 5
    assert frac / lat.budget == pytest.approx(5.0)
    # config guards: window pair must be ordered; specs validated
    with pytest.raises(ValueError):
        tsdb.BurnRateMonitor(db, [], fast_s=60.0, slow_s=30.0)
    with pytest.raises(ValueError):
        tsdb.SloSpec("x", "availability", error_series="e")
    with pytest.raises(ValueError):
        tsdb.SloSpec("x", "latency", latency_series="l")
    with pytest.raises(ValueError):
        tsdb.SloSpec("x", "nope")


def test_sample_registry_cadence_and_flag_gate():
    tsdb.reset_default()
    telemetry.gauge_set("obs_test_gauge", 7.0)
    n = tsdb.sample_registry()
    assert n > 0
    assert tsdb.default().last("obs_test_gauge") == 7.0
    # FLAGS_tsdb=0: zero recording
    pt.set_flags({"FLAGS_tsdb": 0})
    try:
        assert tsdb.sample_registry() == 0
    finally:
        pt.set_flags({"FLAGS_tsdb": 1})
    tsdb.reset_default()


# ---------------------------------------------------------------------------
# promtext: shared parser
# ---------------------------------------------------------------------------

def test_promtext_parses_live_exposition():
    telemetry.gauge_set("obs_parse_gauge", 3.5)
    telemetry.histogram_observe("obs_parse_ms", 12.0)
    text = telemetry.prometheus_text()
    assert promtext.validate_lines(text) == []
    fams = promtext.parse_exposition(text, strict=True)
    g = fams["paddle_tpu_obs_parse_gauge"]
    assert g.type == "gauge" and g.value() == 3.5
    h = fams["paddle_tpu_obs_parse_ms"]
    assert h.type == "histogram"
    assert h.histogram_count() == 1.0
    assert h.histogram_sum() == pytest.approx(12.0)
    buckets = h.histogram_buckets()
    assert buckets[-1][0] == float("inf") and buckets[-1][1] == 1.0
    # labels parse; strict mode raises on garbage
    s = promtext.parse_labels('{a="x",le="+Inf"}')
    assert s == {"a": "x", "le": "+Inf"}
    # escape decoding is a left-to-right scan: an escaped backslash
    # followed by 'n' is backslash+n, never a newline
    assert promtext.parse_labels('{p="C:\\\\net"}') == {"p": "C:\\net"}
    assert promtext.parse_labels('{p="a\\nb\\"c"}') == {"p": 'a\nb"c'}
    with pytest.raises(ValueError):
        promtext.parse_exposition("no_type_sample 1\n", strict=True)
    # value() is the UNLABELED sample only: a federated family whose
    # labeled per-replica samples precede the aggregate must not have
    # one replica misread as the process total
    doc = ("# HELP fleet_x d\n# TYPE fleet_x counter\n"
           'fleet_x{replica="a:1"} 5\nfleet_x{replica="b:2"} 7\n'
           "fleet_x 12\n")
    assert promtext.parse_exposition(doc, strict=True)["fleet_x"] \
        .value() == 12.0
    doc2 = ("# HELP fleet_y d\n# TYPE fleet_y counter\n"
            'fleet_y{replica="a:1"} 5\n')
    assert promtext.parse_exposition(doc2)["fleet_y"].value() is None


def test_promtext_merged_histogram_percentile():
    # two replicas' cumulative buckets, element-wise summed
    merged = [(10.0, 40.0), (100.0, 80.0), (float("inf"), 80.0)]
    p50 = promtext.merged_histogram_percentile(merged, 50)
    assert p50 == pytest.approx(10.0)  # rank 40 sits at bucket edge
    p99 = promtext.merged_histogram_percentile(merged, 99)
    assert 10.0 < p99 <= 100.0
    # +Inf-censored: estimate past the top finite edge reports it
    merged = [(10.0, 1.0), (float("inf"), 100.0)]
    assert promtext.merged_histogram_percentile(merged, 99) == 10.0
    assert promtext.merged_histogram_percentile([], 99) is None
    assert promtext.merged_histogram_percentile(
        [(10.0, 0.0), (float("inf"), 0.0)], 99) is None


def test_graftcheck_validator_is_the_shared_module():
    """The lint's validator and the runtime scraper must be ONE
    implementation (the extraction satellite's whole point)."""
    from tools.graftcheck.passes import stat_catalog as sc
    bad = "paddle_tpu_x{le=} 1\n"
    assert sc.validate_exposition(bad)
    assert promtext.validate_lines(bad)
    # the pass re-exports the shared regexes
    assert sc._SAMPLE_RE is promtext.SAMPLE_RE


# ---------------------------------------------------------------------------
# router federation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def two_replica_fleet():
    lg_mod = lg
    pred, shapes = lg_mod.build_synthetic(4, 8, 1)
    servers = []
    for _ in range(2):
        eng = ServingEngine(pred.clone(), workers=1)
        eng.warmup({"x": (4,)})
        servers.append(ServingServer(eng).start())
    router = Router([s.url for s in servers], poll_interval_ms=200.0,
                    autostart=False, slo_fast_s=2.0, slo_slow_s=6.0)
    rserver = RouterServer(router).start()
    router.poll_once()
    yield router, rserver, servers
    rserver.close()
    for s in servers:
        s.close()


def _post_predict(url, n=6):
    body = json.dumps(
        {"inputs": {"x": np.random.RandomState(0)
                    .rand(1, 4).tolist()}}).encode()
    for _ in range(n):
        req = urllib.request.Request(
            url + "/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200


def test_federation_two_replicas_aggregate_equals_sum(
        two_replica_fleet):
    router, rserver, servers = two_replica_fleet
    _post_predict(rserver.url)
    router.poll_once()
    # counter motion BETWEEN two sweeps is what a windowed rate needs
    _post_predict(rserver.url)
    time.sleep(0.25)
    router.poll_once()
    with urllib.request.urlopen(rserver.url + "/fleetz?window_s=30",
                                timeout=30) as r:
        fz = json.loads(r.read())
    assert fz["window_s"] == 30.0
    rids = sorted(fz["replicas"])
    assert len(rids) == 2
    for rid in rids:
        assert fz["replicas"][rid]["up"] is True
        assert fz["replicas"][rid]["scrape_age_ms"] is not None
    agg = fz["aggregate"]["counters"]["serving_http_requests"]
    per = [fz["replicas"][rid]["counters"]["serving_http_requests"]
           for rid in rids]
    assert agg["total"] == sum(per)
    assert agg["replicas"] == 2
    assert agg["rate_per_s"] is not None and agg["rate_per_s"] > 0
    # gauges aggregate sum AND max
    gq = fz["aggregate"]["gauges"]
    assert any(v["replicas"] == 2 and v["max"] is not None
               for v in gq.values())
    # merged latency histogram with interpolated percentiles
    hists = fz["aggregate"]["histograms"]
    req_ms = hists.get("serving_request_ms")
    assert req_ms and req_ms["count"] > 0 and req_ms["p99"] is not None
    # SLO/alert + autoscale + tsdb occupancy blocks ride along
    assert {a["name"] for a in fz["slo"]["alerts"]} == {
        "availability", "replica_availability", "p99"}
    assert all(a["state"] == "ok" for a in fz["slo"]["alerts"])
    assert fz["autoscale"]["wanted_replicas"] is not None
    assert fz["tsdb"]["series"] > 0
    assert fz["router"]["request_ms"]["p99"] is not None


def test_federation_labels_on_router_metrics(two_replica_fleet):
    router, rserver, servers = two_replica_fleet
    _post_predict(rserver.url, n=2)
    router.poll_once()
    with urllib.request.urlopen(rserver.url + "/metrics",
                                timeout=30) as r:
        text = r.read().decode()
    # strictly valid exposition INCLUDING the fleet families
    assert promtext.validate_lines(text) == []
    fams = promtext.parse_exposition(text, strict=True)
    fleet = fams["paddle_tpu_fleet_serving_http_requests"]
    assert fleet.type == "counter"
    labeled = [s for s in fleet.samples if "replica" in s.labels]
    bare = [s for s in fleet.samples if not s.labels]
    assert len(labeled) == 2 and len(bare) == 1
    # the unlabeled aggregate equals the sum of the labeled samples
    assert bare[0].value == sum(s.value for s in labeled)
    rids = {r_.rid for r_ in router._all()}
    assert {s.labels["replica"] for s in labeled} == rids


def test_fleetz_statusz_and_healthz_carry_alerts(two_replica_fleet):
    router, rserver, servers = two_replica_fleet
    router.poll_once()
    with urllib.request.urlopen(rserver.url + "/statusz",
                                timeout=30) as r:
        sz = json.loads(r.read())
    assert sz["fleet"]["slo"]["alerts"]
    with urllib.request.urlopen(rserver.url + "/healthz",
                                timeout=30) as r:
        hz = json.loads(r.read())
    assert hz["alerts_firing"] == []
    # federation off: /fleetz still answers, explicitly disabled
    router2 = Router([], federate=False, autostart=False)
    try:
        fz = router2.fleetz()
        assert fz["federate"] is False and fz["aggregate"] is None
    finally:
        router2.close()


def test_fleetz_window_s_rejects_nonpositive_and_nonnumeric(
        two_replica_fleet):
    """``/fleetz?window_s=`` must 400 on garbage instead of silently
    clamping: a dashboard asking for a zero/negative/NaN window would
    otherwise get numbers computed over a window it never asked for."""
    router, rserver, servers = two_replica_fleet
    for bad in ("0", "-5", "abc", "nan", "inf", "-inf"):
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                rserver.url + f"/fleetz?window_s={bad}", timeout=30)
        assert ei.value.code == 400, bad
    # an EMPTY value means "not given": the default window answers
    with urllib.request.urlopen(rserver.url + "/fleetz?window_s=",
                                timeout=30) as r:
        assert json.loads(r.read())["window_s"] == 60.0
    # a legitimate window still answers
    with urllib.request.urlopen(rserver.url + "/fleetz?window_s=12.5",
                                timeout=30) as r:
        assert json.loads(r.read())["window_s"] == 12.5


def test_usage_federation_multi_tenant_conservation(two_replica_fleet):
    """The usage observatory end to end on a live fleet THROUGH the
    router: tenant headers survive the forward hop, replicas book and
    conserve at tolerance 0, labeled per-tenant samples federate into
    per-(tenant, replica) series, /fleetz rolls them up, and the sweep
    records ``fleet_tenant_*`` dashboard series."""
    from paddle_tpu.serving import usage

    router, rserver, servers = two_replica_fleet
    tenants = ("tenant-red", "tenant-blue")
    body = json.dumps(
        {"inputs": {"x": np.random.RandomState(0)
                    .rand(1, 4).tolist()}}).encode()
    for i in range(8):
        req = urllib.request.Request(
            rserver.url + "/predict", data=body,
            headers={"Content-Type": "application/json",
                     "X-PaddleTPU-Tenant": tenants[i % 2]})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
    router.poll_once()
    time.sleep(0.25)
    router.poll_once()  # two sweeps: windowed deltas need motion
    # (1) every replica conserves at tolerance 0 and measured both
    # tenants' latency (the in-process servers share one ledger, so
    # the same conserved truth shows on each)
    for s in servers:
        with urllib.request.urlopen(s.url + "/usagez", timeout=30) as r:
            uz = json.loads(r.read())
        assert uz["enabled"] is True
        for field, c in uz["conservation"].items():
            assert c["delta"] == 0, (s.url, field, c)
        for t in tenants:
            assert uz["tenants"][t]["vector"]["requests"] > 0
            assert uz["tenants"][t]["request_ms"]["p99"] is not None
        assert uz["sketch"]["within_bound"] is True
    # (2) the replica exposition carries labeled samples + a bare
    # all-tenant total that equals their sum (the federation's anchor)
    with urllib.request.urlopen(servers[0].url + "/metrics",
                                timeout=30) as r:
        text = r.read().decode()
    assert promtext.validate_lines(text) == []
    fams = promtext.parse_exposition(text, strict=True)
    fam = fams["paddle_tpu_serving_tenant_requests"]
    labeled = [s for s in fam.samples if "tenant" in s.labels]
    bare = [s for s in fam.samples if not s.labels]
    assert len(bare) == 1 and labeled
    assert bare[0].value == sum(s.value for s in labeled)
    assert {t for t in tenants} <= {s.labels["tenant"] for s in labeled}
    # (3) /fleetz federates per-tenant rollups: totals summed across
    # replicas, reset-aware deltas measured, and the per-tenant sum
    # equals the all-tenant family total at tolerance 0
    with urllib.request.urlopen(rserver.url + "/fleetz?window_s=60",
                                timeout=30) as r:
        fz = json.loads(r.read())
    ften = fz["aggregate"]["tenants"]
    assert "requests" in ften
    for t in tenants:
        assert ften["requests"][t]["total"] > 0
        assert ften["requests"][t]["replicas"] == 2
        assert ften["requests"][t]["delta"] is not None
    fam_total = fz["aggregate"]["counters"][
        "serving_tenant_requests"]["total"]
    assert sum(v["total"] for v in ften["requests"].values()) \
        == fam_total
    # (4) the sweep recorded fleet_tenant_* series for dashboards
    for t in tenants:
        assert router._db.last(f"fleet_tenant_requests{{{t}}}") \
            is not None
    # (5) per-(tenant, replica) series exist for every replica — the
    # reset-aware evidence conservation leans on after a respawn
    for rep_ in router._all():
        for t in tenants:
            assert router._db.points(
                f"serving_tenant_requests{{{t}}}[{rep_.rid}]"), (
                rep_.rid, t)
    # stray: a malformed header books to the default tenant, never a
    # new key (the sketch's key-space guard, end to end)
    req = urllib.request.Request(
        rserver.url + "/predict", data=body,
        headers={"Content-Type": "application/json",
                 "X-PaddleTPU-Tenant": "bad tenant!!"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 200
    with urllib.request.urlopen(servers[0].url + "/usagez",
                                timeout=30) as r:
        uz = json.loads(r.read())
    assert "bad tenant!!" not in uz["tenants"]
    assert usage.default_tenant() in uz["tenants"]


def test_router_burn_alert_fires_on_dead_fleet_and_clears():
    """Deterministic alert cycle without processes: health polls
    against an unbound port fail -> replica_availability burns -> the
    alert fires once both windows agree, then clears after the
    (synthetic) recovery ages the fast window out."""
    router = Router(["http://127.0.0.1:9"], poll_interval_ms=50.0,
                    autostart=False, slo_fast_s=0.4, slo_slow_s=1.0,
                    slo_burn_threshold=2.0)
    try:
        deadline = time.monotonic() + 10.0
        fired = False
        while time.monotonic() < deadline:
            router.poll_once()
            if router.burn_monitor.firing():
                fired = True
                break
            time.sleep(0.05)
        assert fired, "replica_availability alert never fired"
        assert "replica_availability" in router.burn_monitor.firing()
        # recovery: stop failing (no more polls), feed clean poll
        # counters so the fast window ages the failures out
        db = router._db
        t = time.monotonic()
        with router._lock:
            n = dict(router._n)
        for i in range(1, 30):
            db.record("router_polls_total",
                      n["health_polls"] + 10 * i, ts=t + i * 0.1)
            db.record("router_poll_failures_total",
                      n["health_poll_failures"], ts=t + i * 0.1)
        st = router.burn_monitor.evaluate(now=t + 3.0)
        by_name = {a["name"]: a for a in st["alerts"]}
        assert by_name["replica_availability"]["state"] == "ok"
    finally:
        router.close()


# ---------------------------------------------------------------------------
# TTFT / inter-token timelines
# ---------------------------------------------------------------------------

def test_ttft_spans_admit_to_first_token_through_chunked_prefill():
    """Structural TTFT contract: with chunked prefill the first token
    arrives only after EVERY chunk paid out (one per scheduler
    iteration), and the TTFT histogram's measurement covers that whole
    span — claim, each chunk, and any interleaved decode work."""
    eng = GenerationEngine(TINY_LLAMA, num_slots=2, max_seq_len=64,
                           max_new_tokens=6, attn_impl="xla", seed=0,
                           page_tokens=8, prefill_chunk=8,
                           prefix_reuse=False)
    try:
        prompt = np.arange(1, 25)  # 24 tokens = 3 chunks of 8
        res = eng.submit(prompt, 4).result(120)
        tl = res["timeline"]
        chunks = [e for e in tl["events"] if e["event"] == "chunk"]
        assert len(chunks) == 3
        assert [c["base"] for c in chunks] == [0, 8, 16]
        # first token strictly after the last chunk
        assert tl["token_ms"][0] >= chunks[-1]["at_ms"]
        assert res["ttft_ms"] == tl["token_ms"][0] == tl["ttft_ms"]
        # ttft >= prefill time is the "including interleave" claim:
        # admission-to-first-token, not prefill-only
        assert res["ttft_ms"] >= res["prefill_ms"] - 1e-6
        assert res["ttft_ms"] >= res["queue_wait_ms"] - 1e-6
        st = eng.stats()
        assert st["ttft_ms"]["count"] == 1
        assert st["inter_token_ms"]["count"] == len(res["tokens"]) - 1
        # inter-token gaps match the timeline's own arithmetic
        gaps = [round(b - a, 3) for a, b in
                zip(tl["token_ms"], tl["token_ms"][1:])]
        assert tl["inter_token_ms"]["max"] == pytest.approx(
            max(gaps), abs=1e-3)
    finally:
        eng.close()


def test_ttft_exemplar_trace_ids_resolve_in_tracez():
    eng = GenerationEngine(TINY_LLAMA, num_slots=2, max_seq_len=64,
                           max_new_tokens=6, attn_impl="xla", seed=0)
    try:
        results = [eng.submit(np.arange(1, 6 + i), 3).result(120)
                   for i in range(3)]
        tz = eng.tracez()
        known = {r["trace_id"] for r in tz["recent"]} \
            | {r["trace_id"] for r in tz["slowest"]}
        assert {r["trace_id"] for r in results} <= known
        assert tz["ttft_exemplars"]
        for ex in tz["ttft_exemplars"]:
            assert ex["trace_id"] in known
        # every stored record carries its timeline
        assert all(r["timeline"] is not None for r in tz["recent"])
        # the sequence spans share the request trace ids
        seq = {s.trace_id for s in telemetry.get_spans()
               if s.name == "generation/sequence"}
        assert {r["trace_id"] for r in results} <= seq
    finally:
        eng.close()


def test_ttft_histograms_on_live_metrics_and_stream(tmp_path):
    """/metrics exposes serving_ttft_ms / serving_inter_token_ms after
    traffic; the streaming /generate contract delivers per-token lines
    + a final summary, and the http loadgen measures client TTFT."""
    pred, shapes = lg.build_synthetic(4, 8, 1)
    eng = ServingEngine(pred, workers=1)
    gen = GenerationEngine(TINY_LLAMA, num_slots=2, max_seq_len=64,
                           max_new_tokens=8, attn_impl="xla", seed=0,
                           deadline_ms=60000.0)
    eng.attach_generator(gen)
    gen.warmup()  # cold compiles must not deadline-shed the loop
    srv = ServingServer(eng).start()
    try:
        mk = lg.prompt_maker(64, 4, 8, 4.0, 6)
        rep = lg.run_closed_loop_generate_http(srv.url, mk, 6, 2,
                                               stream=True)
        assert rep["ok"] == 6 and rep["failed"] == 0
        assert rep["ttft_ms"]["count"] == 6
        assert rep["inter_token_ms"]["count"] > 0
        with urllib.request.urlopen(srv.url + "/metrics",
                                    timeout=30) as r:
            text = r.read().decode()
        assert promtext.validate_lines(text) == []
        fams = promtext.parse_exposition(text)
        assert fams["paddle_tpu_serving_ttft_ms"].histogram_count() \
            >= 6
        assert fams["paddle_tpu_serving_inter_token_ms"] \
            .histogram_count() > 0
        # exemplars ride the histogram objects into /tracez
        with urllib.request.urlopen(srv.url + "/tracez",
                                    timeout=30) as r:
            tz = json.loads(r.read())
        gen_tz = tz["generation"]
        assert gen_tz["ttft_exemplars"]
        known = {rec["trace_id"] for rec in gen_tz["recent"]} \
            | {rec["trace_id"] for rec in gen_tz["slowest"]}
        assert gen_tz["ttft_exemplars"][0]["trace_id"] in known
        # check_slo TTFT/ITL bounds: generous passes, absent fails
        slo = lg.check_slo(rep, ttft_ms=60000.0, itl_ms=60000.0)
        assert slo["ok"], slo
        slo = lg.check_slo(rep, ttft_ms=0.0001)
        assert not slo["ok"] and "TTFT" in slo["violations"][0]
        plain = lg.run_closed_loop_generate_http(srv.url, mk, 2, 1,
                                                 stream=False)
        slo = lg.check_slo(plain, ttft_ms=60000.0)
        assert not slo["ok"]  # unmeasurable != vacuous pass
    finally:
        srv.close()


def test_stream_through_router_is_not_buffered():
    """The router's streaming passthrough must deliver token lines AS
    THEY ARE GENERATED: with decode steps slowed to ~40 ms, a client
    measuring through the router must see TTFT well below the total
    and inter-token gaps near the injected delay — a buffered forward
    (the route() path's read-to-EOF) would show ttft ≈ total and
    gaps ≈ 0."""
    from paddle_tpu import fault

    pred, shapes = lg.build_synthetic(4, 8, 1)
    eng = ServingEngine(pred, workers=1)
    gen = GenerationEngine(TINY_LLAMA, num_slots=2, max_seq_len=64,
                           max_new_tokens=16, attn_impl="xla", seed=0,
                           deadline_ms=60000.0)
    eng.attach_generator(gen)
    gen.warmup()
    srv = ServingServer(eng).start()
    router = Router([srv.url], poll_interval_ms=200.0, autostart=False)
    rserver = RouterServer(router).start()
    router.poll_once()
    try:
        fault.configure("decode_step:delay:40~1.0")
        body = json.dumps({"prompt": list(range(1, 9)),
                           "max_new_tokens": 10,
                           "stream": True}).encode()
        outcome, ntok, ttft, gaps = lg._http_generate_stream(
            rserver.url + "/generate", body, 120.0)
        assert outcome == "ok" and ntok == 10
        total = ttft + sum(gaps)
        # 9 inter-token gaps of >= 40ms each: a buffered forward would
        # put all of that into ttft and none into the gaps
        assert sum(1 for g in gaps if g >= 30.0) >= 7, gaps
        assert ttft < total * 0.5, (ttft, total)
        # the router booked it as a routed 200 with a latency sample
        # (poll: the client returns on the final NDJSON line, a beat
        # before the router's post-stream accounting runs)
        deadline = time.monotonic() + 5.0
        while router._db.last("router_request_ms") is None \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router._db.last("router_request_ms") is not None
        fault.configure("")
        # containment parity with route(): an injected connect-level
        # failure on the stream path strikes and (single replica, no
        # alternate) surfaces the explicit no_ready 503 — never a hung
        # connection
        fault.configure("router_forward:fail@1")
        outcome, ntok, _, _ = lg._http_generate_stream(
            rserver.url + "/generate", body, 30.0)
        assert outcome == "failed" and ntok == 0
        fault.configure("")
        # a spent deadline sheds BEFORE any forward, stream or not
        req = urllib.request.Request(
            rserver.url + "/generate", data=body,
            headers={"Content-Type": "application/json",
                     "X-PaddleTPU-Deadline-Ms": "0.0"})
        try:
            urllib.request.urlopen(req, timeout=30)
            assert False, "expected 503 deadline shed"
        except urllib.error.HTTPError as e:
            doc = json.loads(e.read())
            assert e.code == 503 and doc["reason"] == "deadline", doc
    finally:
        fault.configure("")
        rserver.close()
        srv.close()


def test_timeline_off_with_telemetry_off():
    eng = GenerationEngine(TINY_LLAMA, num_slots=1, max_seq_len=64,
                           max_new_tokens=4, attn_impl="xla", seed=0)
    try:
        pt.set_flags({"FLAGS_telemetry": 0})
        res = eng.generate(np.arange(1, 6), 3)
        assert "timeline" not in res
        assert eng.stats()["ttft_ms"]["count"] == 0
        assert eng.tracez()["recent"] == []
        # the per-request switch forces it back on without telemetry
        res = eng.submit(np.arange(1, 6), 3, timeline=True).result(120)
        assert res["timeline"]["token_ms"]
    finally:
        pt.set_flags({"FLAGS_telemetry": 1})
        eng.close()
