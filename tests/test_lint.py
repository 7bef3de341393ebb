"""Tier-1 lint gates (tools/graftcheck + the check_no_bare_pass /
check_stat_catalog CLI shims).

Static-analysis hygiene: the full graftcheck suite (lock-discipline
race detection, lock-order cycles, resource pairing, donation safety,
flag hygiene, exception policy, stat catalog) must scan the real tree
clean — with every intentional exception reason-annotated in
tools/graftcheck/baseline.txt — inside a wall-clock budget, so the
gate stays cheap enough to run on every change.

Robustness hygiene: no `except ...: pass` in paddle_tpu/ may silently
swallow a failure — handlers must log, bump a monitor stat, or carry an
explicit `# ok: <reason>` waiver.

Observability hygiene: every literal metric name used through the
monitor / telemetry APIs in paddle_tpu/ must appear (backtick-quoted)
in the README stat catalog, so metric names can't drift undocumented
out from under the dashboards reading them — and the serving
``/metrics`` endpoint's claim of strict Prometheus text exposition is
checked against a LIVE scrape (HELP/TYPE per family, name charset, no
duplicate series), not just against fixtures.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "tools", "check_no_bare_pass.py")
CATALOG = os.path.join(REPO, "tools", "check_stat_catalog.py")


def test_graftcheck_full_suite_clean_within_budget():
    """The whole static-analysis suite over paddle_tpu/ + tools/ exits
    0 (zero violations; waivers carry reasons in the baseline) and the
    full repo scan stays under 10 s wall on this host — a lint gate
    slow enough to skip is a lint gate that gets skipped.  --json is
    asserted stable/sorted in tests/test_graftcheck.py."""
    import time

    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    wall = time.monotonic() - t0
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    import json
    payload = json.loads(r.stdout)
    assert payload["ok"] is True
    assert payload["violations"] == []
    assert payload["files_scanned"] > 150  # the scan actually scanned
    assert wall < 10.0, f"graftcheck full scan took {wall:.1f}s (>10s)"


def _load_catalog_tool():
    spec = importlib.util.spec_from_file_location("check_stat_catalog",
                                                  CATALOG)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_paddle_tpu_has_no_silent_except_pass():
    r = subprocess.run(
        [sys.executable, LINT, os.path.join(REPO, "paddle_tpu")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_lint_catches_violation_and_honors_waiver(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""\
        try:
            x = 1
        except Exception:
            pass
    """))
    r = subprocess.run([sys.executable, LINT, str(bad)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "bad.py:3" in r.stdout

    good = tmp_path / "good.py"
    good.write_text(textwrap.dedent("""\
        try:
            x = 1
        except StopIteration:
            pass  # ok: generator drained
        try:
            y = 2
        except Exception:
            log("boom")
            pass
    """))
    r = subprocess.run([sys.executable, LINT, str(good)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout


def test_every_metric_name_is_in_readme_catalog():
    r = subprocess.run(
        [sys.executable, CATALOG, os.path.join(REPO, "paddle_tpu")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_stat_catalog_lint_catches_undocumented_name(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text(textwrap.dedent("""\
        from paddle_tpu.monitor import stat_add
        from paddle_tpu import telemetry

        def f():
            stat_add("documented_stat")
            stat_add("totally_undocumented_stat")
            telemetry.gauge_set("undocumented_gauge", 1.0)
            stat_add(f"dynamic_{f.__name__}")  # non-literal: out of scope
    """))
    readme = tmp_path / "README.md"
    readme.write_text("catalog: `documented_stat` only\n")
    r = subprocess.run(
        [sys.executable, CATALOG, str(bad), "--readme", str(readme)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout
    assert "totally_undocumented_stat" in r.stdout
    assert "undocumented_gauge" in r.stdout
    assert "'documented_stat'" not in r.stdout  # documented: no finding
    assert "dynamic_" not in r.stdout

    readme.write_text("`documented_stat` `totally_undocumented_stat` "
                      "`undocumented_gauge`\n")
    r = subprocess.run(
        [sys.executable, CATALOG, str(bad), "--readme", str(readme)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout


def test_every_serving_flag_is_documented_in_readme():
    """Every registered serving-plane flag — `FLAGS_serving_*` plus
    the fleet tier's `FLAGS_router_*` / `FLAGS_fleet_*` — must appear
    backtick-quoted in the README flag tables: a serving knob that
    isn't documented can't be operated, and the router flags change
    routing/ejection behavior and the autoscaling signal, so they
    must never drift undocumented."""
    from paddle_tpu import flags

    names = sorted(n for n in flags.all_flags()
                   if n.startswith(("FLAGS_serving", "FLAGS_router",
                                    "FLAGS_fleet")))
    assert "FLAGS_serving_mesh" in names  # the lint must see the new
    assert "FLAGS_serving_group_degraded_after" in names  # sharded set
    assert "FLAGS_router_slo_p99_ms" in names  # ...and the fleet set
    assert "FLAGS_fleet_max_restarts" in names
    # ...and the fault-containment set (bisection, deadlines,
    # watchdogs): these change failure semantics, the worst kind of
    # knob to leave undocumented
    assert "FLAGS_serving_bisect" in names
    assert "FLAGS_serving_poison_value" in names
    assert "FLAGS_serving_worker_stuck_ms" in names
    assert "FLAGS_router_forward_timeout_ms" in names
    assert "FLAGS_router_default_deadline_ms" in names
    assert "FLAGS_fleet_liveness_timeout_ms" in names
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    missing = [n for n in names if f"`{n}`" not in readme]
    assert not missing, (f"serving flags missing from the README flag "
                         f"tables: {missing}")


# ---------------------------------------------------------------------------
# strict Prometheus exposition: validator unit + live /metrics scrape
# ---------------------------------------------------------------------------

def test_exposition_validator_catches_violations(tmp_path):
    csc = _load_catalog_tool()
    good = ("# HELP m_total docs\n# TYPE m_total counter\nm_total 3\n"
            "# HELP h_ms docs\n# TYPE h_ms histogram\n"
            'h_ms_bucket{le="1.0"} 1\nh_ms_bucket{le="+Inf"} 2\n'
            "h_ms_sum 4.5\nh_ms_count 2\n")
    assert csc.validate_exposition(good) == []

    cases = {
        "m 1\n": "no preceding # TYPE",
        "# TYPE m counter\nm 1\n": "no # HELP",
        "# HELP m d\n# TYPE m counter\nm 1\nm 1\n": "duplicate series",
        "# HELP m d\n# TYPE m counter\n# TYPE m counter\nm 1\n":
            "duplicate # TYPE",
        "# HELP m d\n# TYPE m sometype\nm 1\n": "not one of",
        "# HELP 1bad d\n# TYPE 1bad counter\n": "bad metric name",
        "# HELP m d\n# TYPE m counter\nm  1\n": "malformed sample",
        "# HELP m d\n# TYPE m counter\nm{le=}\n": "malformed sample",
        "# HELP h d\n# TYPE h histogram\n"
        'h_bucket{le="1.0"} 1\nh_sum 1\nh_count 1\n': "+Inf",
        "m 1\n# HELP m d\n# TYPE m counter\n": "after its samples",
    }
    for text, needle in cases.items():
        errs = csc.validate_exposition(text)
        assert errs and any(needle in e for e in errs), (text, errs)

    # the CLI face of the same validator (what CI scripts call)
    bad_file = tmp_path / "bad.prom"
    bad_file.write_text("# TYPE m counter\nm 1\nm 1\n")
    r = subprocess.run(
        [sys.executable, CATALOG, "--validate-prom", str(bad_file)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1 and "duplicate series" in r.stdout
    # shared violation format: findings carry file:line provenance
    assert f"{bad_file}:3 prom-format" in r.stdout
    # family-level findings anchor to the family's # TYPE line instead
    # of printing a bare metric name
    sum_file = tmp_path / "nosum.prom"
    sum_file.write_text("# HELP h d\n# TYPE h histogram\n"
                        'h_bucket{le="+Inf"} 1\nh_count 1\n')
    r = subprocess.run(
        [sys.executable, CATALOG, "--validate-prom", str(sum_file)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert f"{sum_file}:2 prom-format histogram h is missing h_sum" \
        in r.stdout
    good_file = tmp_path / "good.prom"
    good_file.write_text(good)
    r = subprocess.run(
        [sys.executable, CATALOG, "--validate-prom", str(good_file)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout


def test_live_metrics_scrape_is_strict_prometheus():
    """Scrape a LIVE serving /metrics endpoint and hold it to the
    strict exposition format — the contract a real Prometheus scraper
    relies on, validated against the running registry rather than a
    snapshot fixture."""
    import paddle_tpu as pt
    from paddle_tpu.serving import ServingEngine, serve

    spec = importlib.util.spec_from_file_location(
        "serving_loadgen", os.path.join(REPO, "tools",
                                        "serving_loadgen.py"))
    lg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lg)

    pt.set_flags({"FLAGS_telemetry": True})
    predictor, shapes = lg.build_synthetic(feat=4, hidden=8, depth=1,
                                           classes=2)
    eng = ServingEngine(predictor, workers=1, max_batch=2,
                        max_delay_ms=1.0, deadline_ms=60000)
    srv = serve(eng)
    try:
        make_feed = lg.feed_maker(shapes, rows=1)
        # traffic first, so the scrape covers the serving histograms
        outcome, _version = lg._http_predict(
            srv.url + "/predict",
            lg._encode_bodies(make_feed, 1)[0], 60.0)
        assert outcome == "ok"
        with urllib.request.urlopen(srv.url + "/metrics",
                                    timeout=30) as r:
            assert r.status == 200
            assert "version=0.0.4" in r.headers["Content-Type"]
            text = r.read().decode()
    finally:
        srv.close()
    csc = _load_catalog_tool()
    errs = csc.validate_exposition(text)
    assert errs == [], errs[:10]
    assert "paddle_tpu_serving_http_requests" in text
    assert "paddle_tpu_serving_request_ms_count" in text
