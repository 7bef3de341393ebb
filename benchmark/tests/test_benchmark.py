"""Tests of the benchmark itself (not part of tier-1):

    python -m pytest benchmark/tests -q

The trace reduction against a small recorded trace, the traffic
generator's seed invariance, the operation and byte counts against hand
arithmetic, ``BENCHMARK.json`` against the contract's rules, and a
``--rehearse`` run of every cell at toy size on the CPU.
"""
import collections
import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, ROOT) if p not in sys.path]

import ops_bytes  # noqa: E402
import traffic    # noqa: E402
import xplane     # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- trace reduction ----------------------------------------------------------

TRACE = os.path.join(BENCH, "tests", "data", "small.xplane.pb")


def test_trace_reduction_on_the_recorded_trace():
    """``small.xplane.pb``: a TPU v5e ran ``jit_step`` (two matmuls and a
    tanh) four times with 10 ms sleeps between (PR 23, probe).  Each run
    is one fusion of about 13 us; the device is idle for nearly all of
    the 33.7 ms between the first and the last operation, and the host
    was in ``time.sleep`` inside the ``probe_sleep`` annotation."""
    raw = xplane.load(TRACE)
    assert list(raw["devices"]) == ["/device:TPU:0"]
    ops = raw["devices"]["/device:TPU:0"]["ops"]
    assert collections.Counter(xplane.op_name(n) for _, _, n in ops) == {
        "fusion": 4, "copy-start": 4, "copy-done": 4}
    r = xplane.reduce(raw)
    lo, hi = r["window"]
    # busy by brute force: mark every nanosecond some operation covers
    covered = set()
    for s, e, _ in ops:
        covered.update(range(round(max(s, lo) * 1e9),
                             round(min(e, hi) * 1e9)))
    assert abs(r["busy_s"] - len(covered) * 1e-9) < 2e-8
    assert 50e-6 < r["busy_s"] < 56e-6
    assert abs(r["window_s"] - 33.672e-3) < 1e-6
    assert r["device_ops"][0][0] == "fusion"
    assert abs(r["device_ops"][0][1] - 4 * 13.28e-6) < 1e-6
    # the sleeping call waits; the annotation around it owns the gap
    assert r["idle_gaps"][0][0] == "probe_sleep"
    assert abs(r["idle_gaps"][0][1] - (r["window_s"] - r["busy_s"])) < 1e-6
    assert r["collective_s"] == 0.0
    (name, runs), = r["modules"].items()
    assert name.startswith("jit_step(") and len(runs) == 3


def test_interval_arithmetic():
    assert xplane.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6)]) == [
        (0, 1), (2, 4), (6, 10)]
    assert xplane.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]
    assert xplane.total([(0, 4), (6, 9)]) == 7
    assert xplane.op_name("%all-reduce.3 = f32[8]{0} all-reduce(...)") \
        == "all-reduce.3"
    # an exposed collective is the part no compute overlaps
    gaps = xplane.charge_gaps([(0.0, 1.0), (2.0, 2.1), (3.0, 3.5)],
                              [(-1.0, 5.0, "loop"), (0.1, 0.9, "zeros"),
                               (2.0, 2.02, "tiny"),
                               (3.0, 3.5, "$threading.py:323 wait")])
    assert gaps == {"zeros": 1.0, "loop": pytest.approx(0.6)}


# -- traffic ------------------------------------------------------------------

@pytest.mark.parametrize("mix_name", ["chat-steady", "longprompt-pool"])
def test_traffic_is_the_same_multiset_under_every_seed(mix_name):
    """Same lengths and gaps under three seeds, in another order; in a
    closed loop, and in an open loop cut into blocks, every block of the
    stream is the same multiset."""
    mix = traffic.load_mix(mix_name)
    seeds = (0, 7, 3000000019)

    def schedule(seed):
        if mix["loop"] == "open":
            return traffic.open_schedule(mix, seed, 40.0)["requests"]
        return traffic.closed_schedule(
            mix, seed, mix["blocks"] * mix["block"])["requests"]

    runs = [schedule(s) for s in seeds]
    for key in ("prompt_len", "max_new_tokens"):
        bags = [sorted(r[key] for r in run) for run in runs]
        assert bags[0] == bags[1] == bags[2]
        orders = [[r[key] for r in run] for run in runs]
        assert orders[0] != orders[1] != orders[2]
        if mix["loop"] == "closed":
            # the window holds whole blocks: each is the same work
            n = mix["block"]
            blocks = {tuple(sorted(o[i:i + n])) for o in orders
                      for i in range(0, len(o), n)}
            assert len(blocks) == 1
    if mix["loop"] == "open":
        gaps = []
        for run in runs:
            win = [r["due"] for r in run if r["due"] >= mix["warm_s"]]
            assert len(win) == round(mix["rate_rps"] * 40.0)
            assert win[0] == mix["warm_s"] and win[-1] < mix["warm_s"] + 40
            gaps.append(sorted(round(b - a, 9)
                               for a, b in zip(win, win[1:])))
        # all gaps but the last (which closes the window) are offered
        assert [len(g) for g in gaps] == [len(gaps[0])] * 3
        if mix.get("block_s"):
            # every block of the window is the same work, under every seed
            w0, bs = mix["warm_s"], mix["block_s"]
            blocks = {tuple(sorted(
                (r["prompt_len"] for r in run
                 if w0 + k * bs <= r["due"] < w0 + (k + 1) * bs)))
                for run in runs for k in range(int(40 // bs))}
            assert len(blocks) == 1
            assert len(blocks.pop()) == round(mix["rate_rps"] * bs)
        e = mix["engine"]
        assert max(r["prompt_len"] + r["max_new_tokens"]
                   for r in runs[0]) <= e["max_seq_len"]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert all(lo <= r["prompt_len"] <= hi for r in runs[0])
    assert max(r["prompt_len"] for r in runs[0]) \
        <= max(mix["engine"]["prefill_buckets"])
    assert traffic.token_ids(5, 1, 9, 100) == traffic.token_ids(5, 1, 9, 100)
    assert traffic.token_ids(5, 1, 9, 100) != traffic.token_ids(6, 1, 9, 100)


def test_open_loop_mixes_offer_seven_to_eight_tenths_of_their_knee():
    """``rate_rps`` and ``knee_rps`` are written by hand after a sweep
    (``sweep.py``): they may not drift apart."""
    open_mixes = []
    for name in sorted(os.listdir(os.path.join(BENCH, "traffic"))):
        mix = traffic.load_mix(os.path.splitext(name)[0])
        if mix.get("loop") == "open":
            open_mixes.append(name)
            share = mix["rate_rps"] / mix["knee_rps"]
            assert 0.7 <= share <= 0.8, (name, share)
            assert mix["knee_note"]
    assert "chat-steady.json" in open_mixes


def test_stalled_share_and_gap_ladder():
    """1000 gaps: 890 plain ones of 19-20 ms and 110 that carried a
    prefill, in the four plateaus the rungs make (22 / 33 / 34 / 21 of
    them at 40 / 70 / 104 / 166 ms).  Twice the median is 39 ms, so
    11% are stalled; the widest rung's plateau holds the top 2.1%, so
    the 98.5th to 99.5th percentiles lie in it, off an edge.  With a
    third as many stalled gaps it holds 0.7%, the 99th falls on the
    border below it and the test says so."""
    import harness
    import serve
    stalled = [40.0] * 22 + [70.0] * 33 + [104.0] * 34 + [166.0] * 21
    gaps = [19.0 + 0.001 * i for i in range(890)] + stalled
    assert harness.share_over(gaps, 2.0) == pytest.approx(11.0)
    reader = harness.load_module("readers", "client_share_over")
    with open(os.path.join(BENCH, "metrics",
                           "stalled_gap_share_pct.chat.json")) as f:
        args = json.load(f)["args"]
    assert reader.read({"clients": {"itl": gaps}}, **args) \
        == pytest.approx(11.0)
    assert reader.read({"clients": {}}, **args) is None
    got = serve.gap_ladder(gaps)
    assert got["ladder_ms"]["p95"] == 104.0
    assert got["ladder_ms"]["p99"] == got["ladder_ms"]["p99.5"] == 166.0
    assert got["max_ms"] == 166.0 and got["half_point_off_gate"] == 0.0
    assert got["stalled_gap_share_pct"] == 11.0 and got["off_edge"]
    few = stalled[::3]
    edge = serve.gap_ladder(
        [19.0 + 0.001 * i for i in range(1000 - len(few))] + few)
    assert edge["stalled_gap_share_pct"] == pytest.approx(3.7)
    assert edge["half_point_off_gate"] > 0.03 and not edge["off_edge"]


def test_train_batches_depend_on_the_seed_only():
    import harness
    make = harness.load_module("builders", "bert_mlm").host_batches
    cfg = {"vocab_size": 50, "recipe": {"masked_share": 0.1875}}
    a = make(3000000019, cfg, 2, 16, 2)
    b = make(3000000019, cfg, 2, 16, 2)
    c = make(1, cfg, 2, 16, 2)
    assert all((x["input_ids"] == y["input_ids"]).all()
               for x, y in zip(a, b))
    assert not (a[0]["input_ids"] == c[0]["input_ids"]).all()
    pos = a[0]["mlm_positions"]
    assert pos.shape == (2, 3) and (pos[:, 1:] > pos[:, :-1]).all()


# -- operations and bytes -------------------------------------------------------

def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_flops_by_hand():
    """H 768, I 3072, S 512, 12 layers, 77 predictions.  Per token and
    layer 8 H^2 + 4 H S + 4 H I = 4 718 592 + 1 572 864 + 9 437 184 =
    15 728 640; times 12 layers and 512 tokens = 96 636 764 160.  Head:
    77 x (2 H^2 + 2 H V) = 77 x 48 061 440 = 3 700 730 880.  Forward
    100 337 495 040, training three times that."""
    cfg = _cfg("bert-base-mlm")
    assert ops_bytes.bert_train_flops_per_sequence(cfg, 512, 77) \
        == 3 * 100_337_495_040
    import bench
    assert ops_bytes.bert_train_flops_per_sequence(cfg, 512, 77) \
        == bench.bert_train_flops_per_sample(512, 30522, 768, 12, 3072, 77)


def test_mistral_counts_by_hand():
    """Hidden 4096, 32 heads of 128 over 8 KV heads, FFN 14336, 8 layers,
    float32.  A layer: QKV 4096 x 6144 + out 4096^2 + gate and up
    2 x 4096 x 14336 + down 14336 x 4096 = 25 165 824 + 16 777 216 +
    117 440 512 + 58 720 256 = 218 103 808 parameters.  K and V of one
    position: 2 x 8 x 128 x 4 B x 8 layers = 65 536 B."""
    cfg = _cfg("mistral-7b-v0.1")
    assert ops_bytes.mistral_layer_params(cfg) == 218_103_808
    assert ops_bytes.mistral_kv_bytes_per_token(cfg, 4) == 65_536
    weights = 4 * (8 * (218_103_808 + 8192) + 4096 + 4096 * 32000)
    assert ops_bytes.mistral_weight_bytes(cfg, 4) == weights
    assert ops_bytes.mistral_decode_step_bytes(cfg, 1000, 4) \
        == weights + 65_536_000
    # prefill of 1000 tokens: 8 x (2 x 218 103 808 x 1000
    #   + 2 x 1000^2 x 4096) + 2 x 4096 x 32000
    assert ops_bytes.mistral_prefill_flops(cfg, 1000) \
        == 8 * (436_207_616_000 + 8_192_000_000) + 262_144_000


def test_roofline_reader_takes_its_arithmetic_from_the_cells_files():
    """A fake traced window: the decode module ran 3 times for 50 ms, one
    prefill module once for 400 ms after a span of 2000 tokens; 10 pages
    of 16 tokens live.  The shares are the named function over the named
    peak over that time."""
    import types

    import harness
    reader = harness.load_module("readers", "roofline")
    cfg = _cfg("mistral-7b-v0.1")
    peaks = harness.peaks_for("TPU v5 lite")
    trace = {"to_monotonic": 100.0, "modules": {
        "jit_decode": [(1.0, 1.05), (1.1, 1.15), (1.2, 1.25)],
        "jit_prefill": [(2.0, 2.4)]}}
    span = types.SimpleNamespace(name="generation/prefill", start=101.9,
                                 attrs={"tokens": 2000})
    ctx = {"trace": trace, "cfg": cfg, "trace_spans": [span],
           "gauges": [{"serving_kv_pages_live": 10}],
           "run": types.SimpleNamespace(peaks=peaks),
           "engine": types.SimpleNamespace(page_tokens=16)}

    def args(name, cell):
        reader_name, found = harness.Cell(cell).reader_of(name)
        assert reader_name == "roofline"
        return found

    got = reader.read(ctx, **args("decode_step_roofline.chat",
                                  "mistral7b-chat"))
    want = 100 * ops_bytes.mistral_decode_step_bytes(cfg, 160, 4) \
        / 819e9 / 0.05
    assert got == pytest.approx(want)
    # the family's file gives the pairing and the peak, the cell's
    # configuration the function that counts its work
    got = reader.read(ctx, **args("prefill_roofline.pool",
                                  "mistral7b-longprompt"))
    want = 100 * ops_bytes.mistral_prefill_flops(cfg, 2000) / 197e12 / 0.4
    assert got == pytest.approx(want)


def test_closed_loop_window_is_cut_at_block_ends():
    """``loadgen.py`` against a stub that streams each request's tokens
    10 ms apart, one request at a time: the window opens on the first
    token of the last request of block ``warm_blocks`` and closes on the
    first block end ``seconds`` or more later; the child then stops."""
    import http.server
    import threading

    import loadgen

    gate = threading.Lock()

    class Stub(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.0"

        def do_POST(self):
            body = json.loads(self.rfile.read(
                int(self.headers["Content-Length"])))
            self.send_response(200)
            self.end_headers()
            with gate:
                toks = list(range(body["max_new_tokens"]))
                for t in toks:
                    self.wfile.write(json.dumps({"token": t}).encode()
                                     + b"\n")
                    self.wfile.flush()
                    time.sleep(0.01)
            self.wfile.write(json.dumps(
                {"done": True, "tokens": toks, "finish": "length"}).encode()
                + b"\n")

        def log_message(self, *a):
            pass

    import time
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        t0 = time.monotonic() + 0.1
        plan = {"url": f"http://127.0.0.1:{server.server_port}",
                "loop": "closed", "t0": t0, "timeout_s": 20.0, "workers": 3,
                "block": 4, "warm_blocks": 2, "seconds": 0.5, "tail_s": 0.1,
                "t_stop": t0 + 30.0,
                "requests": [{"prompt": [1, 2], "max_new_tokens": 3}] * 8}
        marks = []
        real_print = loadgen.print if hasattr(loadgen, "print") else print
        loadgen.print = lambda line, **kw: marks.append(json.loads(line))
        try:
            records = loadgen.run(plan)
        finally:
            loadgen.print = real_print
    finally:
        server.shutdown()
    by_name = {m["mark"]: m["t"] for m in marks}
    assert list(by_name) == ["t_open", "t_close"]
    first = {r["index"]: r["arrivals"][0] for r in records}
    assert by_name["t_open"] == first[7]          # block 2 ends at index 7
    ends = [first[i] for i in sorted(first) if (i + 1) % 4 == 0]
    assert by_name["t_close"] == min(
        t for t in ends if t - by_name["t_open"] >= 0.5)
    # whole blocks between the edges: 4 requests of 2 + 3 tokens each
    inside = [r for r in records
              if by_name["t_open"] < r["arrivals"][0] <= by_name["t_close"]]
    assert len(inside) % 4 == 0 and len(inside) >= 4
    # the child stopped taking requests soon after the window closed
    assert max(r["due"] for r in records) < by_name["t_close"] + 0.1 + 0.05


# -- BENCHMARK.json against the contract ----------------------------------------

def test_spec_names_units_and_lengths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    names = collections.Counter()
    for group, keys in (
            ("configs", {"name", "source", "file", "reduced", "why"}),
            ("workloads", {"name", "config", "traffic", "chips", "why"}),
            ("end_to_end", {"name", "unit", "better", "bound", "source"}),
            ("per_layer", {"name", "unit", "better", "source", "layer",
                           "moves"})):
        for entry in SPEC[group]:
            assert set(entry) - {"workloads"} == keys, entry
            assert NAME.match(entry["name"]), entry["name"]
            names[(group in ("end_to_end", "per_layer"), entry["name"])] += 1
            for key in ("why", "layer", "source"):
                if key in entry and group in ("configs", "workloads",
                                              "per_layer"):
                    text = entry[key]
                    assert 1 <= len(text) <= 200, (len(text), text)
                    assert "\n" not in text and "\t" not in text
    assert max(names.values()) == 1
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    pairs = collections.Counter((w["config"], w["traffic"])
                                for w in SPEC["workloads"])
    assert max(pairs.values()) == 1
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(CELLS) // 4)
    for c in SPEC["configs"]:
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        body = _cfg(c["name"])
        assert body["reduced"] == c["reduced"]
        for key in ("source", "reduced", "assumed", "deployment", "why",
                    "check_tolerance", "builder"):
            assert key in body, (c["name"], key)
        assert all(NAME.match(k) for k in c["reduced"])
        assert os.path.exists(os.path.join(
            BENCH, "builders", body["builder"] + ".py"))
        # the file holds the published value of every key but the cuts
        assert not set(body.get("published", {})) - set(c["reduced"])


def test_every_cell_reports_what_its_metrics_move():
    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    layers = collections.defaultdict(set)
    for cell in CELLS:
        assert sum(reports(m, cell) for m in SPEC["end_to_end"]) >= 2
        assert any(reports(m, cell) for m in SPEC["per_layer"])
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", CELLS):
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert spec["unit"] == m["unit"]
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        layers[m["layer"]].add(m["name"])
    for w in SPEC["workloads"]:
        mix = traffic.load_mix(w["traffic"])
        assert os.path.exists(os.path.join(BENCH, mix["driver"] + ".py"))
        assert os.path.exists(os.path.join(
            BENCH, "reference", w["config"] + ".py"))


# -- every cell, rehearsed on the CPU -------------------------------------------

DEVICE_NAMES = {m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_reaches_its_last_line(cell):
    chips = next(w["chips"] for w in SPEC["workloads"] if w["name"] == cell)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3000000019", "--seconds", "3", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["counts"]["compiles_in_window"] == 0
    # the memory peak's rule needs the programs' temporaries: seen
    assert last["largest_temp_bytes"] > 0
    # counts only: no device metric's name, not the contract's line
    assert "metrics" not in last and "device" not in last
    assert not DEVICE_NAMES & set(last["counts"])


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout
