"""What a serving program ran in (PR 67): the configurations' statements
of the two admitted dtypes, the harness's observation of an engine, the
roofline readers' item sizes by class, and the stand-ins that fix the
``bfloat16`` limits, at rehearsal sizes on the CPU.

``test_manifest.py`` takes these checks in as its own, so that tier-1
collects them with it (``tests/test_benchmark_manifest.py``); run alone:

    python -m pytest benchmark/tests/test_manifest.py -k as_run
"""
import fnmatch
import functools
import inspect
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [p for p in (BENCH, os.path.dirname(BENCH), HERE)
                if p not in sys.path]

import harness  # noqa: E402  (numpy only)
import ops_bytes  # noqa: E402

# the float32 entries as the parent had them: (share_of_range, near-tie
# margin), and a cell of the configuration
FLOAT32 = {
    "mistral-7b-v0.1": (0.015625, None, "mistral7b-longprompt"),
    "smallthinker-21b-a3b": (0.015625, 0.0128, "smallthinker21b-mixedlen"),
    "sdar-30b-a3b-chat": (0.0078125, 0.0004, "sdar30b-blockgen"),
    "lfm2-24b-a2b": (0.0078125, 0.0004, "lfm2-24b-longanswer"),
    "olmo-hybrid-7b": (0.015625, None, "olmo-hybrid7b-longdoc"),
    "solar-open2-250b": (0.015625, 0.002, "solar-open2-agentturns"),
    "gigachat35-432b-a28b": (0.0078125, 0.002, "gigachat35-ragturns"),
    "command-a-plus-05-2026": (0.00390625, 0.002, "command-a-plus-ragdocs"),
    "deepseek-v2": (0.0078125, 0.002, "deepseek-v2-docqa"),
    "granite-4.0-h-micro": (0.0009765625, None, "granite4h-micro-manychats"),
    "nemotron3-super-120b-a12b": (0.0009765625, 0.00025,
                                  "nemotron3-super-agentfleet"),
    "longcat-flash-chat": (0.0078125, 0.002, "longcat-flash-agentchat"),
}
MARGIN = "near_tie_margin_share_of_router_range"
F32 = {"weights": "float32", "pages": "float32", "state": "float32"}
BF16 = {"weights": "bfloat16", "pages": "bfloat16", "state": "float32"}


@pytest.mark.parametrize("config", list(FLOAT32))
def test_as_run_a_decoder_file_states_both_dtypes(config):
    """Both entries of ``check_tolerance`` (or a written refusal of the
    second), the float32 one the parent's to the digit, and a keep list
    whose every entry says why."""
    cfg = harness.load_json("configs", config + ".json")
    share, margin, _ = FLOAT32[config]
    entry = harness.tolerance_entry(cfg, "float32")
    assert entry["share_of_range"] == share
    assert entry.get(MARGIN) == margin and len(entry["why"]) > 200
    assert cfg["as_run"]["dtype"] == "float32"
    refused = "refused" in cfg["check_tolerance"]["bfloat16"]
    # a refused file admits float32 alone and says its bfloat16 block is
    # not in force: it is what was tried, kept for the next reading
    assert cfg["as_run"]["dtypes_admitted"] \
        == ["float32", "bfloat16"][:1 if refused else 2]
    stated = cfg["as_run"]["bfloat16"]
    assert stated.get("in_force", True) is not refused
    assert (stated["weights"], stated["pages"], stated["state"]) \
        == ("bfloat16", "bfloat16", "float32")
    # the keep list is arrays, which a run observes; what no run can
    # observe is listed apart, with what the check enforces of it
    assert all(len(k["why"]) > 40 and k["arrays"]
               for k in stated["keeps_float32"])
    apart = stated["computations_kept_by_stated"]
    assert len(apart["enforced_by_the_check"]) > 40 and all(
        k["computations"] and ("why" in k or "of" in k)
        for k in apart["list"])
    low = cfg["check_tolerance"]["bfloat16"]
    if "refused" in low:
        assert len(low["refused"]) > 200
        assert harness.tolerance_entry(cfg, "bfloat16") is None
    else:
        assert share < low["share_of_range"] <= 0.25
        assert (MARGIN in low) == (margin is not None)
        assert margin is None or low[MARGIN] >= margin
        # a margin wide enough to make every compared row a near tie
        # comes with a limit on the router's scores themselves, under it
        if margin is not None and low[MARGIN] >= 0.05:
            assert 0 < low[harness.ROUTER_LIMIT] < low[MARGIN]
        assert harness.ROUTER_LIMIT not in \
            harness.tolerance_entry(cfg, "float32")
        assert len(low["why"]) > 200
        assert harness.tolerance_entry(cfg, "bfloat16") is low
    assert harness.tolerance_entry(cfg, "float8_e4m3fn") is None
    said = str(cfg["assumed"]) + cfg["as_run"]["why"]
    assert "the program's choice today" not in said


def _engine(config):
    cell = harness.Cell(FLOAT32[config][2], rehearse=True)
    gen = cell.builder().engine(
        cell.cfg, cell.mix, num_slots=2,
        buckets=[min(cell.mix["engine"]["prefill_buckets"])])
    gen.close()
    return cell, gen


_engine_read_only = functools.lru_cache(maxsize=None)(_engine)


@pytest.mark.parametrize("config", list(FLOAT32))
def test_as_run_the_keep_list_matches_the_engines_arrays(config):
    """Every pattern of ``keeps_float32`` matches an array of the engine a
    rehearsal-size builder makes; the engine is observed float32 in all
    three classes and held to the float32 entry."""
    cell, gen = _engine_read_only(config)
    names = [n[len(gen.name) + 1:] for n in gen.scope.local_var_names()
             if n.startswith(gen.name + ".")]
    for pattern in harness.kept_patterns(cell.cfg):
        assert any(fnmatch.fnmatchcase(n, pattern) for n in names), pattern
    assert cell.observed == {
        "weights": "float32", "pages": "float32",
        "state": "float32" if gen.state_names else None}
    assert cell.admitted
    share, margin, _ = FLOAT32[config]
    tol = cell.cfg["check_tolerance"]
    if "check_tolerance" not in cell.cfg.get("rehearse", {}):
        assert cell.tolerance == share and tol.get(MARGIN) == margin


# the step whose bytes count what is kept, a configuration: (cell, metric)
STEP_BYTES = {
    "mistral-7b-v0.1": ("mistral7b-chat", "decode_step_roofline.chat"),
    "smallthinker-21b-a3b": ("smallthinker21b-mixedlen",
                             "decode_step_roofline.mix"),
    "sdar-30b-a3b-chat": ("sdar30b-blockgen", "block_step_roofline.blk"),
}


@pytest.mark.parametrize("config", list(FLOAT32))
def test_as_run_the_kept_bytes_are_the_keep_lists_arrays(config):
    """The keep list lives in one place: what a step's ``*_bytes``
    function counts at the ``kept`` item size is, element for element,
    the arrays of a rehearsal-size engine that the configuration's
    ``keeps_float32`` patterns match (and no float vector is left
    unmatched).  A PR that widens a keep list and not the count, or the
    count and not the list, fails here."""
    import numpy as np

    cell, gen = _engine_read_only(config)
    patterns = harness.kept_patterns(cell.cfg)
    slots = set(gen.cache_names) | set(gen.state_names)
    prefix = gen.name + "."
    matched, loose = 0, []
    for n in gen.scope.local_var_names():
        v = gen.scope.find_var(n)
        if not n.startswith(prefix) or n in slots \
                or "float" not in str(getattr(v, "dtype", "")):
            continue
        short = n[len(prefix):]
        if any(fnmatch.fnmatchcase(short, p) for p in patterns):
            matched += int(np.prod(v.shape))
        elif v.ndim < 2:
            loose.append(short)
    assert not loose, loose
    name, metric = STEP_BYTES.get(
        config, (FLOAT32[config][2], "decode_step_roofline.pool"))
    fn = harness.resolve(harness.Cell(name).reader_of(metric)[1]["fn"])
    n = len(inspect.signature(fn).parameters) - 2
    for at in (0.0, 7.0):
        assert fn(cell.cfg, *[at] * n, ops_bytes.ItemSizes(0, 0, 0, 1)) \
            == matched > 0


def _cast(gen, pick, dtype):
    import jax.numpy as jnp

    for n in gen.scope.local_var_names():
        v = gen.scope.find_var(n)
        if hasattr(v, "dtype") and "float" in str(v.dtype) and pick(n, v):
            gen.scope.set_var(n, jnp.asarray(v).astype(dtype))


def test_as_run_the_check_refuses_a_bf16_engine_whose_kept_array_is_bf16():
    """An engine whose matrices and pages are bfloat16 is held to the
    bfloat16 entry; with the router cast too, or the pages left float32,
    the run is not correct and the line names what is astray."""
    import jax.numpy as jnp

    cell, gen = _engine("sdar-30b-a3b-chat")
    kept = harness.kept_patterns(cell.cfg)
    prefix = gen.name + "."

    def matrix(n, v):
        return n.startswith(prefix) and n not in gen.cache_names \
            and v.ndim >= 2 and not any(
                fnmatch.fnmatchcase(n[len(prefix):], p) for p in kept)

    _cast(gen, matrix, jnp.bfloat16)
    cell.observed = None
    cell.note_engine(gen)
    assert cell.observed["weights"] == "bfloat16"
    assert cell.observed_problems == [
        "pages are float32, stated bfloat16 beside bfloat16 weights"]
    _cast(gen, lambda n, v: n in gen.cache_names, jnp.bfloat16)
    cell.observed, cell.observed_problems = None, []
    cell.note_engine(gen)
    assert cell.admitted and cell.observed == dict(BF16, state=None)
    whole = cell.cfg["check_tolerance_file"]
    assert cell.tolerance == whole["bfloat16"]["share_of_range"]
    assert cell.cfg["check_tolerance"][MARGIN] == whole["bfloat16"][MARGIN]
    _cast(gen, lambda n, v: n.endswith("blk0.moe.router.w"), jnp.bfloat16)
    cell.observed, cell.observed_problems = None, []
    cell.note_engine(gen)
    assert not cell.admitted
    assert cell.observed_problems == [
        "array blk0.moe.router.w (bfloat16): kept float32 beside bfloat16 "
        "weights by keeps_float32 (every vector is)"]
    # and the result line says so
    run = harness.Run(cell, types.SimpleNamespace(rehearse=True, trace=0),
                      0.0)
    import contextlib
    import io
    import json

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.finish(correct=True, attempted=1, failed=0, end_to_end={},
                   ctx={})
    run.cleanup()
    line = json.loads(out.getvalue().splitlines()[-1])
    assert line["correct"] is False
    assert line["as_run_observed"]["weights"] == "bfloat16"


def test_as_run_a_float8_engine_has_no_entry():
    cell, gen = _engine("mistral-7b-v0.1")
    import jax.numpy as jnp

    _cast(gen, lambda n, v: v.ndim >= 2, jnp.float8_e4m3fn)
    cell.observed = None
    cell.note_engine(gen)
    assert not cell.admitted and "no entry" in cell.observed_problems[0]


def test_as_run_a_refused_file_admits_float32_alone():
    """``held_to`` reads ``as_run.dtypes_admitted``: under a file whose
    ``bfloat16`` entry is refused, bfloat16 weights have no entry and the
    problem says what the file admits; float32 ones are held as ever."""
    refused = [c for c in FLOAT32 if "refused" in harness.load_json(
        "configs", c + ".json")["check_tolerance"]["bfloat16"]]
    for config in refused:
        cfg = harness.load_json("configs", config + ".json")
        entry, problems = harness.held_to(cfg, dict(BF16,
                                                    kept_not_float32=[]))
        assert entry is None and "admits float32 and" in problems[0]
        entry, problems = harness.held_to(cfg, dict(F32,
                                                    kept_not_float32=[]))
        assert entry["share_of_range"] == FLOAT32[config][0]
        assert not problems
    # and a file cannot be held to an entry it does not admit
    cfg = harness.load_json("configs", "mistral-7b-v0.1.json")
    cfg["as_run"]["dtypes_admitted"] = ["float32"]
    assert harness.held_to(cfg, dict(BF16, kept_not_float32=[]))[0] is None


@pytest.mark.parametrize("driver", ["serve_state", "serve_blocks"])
def test_as_run_a_router_off_its_limit_is_not_correct(driver):
    """Where the entry states ``router_off_limit_share_of_router_range``
    a compared row whose router scores lie off the reference's by more is
    not correct, whatever the logits read; without the key (every float32
    entry) the deviation is reported and judges nothing."""
    import numpy as np

    off = 0.1

    def report():
        r = np.zeros((9, 4), "float32")
        r[3, 0] = off
        return r

    if driver == "serve_state":
        import serve_state

        logits = np.ones((9, 8), "float32")
        res = {"logits": list(logits), "tokens": [1] * 9,
               "router_logits": list(np.zeros((9, 2, 4), "float32"))}

        def judged(limit):
            return serve_state.check_request(
                lambda p, ids, rows, prog: (logits, report()), None, 1e-3,
                128, [1] * 5, res, limit)
    else:
        import serve_blocks

        B = 4
        logits = np.ones((B, 8), "float32")
        res = {"tokens": [1] * B, "finish": "length", "passes": [
            {"base": 4, "tokens": [1] * B, "masked": np.zeros(B, bool),
             "quota": 0, "logits": logits,
             "router_logits": np.zeros((2, B, 4), "float32")}]}

        def judged(limit):
            return serve_blocks.check_request(
                lambda p, ids, m, rows, prog, cov: (logits, report()[:B]),
                None, B, 1e-3, 128, [1] * 4, B, res, router_tol=limit)
    for limit, fine in ((None, True), (0.2, True), (0.05, False)):
        got_fine, got = judged(limit)
        assert got_fine is fine, (limit, got)
        assert got["router_off"] == pytest.approx(off)


def test_as_run_the_stand_ins_leave_a_float32_stream_whole():
    """``as_run.bfloat16.residual_stream: float32`` in a file makes the
    stand-ins' ``residual`` hook the identity; pages and product inputs
    are rounded as ever."""
    import jax.numpy as jnp

    import standins

    x = jnp.asarray([1.0 + 2.0 ** -12, 3.0 + 2.0 ** -10], jnp.float32)
    rounded = standins.through(x, "stated")
    assert not bool((rounded == x).any())
    plain = standins.rounder("stated", {"as_run": {"bfloat16": {}}})
    whole = standins.rounder("fp8", {"as_run": {"bfloat16": {
        "residual_stream": "float32"}}})
    assert bool((plain("residual", x) == rounded).all())
    assert bool((whole("residual", x) == x).all())
    assert bool((whole("product", x) == rounded).all())
    y = jnp.asarray([1.0 + 2.0 ** -5, 3.0 + 2.0 ** -4], jnp.float32)
    assert bool((plain("pages", y) == y).all())       # bfloat16 holds y
    assert not bool((whole("pages", y) == y).any())   # float8 does not


# -- the readers' item sizes ---------------------------------------------------

READERS = [
    ("roofline", "mistral7b-chat", "decode_step_roofline.chat"),
    ("roofline_span", "granite4h-micro-manychats",
     "decode_step_roofline.pool"),
    ("roofline_blocks", "sdar30b-blockgen", "block_step_roofline.blk"),
    ("roofline_moe", "smallthinker21b-mixedlen", "decode_step_roofline.mix"),
    ("roofline_kernel", "granite4h-micro-manychats",
     "paged_kernel_roofline.pool"),
    ("roofline_kernel", "granite4h-micro-manychats",
     "ssm_step_roofline.pool"),
    ("roofline_kernel_calls", "nemotron3-super-agentfleet",
     "expert_kernel_roofline.pool"),
    ("roofline_kernel_prefill", "granite4h-micro-manychats",
     "ssm_chunk_roofline.pool"),
]


def _context(cell):
    """A hand-made traced window that every roofline reader finds
    something in: three decode runs, two prefills, one kernel of every
    name, spans with every attribute."""
    def span(name, start, **attrs):
        return types.SimpleNamespace(name=name, start=start, attrs=attrs)

    attrs = dict(live_positions=5000.0, state_slots=100.0,
                 experts_touched=30.0, rows=64.0,
                 live_positions_window=3000.0, pairs_held=800.0,
                 experts_held_touched=20.0, latent_positions=4000.0)
    kernels = ["paged_decode_attention.1", "ssd_step.2", "ssd_chunk.3",
               "grouped_matmul_ragged-dot.4"]
    trace = {"to_monotonic": 100.0,
             "modules": {"decode": [(0.0, 0.02), (0.03, 0.05), (0.5, 0.52)],
                         "p256": [(0.06, 0.09), (0.3, 0.33)]},
             "op_seconds": {k: 0.01 for k in kernels},
             "op_text": {k: "%" + k + " = f32[8,128]" for k in kernels}}
    spans = [span("generation/decode_step", 100.01 + i / 50, **attrs)
             for i in range(3)]
    for name in ("generation/prefill", "generation/prefill_fetch"):
        spans += [span(name, 100.055, tokens=200, scan_tokens=200, **attrs),
                  span(name, 100.29, tokens=180, scan_tokens=180, **attrs)]
    run = types.SimpleNamespace(
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        trace_t0=100.0, trace_t1=101.0)
    return {"run": run, "cfg": cell.cfg, "mix": cell.mix, "trace": trace,
            "trace_spans": spans,
            "gauges": [{"serving_kv_pages_live": 40.0}],
            "engine": types.SimpleNamespace(page_tokens=16)}


@pytest.mark.parametrize("reader,cell,metric", READERS,
                         ids=[f"{r}-{m}" for r, _, m in READERS])
def test_as_run_a_reader_takes_its_item_sizes_by_class(reader, cell, metric,
                                                      monkeypatch):
    """Float32 classes read the parent's number (an item size of 4, as a
    context with no observation reads); bfloat16 weights and pages with
    float32 state read exactly half the weight and page bytes and the
    whole of what is kept and of the state."""
    cell = harness.Cell(cell)
    name, args = cell.reader_of(metric)
    assert name == reader
    fn, given = harness.resolve(args["fn"]), []
    probe = types.ModuleType("as_run_probe")

    def needs(cfg, *rest):
        given.append(rest)
        return fn(cfg, *rest)

    probe.needs = needs
    monkeypatch.setitem(sys.modules, "as_run_probe", probe)
    read = harness.load_module("readers", reader).read
    args = dict(args, fn="as_run_probe.needs")
    ctx = _context(cell)
    parent = read(ctx, **args)
    assert parent is not None and parent > 0
    assert all(rest[-1] == ops_bytes.sizes_of(4) for rest in given)
    same = read(dict(ctx, as_run_observed=F32), **args)
    assert same == parent
    del given[:]
    half = read(dict(ctx, as_run_observed=BF16), **args)
    sizes = ops_bytes.ItemSizes(2, 2, 4)
    assert given and all(rest[-1] == sizes for rest in given)
    *means, _ = given[0]
    whole, low, rest = (fn(cell.cfg, *means, ops_bytes.ItemSizes(*s))
                        for s in ((4, 4, 4), (2, 2, 4), (0, 0, 4)))
    assert low - rest == pytest.approx((whole - rest) / 2, rel=1e-12)
    if len({r[:-1] for r in given}) == 1 and len(given) > 0 \
            and reader != "roofline_kernel_calls":
        assert half / parent == pytest.approx(low / whole, rel=1e-9)
    # a run's own observation is read where the context brings none
    ctx["run"].cell = types.SimpleNamespace(observed=BF16)
    assert read(ctx, **args) == half


def test_as_run_every_byte_count_takes_sizes_by_class():
    """Every ``*_bytes`` function a metric names: a number for an item
    size is every class alike; half the weights' and pages' size is half
    of those bytes and all of the state's and the kept ones'."""
    seen = 0
    for w in harness.Cell("mistral7b-chat").bench["workloads"]:
        cell = harness.Cell(w["name"])
        for m in cell.metrics("per_layer"):
            fn = cell.reader_of(m["name"])[1].get("fn", "")
            if not fn.endswith("_bytes"):
                continue
            f = harness.resolve(fn)
            n = len(inspect.signature(f).parameters) - 2
            at = [10.0 + 3 * i for i in range(n)]
            plain, whole, low, rest = (
                f(cell.cfg, *at, s) for s in (
                    4, ops_bytes.ItemSizes(4, 4, 4),
                    ops_bytes.ItemSizes(2, 2, 4),
                    ops_bytes.ItemSizes(0, 0, 4)))
            assert plain == whole > 0 and 0 <= rest <= whole
            assert low - rest == pytest.approx((whole - rest) / 2,
                                               rel=1e-12)
            seen += 1
    assert seen >= 30


def test_as_run_no_reader_assumes_an_item_size():
    for name in os.listdir(os.path.join(BENCH, "readers")):
        with open(os.path.join(BENCH, "readers", name)) as f:
            assert '["as_run"]["dtype"]' not in f.read(), name


# -- the stand-ins, at rehearsal sizes ----------------------------------------

@pytest.mark.parametrize("config", ["mistral-7b-v0.1", "sdar-30b-a3b-chat",
                                    "granite-4.0-h-micro"])
def test_as_run_stated_passes_its_limit_and_fp8_fails_it(config, tmp_path):
    """One dense, one routed and one recurrent configuration at the toy
    widths of their ``rehearse`` groups (whose ``check_tolerance.bfloat16``
    is the toy widths' own: a limit is of a width): ``stated`` is fine on
    every prompt with twice the room, ``fp8``'s largest reading is twice
    over the limit.  In a process of its own: an engine's start-up draws,
    to whose deviation the seeded weights are scaled, depend on what the
    process built before."""
    import json
    import subprocess

    out = tmp_path / "readings.json"
    subprocess.run(
        [sys.executable, os.path.join(HERE, "standins.py"), "--workload",
         FLOAT32[config][2], "--rehearse", "--entry", "bfloat16",
         "--seeds", "6700000013", "--standins", "stated,fp8", "--out",
         str(out)], check=True, capture_output=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    runs = json.loads(out.read_text())["runs"]
    cfg = harness.Cell(FLOAT32[config][2], rehearse=True).cfg
    limit = cfg["check_tolerance"]["bfloat16"]["share_of_range"]
    stated, fp8 = ([p for r in runs if r["standin"] == kind
                    for p in r["prompts"]] for kind in ("stated", "fp8"))
    assert stated and all(r["fine"] for r in stated)
    assert fp8 and not all(r["fine"] for r in fp8)
    assert max(r["rel"] for r in stated) * 2 <= limit
    assert max(r["rel"] for r in fp8) >= limit * 2
