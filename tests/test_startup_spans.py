"""The start-up account (PR 53): the ``startup/`` and ``compile/`` span
families of ``paddle_tpu/telemetry.py`` and ``compile_cache.py``, their
self times and counters, the ring that keeps them past a window, and the
benchmark's two readers of it on hand-made spans.  No test here holds
anything to a wall-clock bound.
"""
import importlib.util
import os
import sys
import threading
import types

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer, telemetry
from paddle_tpu.monitor import monitor, stat_get
from paddle_tpu.serving.generation import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
PARTS = ("startup_import_us", "startup_backend_init_us",
         "startup_program_build_us", "startup_step_build_us",
         "startup_pool_alloc_us", "startup_warmup_us",
         "startup_warm_program_us", "compile_trace_us", "compile_lower_us",
         "compile_backend_us", "compile_cache_misses")
MODEL = {"vocab_size": 97, "hidden": 32, "num_layers": 2, "num_heads": 4,
         "num_kv_heads": 2, "intermediate": 64}


@pytest.fixture(autouse=True)
def _fresh_rings():
    telemetry.clear_spans()
    yield
    pt.set_flags({"FLAGS_telemetry": True, "FLAGS_trace_buffer_size": 4096})
    telemetry.clear_spans()


def _net():
    x = layers.data("x", [4])
    y = layers.data("y", [1])
    loss = layers.mean(pt.layers.square_error_cost(layers.fc(x, 1), y))
    optimizer.SGDOptimizer(0.1).minimize(loss)
    return loss


def _feed():
    x = np.random.RandomState(0).rand(8, 4).astype("float32")
    return {"x": x, "y": x.sum(1, keepdims=True).astype("float32")}


def _kept(name=None):
    return [s for s in telemetry.get_spans(kept=True)
            if name is None or s.name == name]


def _counters():
    return {n: stat_get(n) for n in PARTS}


# -- the compile family -------------------------------------------------------

def test_a_programs_three_compile_spans_fall_under_executor_compile():
    import jax

    loss = _net()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    telemetry.clear_spans()
    main = pt.default_main_program()
    exe.run(feed=_feed(), fetch_list=[loss])
    spans = telemetry.get_spans()
    aot, = [s for s in spans if s.name == "executor/compile"]
    assert aot.attrs["program"] == main._uid and aot.attrs["aot"]
    under = [s for s in spans if s.parent_id == aot.span_id]
    assert sorted(s.name for s in under if "step_fn" in s.attrs["fun_name"]) \
        == ["compile/backend", "compile/lower", "compile/trace"]
    for s in under:
        assert s.name.startswith("compile/"), s
        assert s.attrs["fun_name"] and s.attrs["program"] == main._uid
        assert s.tid == aot.tid and s.trace_id == aot.trace_id
        assert aot.start <= s.start <= s.end <= aot.end + 1e-3
        # (on the CPU the program places no cache, and nothing is asked
        # unless a test before this one left a directory placed)
        assert ("cache_hit" in s.attrs) == (
            s.name == "compile/backend"
            and bool(jax.config.jax_compilation_cache_dir))
    # the build of the jitted step is a part of its own, before it
    build, = [s for s in spans if s.name == "startup/step_build"]
    assert build.attrs["program"] == main._uid and build.end <= aot.start
    assert all(s in _kept() for s in under + [build])

    # the same program again, in this process: nothing compiles
    n = len(_kept())
    exe.run(feed=_feed(), fetch_list=[loss])
    assert len(_kept()) == n


def test_cache_hit_reads_0_then_1_against_a_placed_cache(tmp_path,
                                                         monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu import compile_cache

    knobs = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": -1}
    old = {k: getattr(jax.config, k) for k in knobs}
    for k, v in knobs.items():
        jax.config.update(k, v)
    cc.reset_cache()
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    try:
        loss = _net()
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        telemetry.clear_spans()
        before = _counters()
        hits0 = stat_get("compile_cache_hits")
        exe.run(feed=_feed(), fetch_list=[loss])

        def step_backend():
            return [s for s in _kept("compile/backend")
                    if "step_fn" in s.attrs["fun_name"]]

        cold, = step_backend()
        assert cold.attrs["cache_hit"] == 0
        # a "restarted" executor: the same program, a fresh jit cache
        exe2 = pt.Executor()
        exe2.run(feed=_feed(), fetch_list=[loss])
        _, warm = step_backend()
        assert warm.attrs["cache_hit"] == 1
        assert warm.attrs["retrieval_ms"] > 0
        asked = [s for s in _kept("compile/backend")
                 if "cache_hit" in s.attrs]
        hits = stat_get("compile_cache_hits") - hits0
        misses = stat_get("compile_cache_misses") \
            - before["compile_cache_misses"]
        assert hits == sum(s.attrs["cache_hit"] for s in asked) >= 1
        assert hits + misses == len(asked)
        # the account's row of the step: not every compile of it was a hit
        row, = [r for r in telemetry.startup_account()["programs"]
                if r[0] == "step_fn"]
        assert row[6] == 0 and row[5] > 0
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_a_nested_jit_has_no_span_of_its_own_unless_it_is_worth_a_line():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()       # (registers the listeners)

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer_fn(x):
        return inner(x) + inner(x + 1.0)

    telemetry.clear_spans()
    outer_fn(jnp.ones((3,), "float32")).block_until_ready()
    traces = _kept("compile/trace")
    outer, = [s for s in traces if s.attrs["fun_name"] == "outer_fn"]
    for s in traces:
        if s is not outer and outer.start <= s.start <= outer.end:
            # a nested trace kept is one of 5 ms or more, and the outer
            # trace's self time leaves it out
            assert s.end - s.start >= 0.005
    # ... whatever their depth, so the self times inside it add up to it
    inside = [s for s in traces
              if outer.start <= s.start and s.end <= outer.end]
    assert sum(s.attrs["self_ms"] for s in inside) \
        == pytest.approx((outer.end - outer.start) * 1e3, abs=0.01)
    assert [s.attrs["fun_name"] for s in _kept("compile/backend")
            if "outer_fn" in s.attrs["fun_name"]]


# -- self times ---------------------------------------------------------------

def test_self_times_of_nested_parts_add_up_to_the_enclosing_wall_time():
    before = _counters()
    with telemetry.startup_span("startup/warmup") as whole:
        for bucket in (8, 16):
            with telemetry.startup_span("startup/warm_program",
                                        kind="prefill", bucket=bucket):
                with telemetry.startup_span("startup/program_build"):
                    sum(range(20000))
                t = telemetry.time.monotonic()
                sum(range(20000))
                # a compile reported after the fact, with an inner one
                # that jax reports first
                mid = telemetry.time.monotonic()
                telemetry.span_record("compile/trace", t + (mid - t) / 4,
                                      t + (mid - t) / 2, fun_name="inner")
                got = telemetry.span_record(
                    "compile/trace", t, mid, fun_name="step_fn",
                    inherit=("program", "kind", "bucket"))
                assert (got.attrs["kind"], got.attrs["bucket"]) \
                    == ("prefill", bucket)
                assert "program" not in got.attrs
                assert got.parent_id is not None
    kept = _kept()
    assert len(kept) == 9 and kept[-1] is whole
    wall = whole.end - whole.start
    total = sum(s.attrs["self_ms"] for s in kept) / 1e3
    assert total <= wall + 1e-4
    assert total >= 0.9 * wall            # and little of it is lost
    for s in kept:
        assert 0.0 <= s.attrs["self_ms"] <= (s.end - s.start) * 1e3 + 1e-3
    outer_traces = [s for s in kept if s.attrs.get("fun_name") == "step_fn"]
    for s in outer_traces:                # each less its inner quarter
        assert s.attrs["self_ms"] == pytest.approx(
            0.75 * (s.end - s.start) * 1e3, abs=0.01)
    # the counters hold the same self times, in whole microseconds
    after = _counters()
    grown = sum(after[n] - before[n] for n in PARTS)
    assert abs(grown - total * 1e6) <= len(kept)
    account = telemetry.startup_account()
    assert account["warm_program"]["n"] == 2
    assert account["trace"]["n"] == 4
    assert sum(p["s"] for k, p in account.items() if k != "programs") \
        == pytest.approx(total, abs=1e-4)


def test_parts_on_another_thread_are_that_threads_own():
    """Two threads' spans side by side: neither takes the other's as a
    child, so each thread's self times add up to its own span."""
    done = []

    def front():
        with telemetry.startup_span("startup/warmup", programs=1) as span:
            sum(range(50000))
        done.append(span)

    with telemetry.startup_span("startup/warmup", programs=2) as mine:
        t = threading.Thread(target=front)
        t.start()
        t.join()
    other, = done
    assert other.tid != mine.tid and other.parent_id is None
    assert other.attrs["self_ms"] == pytest.approx(
        (other.end - other.start) * 1e3, abs=0.01)
    assert mine.attrs["self_ms"] == pytest.approx(
        (mine.end - mine.start) * 1e3, abs=0.01)


# -- kept past the window -----------------------------------------------------

def test_the_families_survive_a_flood_of_the_main_ring():
    pt.set_flags({"FLAGS_trace_buffer_size": 64})
    telemetry.clear_spans()
    with telemetry.startup_span("startup/pool_alloc", pools=3):
        pass
    telemetry.span_record("compile/backend", 1.0, 2.0, fun_name="jit_f")
    for i in range(1000):
        with telemetry.trace_span("generation/iteration", i=i):
            pass
    assert len(telemetry.get_spans()) == 64
    assert not [s for s in telemetry.get_spans()
                if s.name.startswith(telemetry.KEPT_FAMILIES)]
    assert [s.name for s in _kept()] \
        == ["startup/pool_alloc", "compile/backend"]
    # a span of another family is never kept, whoever records it
    telemetry.span_record("executor/compile", 1.0, 2.0)
    assert len(_kept()) == 2
    telemetry.clear_spans()
    assert _kept() == []


def test_importing_the_package_is_the_first_part():
    """``startup/import`` is made on the package's last line; this
    process imported it long ago, so look at the counter and at a child
    process's ring."""
    assert stat_get("startup_import_us") > 0
    import subprocess

    out = subprocess.run(
        [sys.executable, "-c",
         "import paddle_tpu as pt\n"
         "s, = pt.telemetry.get_spans(kept=True)\n"
         "a = pt.telemetry.startup_account()\n"
         "print(s.name, s.attrs['jax_ms'] > 0, s.attrs['modules'] > 100,"
         " a['import']['n'], s.attrs['self_ms'] >= s.attrs['jax_ms'])\n"
         "pt.set_flags({'FLAGS_telemetry': False})\n"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["startup/import", "True", "True", "1",
                                  "True"]


# -- off means off ------------------------------------------------------------

def test_with_telemetry_off_no_span_is_made_and_no_counter_moves():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()       # (its first call times the backend)
    telemetry.clear_spans()
    pt.set_flags({"FLAGS_telemetry": False})
    before = _counters()

    @jax.jit
    def g(x):
        return jnp.cos(x) + 3.0

    g(jnp.ones((5,), "float32")).block_until_ready()
    with telemetry.startup_span("startup/pool_alloc") as span:
        span.attrs["bytes"] = 1           # dropped, not an error
    assert telemetry.span_record("compile/trace", 0.0, 1.0) is None
    loss = _net()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    exe.run(feed=_feed(), fetch_list=[loss])
    assert telemetry.get_spans() == [] and _kept() == []
    assert _counters() == before
    assert telemetry.startup_account() == {"programs": []}


# -- the engine ---------------------------------------------------------------

def test_an_engines_construction_and_warmup_give_the_parts():
    before = _counters()
    eng = GenerationEngine(MODEL, num_slots=2, max_seq_len=32,
                           prefill_buckets=[8, 16], attn_impl="xla")
    try:
        built = _kept()
        pool, = [s for s in built if s.name == "startup/pool_alloc"]
        assert pool.attrs["pools"] == len(eng.cache_names)
        assert pool.attrs["bytes"] == eng.kv_cache_bytes > 0
        assert [s.attrs["kind"] for s in built
                if s.name == "startup/program_build"] == ["decode"]
        n = eng.warmup()
        kept = _kept()
        whole, = [s for s in kept if s.name == "startup/warmup"]
        assert whole.attrs["programs"] == n
        warmed = [s for s in kept if s.name == "startup/warm_program"]
        assert len(warmed) == n
        assert [(s.attrs["kind"], s.attrs["bucket"]) for s in warmed] \
            == [("prefill", 8), ("prefill", 16), ("chunk", 8),
                ("chunk", 16), ("decode", None)]
        assert all(s.parent_id == whole.span_id for s in warmed)
        builds = [s for s in kept if s.name == "startup/program_build"]
        assert sorted((s.attrs["kind"], s.attrs.get("bucket"))
                      for s in builds[1:]) \
            == [("chunk", 8), ("chunk", 16), ("prefill", 8),
                ("prefill", 16)]
        assert len([s for s in kept if s.name == "startup/step_build"]) \
            >= n
        # what a program compiled under its warm-up says which it is
        for w in warmed:
            steps = [s for s in kept if s.name.startswith("compile/")
                     and "step_fn" in s.attrs["fun_name"]
                     and w.start <= s.start and s.end <= w.end + 1e-3]
            assert sorted(s.name for s in steps) == [
                "compile/backend", "compile/lower", "compile/trace"]
            for s in steps:
                assert (s.attrs["kind"], s.attrs["bucket"]) \
                    == (w.attrs["kind"], w.attrs["bucket"])
                assert isinstance(s.attrs["program"], int)
        # on this thread the parts add up to no more than their union
        mine = [s for s in kept if s.tid == whole.tid
                and s.start >= whole.start]
        assert sum(s.attrs["self_ms"] for s in mine) \
            <= (whole.end - whole.start) * 1e3 + 0.1
        account = eng.stats()["startup"]
        for part in ("program_build", "step_build", "pool_alloc",
                     "warmup", "warm_program", "trace", "lower",
                     "backend"):
            assert account[part]["n"] >= 1 and account[part]["s"] >= 0
        rows = {(r[0], r[1], r[2]) for r in account["programs"]}
        assert {("step_fn", "prefill", 8), ("step_fn", "chunk", 16),
                ("step_fn", "decode", None)} <= rows
        after = _counters()
        assert all(after[k] >= before[k] for k in PARTS)
        assert after["startup_pool_alloc_us"] \
            > before["startup_pool_alloc_us"]

        # a second warm-up, and traffic after it, add nothing
        assert eng.warmup() == 1
        res = eng.generate(list(range(1, 11)), 4)
        assert len(res["tokens"]) == 4
        assert len(_kept()) == len(kept)
        assert _counters() == after
    finally:
        eng.close()


# -- the benchmark's readers, on hand-made spans -------------------------------

def _reader(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "startup_reader_" + name, os.path.join(BENCH, "readers",
                                               name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(name, start, end, self_ms=None, tid=1, **attrs):
    attrs["self_ms"] = (end - start) * 1e3 if self_ms is None else self_ms
    return types.SimpleNamespace(name=name, start=start, end=end, tid=tid,
                                 attrs=attrs)


class _Run:
    """What a reader uses of ``harness.Run``."""

    def __init__(self, phases, t_start=100.0):
        self.t_start, self.phases, self.said = t_start, phases, []

    def say(self, msg):
        self.said.append(msg)


SERVE = [("imports", 10.0), ("weights + reference check", 20.0),
         ("cache loads or compiles + warm-up", 8.0), ("warm traffic", 7.0)]
TRAIN = [("imports", 10.0), ("weights", 9.0), ("reference check", 4.0),
         ("cache load or compile + warm-up", 8.0)]
# the program's spans of a serving set-up: t_start 100, warm traffic from
# 138, window open at 145
HAND = [
    _span("startup/import", 101.0, 104.0, jax_ms=2500.0, modules=154),
    _span("startup/pool_alloc", 130.0, 131.0, pools=2),
    _span("startup/warmup", 131.0, 138.0, self_ms=100.0, programs=2),
    _span("startup/warm_program", 131.0, 135.0, self_ms=500.0,
          kind="prefill", bucket=2048),
    _span("compile/trace", 131.0, 132.0, fun_name="step_fn",
          kind="prefill", bucket=2048),
    _span("compile/lower", 132.0, 132.5, fun_name="jit(step_fn)",
          kind="prefill", bucket=2048),
    _span("compile/backend", 132.5, 134.5, fun_name="jit(step_fn)",
          kind="prefill", bucket=2048, cache_hit=1, retrieval_ms=1900.0),
    _span("startup/warm_program", 135.0, 138.0, self_ms=200.0,
          kind="decode", bucket=None),
    _span("compile/trace", 135.0, 135.8, fun_name="step_fn", kind="decode",
          bucket=None),
    _span("compile/backend", 136.0, 138.0, fun_name="jit(step_fn)",
          kind="decode", bucket=None, cache_hit=0, retrieval_ms=0.0),
    # the front's warm-up, on another thread, beside the generator's
    _span("startup/warmup", 131.5, 133.5, tid=2, programs=8),
    # inside the warm traffic, and inside the window: not set-up's parts
    _span("compile/backend", 140.0, 141.0, fun_name="jit(late)"),
    _span("compile/backend", 150.0, 151.0, fun_name="jit(later)"),
]


@pytest.mark.parametrize("spans,want", [
    (["startup/import"], 3.0),
    (["compile/trace", "compile/lower"], 1.0 + 0.5 + 0.8),
    # (the one inside the warm traffic began before the window opened)
    (["compile/backend"], 2.0 + 2.0 + 1.0),
    (["startup/warm_program"], 0.7),
    (["startup/backend_init"], 0.0),
])
def test_the_part_reader_sums_self_seconds_begun_before_the_window(
        spans, want):
    part = _reader("startup_part")
    ctx = {"run": _Run(SERVE), "startup_spans": HAND, "spans": []}
    assert part.window_open(ctx["run"]) == 145.0
    assert part.read(ctx, spans=spans) == pytest.approx(want)


@pytest.mark.parametrize("phases,spans,want", [
    # 38 s before the warm traffic; the union covers 101-104 and 130-138
    (SERVE, HAND, 38.0 - 3.0 - 8.0),
    # a training cell has no warm traffic phase: 31 s, the same spans up
    # to 131
    (TRAIN, HAND, 31.0 - 3.0 - 1.0),
    # no span at all: everything is the remainder
    (SERVE, [], 38.0),
    # a span that began before the process's clock did is cut to it
    (SERVE, [_span("startup/import", 95.0, 104.0)], 38.0 - 4.0),
])
def test_the_remainder_takes_the_union_off_once(phases, spans, want):
    rest = _reader("startup_unaccounted")
    run = _Run(phases)
    ctx = {"run": run, "startup_spans": spans}
    assert rest.read(ctx) == pytest.approx(want)
    assert rest.read(ctx) == pytest.approx(want)      # (the table, once)
    said = "\n".join(run.said)
    assert said.count("start-up account") == 1
    assert "harness phases: imports 10.000" in said
    if spans is HAND and phases is SERVE:
        # the table names programs by the engine's kind and bucket
        assert "prefill 2048" in said and "decode" in said
        assert "cache_hit 1" in said and "cache_hit 0" in said
        by_backend, = [ln for ln in run.said if "by backend" in ln]
        assert by_backend.index("prefill 2048") \
            < by_backend.index("decode")
        assert "warm_program" in said and "import" in said


@pytest.mark.parametrize("reader,args", [
    ("startup_part", {"spans": ["startup/import"]}),
    ("startup_unaccounted", {}),
])
def test_a_program_that_keeps_no_such_ring_gives_nothing_to_read(
        reader, args, monkeypatch):
    """The readers also run on the parent commit, whose ``get_spans``
    takes no argument: nothing to read, and nothing raised."""
    monkeypatch.setattr(telemetry, "get_spans", lambda: [])
    run = _Run(SERVE)
    assert _reader(reader).read({"run": run}, **args) is None
    assert run.said == []


def test_the_readers_read_the_programs_own_ring():
    telemetry.span_record("startup/import", 101.0, 104.0)
    telemetry.span_record("compile/backend", 150.0, 151.0, fun_name="f")
    run = _Run(SERVE)
    ctx = {"run": run}
    assert _reader("startup_part").read(
        ctx, spans=["startup/import", "compile/backend"]) \
        == pytest.approx(3.0)
    assert _reader("startup_unaccounted").read(ctx) == pytest.approx(35.0)


def test_every_part_counter_is_in_the_registry_under_its_name():
    names = {n for n, _ in monitor.publish()}
    with telemetry.startup_span("startup/backend_init"):
        pass
    assert "startup_backend_init_us" in {n for n, _ in monitor.publish()}
    assert "compile_cache_misses" in names


def test_chip_smoke_prints_the_account(capsys):
    sys.path.insert(0, REPO)
    import chip_smoke

    with telemetry.startup_span("startup/warm_program", kind="prefill",
                                bucket=64):
        telemetry.span_record("compile/backend", 5.0, 6.5,
                              fun_name="jit(step_fn)", cache_hit=1,
                              inherit=("kind", "bucket"))
    chip_smoke.say_startup_account()
    out = capsys.readouterr().out
    assert "start-up account" in out and "backend 1.50 x 1" in out
    assert "compile/backend spans that asked the cache 1" in out
    assert "prefill 64: trace 0.00 s, lower 0.00 s, backend 1.50 s, " \
        "cache_hit 1" in out


def test_the_ring_follows_the_flag_set_after_the_first_span():
    """Importing the package records a span, so a driver sets
    ``FLAGS_trace_buffer_size`` after the ring exists (the benchmark
    sizes it for a window's spans): the ring takes the new size and
    keeps what it held."""
    def flood(n):
        for i in range(n):
            with telemetry.trace_span("generation/iteration", i=i):
                pass

    flood(5)
    pt.set_flags({"FLAGS_trace_buffer_size": 1 << 17})
    flood(5000)
    assert len(telemetry.get_spans()) == 5005
    pt.set_flags({"FLAGS_trace_buffer_size": 8})
    flood(1)
    assert [s.attrs["i"] for s in telemetry.get_spans()] \
        == list(range(4993, 5000)) + [0]
