"""The program store (``paddle_tpu/program_store.py``): the lowered module
of a Program's step kept beside the compile cache, on the CPU with the
cache placed by ``JAX_COMPILATION_CACHE_DIR`` in a ``tmp_path``.

A second ``Executor`` that builds the same Program loads the module and
never runs ``lower_block``; each part of the key alone makes a miss; what
a trace books and logs besides the module is done again on a hit; what
``jax.export`` cannot keep is refused, counted and still runs; with no
cache placed nothing is stored.  Two fresh subprocesses (one script, run
twice) show the same across processes, to the bit and with the state
donated.

The step builders of ``parallel/sharded.py`` are clients too (PR 62): the
object ``build_sharded_step`` returns answers ``.lower(*args)`` from the
store, over a mesh of one device and of four, under a key that also holds
the mesh, every ``PartitionSpec``, the donation and the compiler options.
"""
import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import compile_cache, flags, program_store
from paddle_tpu.framework import executor as executor_mod
from paddle_tpu.framework.core import reset_unique_name
from paddle_tpu.monitor import monitor, stat_get
from paddle_tpu.ops.registry import get_op_def, reset_op_seed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STORE_STATS = ("program_store_hits", "program_store_misses",
               "program_store_refused")


def _net(prob=0.25, seed=3, width=16):
    """The same Program, name for name, however often it is built."""
    reset_unique_name()
    reset_op_seed()
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    main.random_seed = seed
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[8], dtype="float32")
        y = pt.layers.data(name="y", shape=[1], dtype="float32")
        h = pt.layers.fc(x, width, act="relu")
        h = pt.layers.dropout(h, prob)
        pred = pt.layers.fc(h, 1)
        loss = pt.layers.mean(pt.layers.square(pred - y))
        pt.optimizer.AdamOptimizer(1e-2).minimize(loss)
    return main, startup, loss, pred


def _feed(batch=4):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(batch, 8).astype("float32"),
            "y": rng.randn(batch, 1).astype("float32")}


def _run(main, startup, fetches, feed=None, steps=2):
    """A fresh scope and ``Executor``: the start-up program, then
    ``steps`` of ``main``; the last step's fetches."""
    with pt.scope_guard(pt.Scope()):
        exe = pt.Executor()
        exe.run(startup)
        for _ in range(steps):
            out = exe.run(main, feed=feed or _feed(), fetch_list=fetches)
    return out


def _counts():
    return {k: stat_get(k) for k in STORE_STATS}


def _delta(before):
    return {k.replace("program_store_", ""): stat_get(k) - n
            for k, n in before.items()}


def _run_default(**kw):
    main, startup, loss, _ = _net(**kw)
    return _run(main, startup, [loss])


def test_a_second_executor_loads_the_module_and_lowers_no_block(
        store, monkeypatch):
    lowered = []
    lower_block = executor_mod.lower_block
    monkeypatch.setattr(
        executor_mod, "lower_block",
        lambda block, *a, **kw: (lowered.append(block),
                                 lower_block(block, *a, **kw))[1])
    before = _counts()
    first = _run_default()
    assert _delta(before) == {"hits": 0, "misses": 2, "refused": 0}
    assert len(lowered) == 2 and len(os.listdir(store)) == 2

    before, hits0 = _counts(), stat_get("compile_cache_hits")
    del lowered[:]
    second = _run_default()
    assert _delta(before) == {"hits": 2, "misses": 0, "refused": 0}
    assert lowered == [], "a hit traced the Program"
    assert stat_get("compile_cache_hits") - hits0 == 2
    assert first[0].tobytes() == second[0].tobytes()
    assert len(os.listdir(store)) == 2


def test_a_loaded_programs_state_is_as_free_to_move_as_a_traced_ones(store):
    """jax commits the results of ``Exported.call`` to one device; a
    start-up program's state must stay uncommitted, as its own ``jit``
    leaves it, or a step compiled over a mesh refuses it (the dp4 cell:
    ``Executor.run(startup)``, then ``build_sharded_step``'s step)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    rep = NamedSharding(Mesh(np.array(jax.devices()[:2]), ("dp",)), P())
    for turn in ("miss", "hit"):
        main, startup, _, _ = _net()
        before = _counts()
        with pt.scope_guard(pt.Scope()) as scope:
            pt.Executor().run(startup)
            state = [scope.find_var(n) for n in scope.local_var_names()]
        assert _delta(before)["hits" if turn == "hit" else "misses"] == 1
        over_the_mesh = jax.jit(lambda *xs: xs, in_shardings=rep).lower(
            *state).compile()
        moved = over_the_mesh(*state)     # (raises on a committed array)
        assert all(len(m.sharding.device_set) == 2 for m in moved)


def _reads_a_flag(monkeypatch):
    """``relu``'s lowering reads ``FLAGS_checkpoint_retries`` (no lowering
    of the package's reads a flag of its own)."""
    opdef = get_op_def("relu")
    lower = opdef.lower

    def reading(ctx, op):
        flags.flag_value("FLAGS_checkpoint_retries")
        return lower(ctx, op)

    monkeypatch.setattr(opdef, "lower", reading)


def _other_dtype(main):
    var = next(v for v in main.global_block().vars.values()
               if not v.persistable and not v.is_data
               and v.dtype == "float32")
    var.dtype = "float64"


KEY_PARTS = {
    "an op's attribute": lambda mp: dict(net=dict(prob=0.5)),
    "a var's dtype": lambda mp: dict(edit=_other_dtype),
    "a feed's shape": lambda mp: dict(feed=_feed(6)),
    "a fetch's name": lambda mp: dict(fetch_pred=True),
    "random_seed": lambda mp: dict(net=dict(seed=4)),
    "a flag a lowering read": lambda mp: flags.set_flags(
        {"FLAGS_checkpoint_retries": 5}) or {},
    "the sources' digest": lambda mp: mp.setattr(
        program_store, "_source_digest", lambda: "0" * 64) or {},
    "a version": lambda mp: mp.setattr(
        program_store, "_versions",
        lambda v=program_store._versions(): dict(v, jaxlib="0.0.0")) or {},
}


@pytest.mark.parametrize("part", sorted(KEY_PARTS))
def test_each_part_of_the_key_alone_makes_a_miss(store, monkeypatch, part):
    _reads_a_flag(monkeypatch)

    def run(net=None, edit=None, feed=None, fetch_pred=False):
        main, startup, loss, pred = _net(**(net or {}))
        if edit:
            edit(main)
        return _run(main, startup, [loss] + [pred] * fetch_pred, feed,
                    steps=1)

    run()
    before = _counts()
    run()
    assert _delta(before) == {"hits": 2, "misses": 0, "refused": 0}
    # a flag no lowering reads (``benchmark/serve.py`` sets this one)
    monkeypatch.setitem(flags._FLAGS, "FLAGS_trace_buffer_size", 1 << 17)
    before = _counts()
    run()
    assert _delta(before) == {"hits": 2, "misses": 0, "refused": 0}

    monkeypatch.setitem(flags._FLAGS, "FLAGS_checkpoint_retries",
                        flags.flag_value("FLAGS_checkpoint_retries"))
    changed = KEY_PARTS[part](monkeypatch)
    before = _counts()
    run(**changed)
    got = _delta(before)
    # (the start-up program holds no dropout, no relu and no feed: what
    # changes only the main program leaves it a hit)
    assert got["misses"] >= 1 and got["hits"] + got["misses"] == 2, got
    assert got["refused"] == 0
    before = _counts()
    run(**changed)
    assert _delta(before) == {"hits": 2, "misses": 0, "refused": 0}


def test_one_byte_of_one_source_changes_the_digest(tmp_path, monkeypatch):
    pkg = tmp_path / "pkg"
    (pkg / "ops").mkdir(parents=True)
    (pkg / "a.py").write_bytes(b"x = 1\n")
    (pkg / "ops" / "b.py").write_bytes(b"y = 2\n")
    (pkg / "ops" / "b.txt").write_bytes(b"not a source")
    monkeypatch.setattr(program_store, "__file__", str(pkg / "store.py"))
    digest = program_store._source_digest.__wrapped__
    first = digest()
    (pkg / "ops" / "b.txt").write_bytes(b"still not a source")
    assert digest() == first
    (pkg / "ops" / "b.py").write_bytes(b"y = 3\n")
    assert digest() != first
    (pkg / "ops" / "b.py").write_bytes(b"y = 2\n")
    assert digest() == first
    (pkg / "ops" / "b.py").rename(pkg / "ops" / "c.py")
    assert digest() != first


def test_a_lowering_from_outside_the_package_is_in_the_key(store,
                                                          monkeypatch):
    """The sources' digest covers ``paddle_tpu/``; an op registered from
    elsewhere brings its file's digest, or the Program has no key."""
    def digest():
        main, _, loss, _ = _net()
        return program_store.program_digest(main, ["x", "y"], [loss.name],
                                            None)

    inside = digest()
    assert inside is not None and digest() == inside
    opdef = get_op_def("relu")
    lower = opdef.lower
    monkeypatch.setattr(opdef, "lower", lambda ctx, op: lower(ctx, op))
    outside = digest()
    assert outside not in (None, inside)
    assert program_store._outside_lowerings(_net()[0]) == [
        ["relu", program_store.hashlib.sha256(
            open(__file__, "rb").read()).hexdigest()]]
    before = _counts()
    monkeypatch.setattr(opdef, "lower",
                        eval("lambda ctx, op: None", {"__name__": "nowhere"}))
    assert digest() is None
    assert _delta(before) == {"hits": 0, "misses": 0, "refused": 1}


@pytest.mark.parametrize("damage", ["truncated", "a byte changed",
                                    "not a module"])
def test_a_damaged_file_is_a_miss_that_is_rewritten(store, damage):
    want = _run_default()
    sizes = {}
    for name in os.listdir(store):
        path = os.path.join(store, name)
        data = open(path, "rb").read()
        sizes[name] = len(data)
        if damage == "truncated":
            data = data[:len(data) // 2]
        elif damage == "a byte changed":
            data = data[:-9] + bytes([data[-9] ^ 1]) + data[-8:]
        else:
            data = b"junk"
        with open(path, "wb") as f:
            f.write(data)
    before = _counts()
    got = _run_default()
    assert _delta(before) == {"hits": 0, "misses": 2, "refused": 0}
    assert got[0].tobytes() == want[0].tobytes()
    assert {n: os.path.getsize(os.path.join(store, n))
            for n in os.listdir(store)} == sizes
    before = _counts()
    _run_default()
    assert _delta(before) == {"hits": 2, "misses": 0, "refused": 0}


def test_a_hit_books_and_logs_what_the_trace_did(store, monkeypatch,
                                                 caplog):
    """The ``*_lowered_*`` stats (and every other stat a lowering books)
    read the same after a hit as after a miss, and a warning the trace
    logged is logged by the process that loads the module, once."""
    opdef = get_op_def("relu")
    lower = opdef.lower
    line = "relu lowered to its test formulation: no kernel here"

    def warning(ctx, op):
        logging.getLogger("paddle_tpu.ops.nn").warning(line)
        monitor.get("attention_lowered_xla").increase(3)
        return lower(ctx, op)

    monkeypatch.setattr(opdef, "lower", warning)

    def booked():
        return {name: n for name, n in monitor.publish()
                if not name.startswith(("compile_", "startup_",
                                        "program_store_", "executor_",
                                        "host_syncs"))}

    def run():
        before = booked()
        with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
            caplog.clear()
            _run_default()
        said = [r.getMessage() for r in caplog.records].count(line)
        return {k: n - before.get(k, 0) for k, n in booked().items()
                if n != before.get(k, 0)}, said

    miss, said = run()
    # (the trace lowers relu twice: the op, and again under its gradient)
    assert miss["attention_lowered_xla"] == 6 and said == 2
    assert any(k.startswith("dropout_lowered_") for k in miss)
    # a process that has said it says it no second time
    assert run() == (miss, 0)
    # another process (nothing said yet) loads the module and says it
    monkeypatch.setattr(program_store, "_said", set())
    before = _counts()
    assert run() == (miss, 1)
    assert _delta(before) == {"hits": 2, "misses": 0, "refused": 0}


def _with_a_host_callback():
    main, startup, loss, _ = _net()
    with pt.program_guard(main, startup):
        out = main.global_block().create_var(name="doubled", shape=[1],
                                             dtype="float32")
        pt.layers.py_func(lambda a: np.asarray(a) * 2, loss, out)
    return main, startup, [loss, out], None


def _under_a_two_device_mesh():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    main, startup, loss, _ = _net()
    rep = NamedSharding(Mesh(np.array(jax.devices()[:2]), ("dp",)), P())

    def place(scope):
        for name in scope.local_var_names():
            scope.set_var(name, jax.device_put(scope.find_var(name), rep))

    return main, startup, [loss], place


@pytest.mark.parametrize("build,reason", [
    (_with_a_host_callback, "host_callbacks"),
    (_under_a_two_device_mesh, "more than one device")])
def test_what_the_store_cannot_keep_is_refused_counted_and_runs(
        store, caplog, build, reason):
    main, startup, fetches, place = build()
    want = None
    for turn in range(2):
        with pt.scope_guard(pt.Scope()) as scope:
            exe = pt.Executor()
            exe.run(startup)
            if place:
                place(scope)
            before = _counts()
            with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
                caplog.clear()
                out = exe.run(main, feed=_feed(), fetch_list=fetches)
        assert _delta(before) == {"hits": 0, "misses": 0, "refused": 1}
        # logged once with its reason, however often it is refused
        said = [r.getMessage() for r in caplog.records
                if "program store: refused" in r.getMessage()]
        assert len(said) == (turn == 0) and all(reason in m for m in said)
        assert np.isfinite(out[0]).all()
        if want is None:
            want = out
        assert [o.tobytes() for o in out] == [o.tobytes() for o in want]
    if build is _with_a_host_callback:
        assert out[1] == pytest.approx(2 * out[0])


def test_check_nan_inf_makes_no_module_and_is_counted(store, monkeypatch):
    main, startup, loss, _ = _net()
    with pt.scope_guard(pt.Scope()):
        exe = pt.Executor()
        exe.run(startup)
        n = len(os.listdir(store))
        monkeypatch.setitem(flags._FLAGS, "FLAGS_check_nan_inf", True)
        before = _counts()
        exe.run(main, feed=_feed(), fetch_list=[loss])
    assert _delta(before) == {"hits": 0, "misses": 0, "refused": 1}
    assert len(os.listdir(store)) == n


def test_with_no_cache_placed_nothing_is_stored(tmp_path, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.chdir(tmp_path)
    assert program_store.directory() is None
    main, startup, loss, _ = _net()
    assert program_store.program_digest(main, ["x", "y"], [loss.name],
                                        None) is None
    before = _counts()
    _run(main, startup, [loss])
    assert _delta(before) == {"hits": 0, "misses": 0, "refused": 0}
    assert os.listdir(str(tmp_path)) == []
    # a backend answered for (the tests' way to the kernel route) makes the
    # cache's rule place a directory; over the CPU's devices no store
    monkeypatch.setattr(program_store, "ensure_compile_cache",
                        lambda: str(tmp_path))
    assert program_store.directory() is None
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert program_store.directory() == os.path.join(
        str(tmp_path), program_store.SUBDIR)


def test_the_watch_sees_its_own_thread_and_nothing_while_paused():
    import threading

    from paddle_tpu import watch

    stat = monitor.get("attention_lowered_xla")
    with watch.watching() as seen:
        other = threading.Thread(target=lambda: (
            flags.flag_value("FLAGS_benchmark"), stat.increase(5)))
        other.start()
        other.join(10)
        assert not other.is_alive()
        flags.flag_value("FLAGS_telemetry")
        flags.get_flags("FLAGS_fault_seed")
        stat.increase(2)
        with watch.paused():
            flags.flag_value("FLAGS_metrics_dir")
            stat.increase(7)
        stat.increase(1)
    assert seen.flags == {"FLAGS_telemetry": True, "FLAGS_fault_seed": 0}
    assert seen.stats == {"attention_lowered_xla": 3}
    before = stat.get()
    flags.flag_value("FLAGS_benchmark")
    stat.increase()
    with watch.paused():
        stat.increase()
    assert len(seen.flags) == 2 and seen.stats == {
        "attention_lowered_xla": 3}
    assert stat.get() == before + 2 and watch.active == 0


def test_the_account_has_a_part_and_no_column_for_the_store(store):
    from paddle_tpu import telemetry

    telemetry.clear_spans()
    _run_default()
    _run_default()
    spans = [s for s in telemetry.get_spans(kept=True)
             if s.name == "compile/program_store"]
    assert [s.attrs["hit"] for s in spans] == [0, 0, 1, 1]
    for s in spans:
        assert s.attrs["bytes"] > 0 and s.attrs["load_ms"] >= 0
        assert "program" in s.attrs and s.attrs["self_ms"] >= 0
    # a miss's span holds the trace and the lowering it paid; its self
    # time leaves them out
    fills = [s for s in telemetry.get_spans(kept=True)
             if s.name in ("compile/trace", "compile/lower")
             and spans[1].start <= s.start and s.end <= spans[1].end]
    assert fills and spans[1].attrs["self_ms"] < \
        (spans[1].end - spans[1].start) * 1e3
    account = telemetry.startup_account()
    assert account["program_store"]["n"] == 4
    assert all(len(row) == 7 for row in account["programs"])
    assert {r[0] for r in account["programs"]} >= {"step_fn"}


# -- a step built over a mesh (``parallel/sharded.py``, PR 62) --------------

def _sharded(n=4, axis="dp", multistep=0, **build):
    """``_net()``'s step (or ``multistep`` of them in one program) over a
    mesh of ``n`` devices, from a fresh start-up: ``(fn, args as numpy, the
    mesh)``."""
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.parallel import (build_sharded_multistep,
                                     build_sharded_step)

    main, startup, loss, _ = _net()
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    mesh = Mesh(np.array(jax.devices()[:n]), (axis,))
    feed = _feed(8)
    if multistep:
        fn, mut_in, const_in, _ = build_sharded_multistep(
            main, ["x", "y"], [loss.name], mesh, multistep,
            batch_axes=(axis,), **build)
        feed = {k: np.stack([v] * multistep) for k, v in feed.items()}
    else:
        fn, mut_in, const_in, _ = build_sharded_step(
            main, ["x", "y"], [loss.name], mesh, batch_axes=(axis,),
            **build)
    args = ((feed["x"], feed["y"]),
            tuple(np.asarray(scope.find_var(k)) for k in mut_in),
            tuple(np.asarray(scope.find_var(k)) for k in const_in))
    return fn, args, mesh


def _three_steps(step, fn, args):
    """Three steps of ``step`` from ``args``, the state threaded through
    (and donated): every loss's and the last state's bytes."""
    import jax

    feed, mut, const = (jax.device_put(a, sh) for a, sh in zip(
        args, fn.jit_kwargs["in_shardings"]))
    first, losses = mut, []
    for i in range(3):
        fetches, mut, _ = step(feed, mut, const, np.int32(i + 1))
        losses.append(np.asarray(fetches[0]).tobytes())
    assert all(m.is_deleted() for m in first), "the state was not donated"
    return losses, [np.asarray(m).tobytes() for m in mut]


@pytest.mark.parametrize("devices,multistep", [(1, 0), (4, 0), (4, 2)])
def test_a_sharded_step_misses_then_hits_and_steps_to_the_bit(
        store, monkeypatch, devices, multistep):
    from paddle_tpu import telemetry
    from paddle_tpu.parallel import sharded

    lowered = []
    lower_block = sharded.lower_block
    monkeypatch.setattr(
        sharded, "lower_block",
        lambda block, *a, **kw: (lowered.append(block),
                                 lower_block(block, *a, **kw))[1])
    telemetry.clear_spans()
    got = []
    for turn in ("miss", "hit"):
        fn, args, mesh = _sharded(devices, multistep=multistep)
        before, hits0 = _counts(), stat_get("compile_cache_hits")
        del lowered[:]
        compiled = fn.lower(*args, np.int32(1)).compile()
        assert _delta(before) == {"hits": int(turn == "hit"),
                                  "misses": int(turn == "miss"),
                                  "refused": 0}
        assert len(lowered) == (turn == "miss"), "a hit traced the Program"
        if turn == "hit":
            assert stat_get("compile_cache_hits") - hits0 == 1
        span = [s for s in telemetry.get_spans(kept=True)
                if s.name == "compile/program_store"][-1]
        assert span.attrs["hit"] == (turn == "hit")
        assert span.attrs["devices"] == mesh.size == devices
        got.append(_three_steps(compiled, fn, args))
    # the step's own jit, called as it is: the same bits
    want = _three_steps(fn, fn, args)
    assert got[0] == want and got[1] == want


def _options(value):
    from paddle_tpu.parallel import sharded

    return lambda mp: mp.setattr(
        sharded, "overlap_compiler_options",
        lambda mesh, axes: ({"xla_enable_async_all_reduce": value},
                            None)) or {}


def _replicated_feed(mp):
    from jax.sharding import PartitionSpec as P

    return dict(feed_pspecs={"x": P()})


MESH_KEY_PARTS = {
    "the builder, with its stacked feed": lambda mp: dict(multistep=1),
    "the mesh's shape": lambda mp: dict(n=2),
    "an axis name": lambda mp: dict(axis="data"),
    "a PartitionSpec": _replicated_feed,
    "donate_state": lambda mp: dict(donate_state=False),
    "a compiler option": _options(True),
    "a compiler option's value": _options(False),
}


@pytest.mark.parametrize("part", sorted(MESH_KEY_PARTS))
def test_each_part_of_a_sharded_steps_key_alone_makes_a_miss(
        store, monkeypatch, part):
    """(Lowered and never compiled: the CPU's compiler knows no option of
    the TPU's.)"""
    def lower(**build):
        fn, args, _ = _sharded(**build)
        before = _counts()
        fn.lower(*args, np.int32(1))
        got = _delta(before)
        assert got["refused"] == 0 and got["hits"] + got["misses"] == 1
        return "hit" if got["hits"] else "miss"

    if part == "a compiler option's value":
        _options(True)(monkeypatch)
    assert [lower(), lower()] == ["miss", "hit"]
    changed = MESH_KEY_PARTS[part](monkeypatch)
    assert [lower(**changed), lower(**changed)] == ["miss", "hit"]


def test_a_module_for_four_devices_is_refused_under_two(store, monkeypatch,
                                                        caplog):
    # (one key for every mesh, as a damaged or misplaced entry would have)
    monkeypatch.setattr(program_store, "_placement", lambda *a: {})
    fn, args, _ = _sharded(4)
    before = _counts()
    fn.lower(*args, np.int32(1))
    assert _delta(before) == {"hits": 0, "misses": 1, "refused": 0}
    kept = {n: os.path.getsize(os.path.join(store, n))
            for n in os.listdir(store)}
    for turn in range(2):
        fn, args, _ = _sharded(2)
        before = _counts()
        with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
            caplog.clear()
            compiled = fn.lower(*args, np.int32(1)).compile()
        assert _delta(before) == {"hits": 0, "misses": 0, "refused": 1}
        said = [r.getMessage() for r in caplog.records
                if "program store: refused" in r.getMessage()]
        assert len(said) == (turn == 0)
        assert all("a module for 4 devices" in m for m in said)
        # the step's own jit compiled it, and the entry is as it was
        assert _three_steps(compiled, fn, args) == _three_steps(fn, fn, args)
        assert {n: os.path.getsize(os.path.join(store, n))
                for n in os.listdir(store)} == kept


def test_with_no_store_a_sharded_steps_lower_is_the_plain_jits(
        tmp_path, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(program_store, "stored_step", None)
    fn, args, _ = _sharded(4)
    assert fn.digest is None
    before = _counts()
    lowered = fn.lower(*args, np.int32(1))
    assert lowered.as_text() == fn.jitted.lower(*args, np.int32(1)).as_text()
    compiled = lowered.compile()
    assert _three_steps(compiled, fn, args) == _three_steps(fn, fn, args)
    assert _delta(before) == {"hits": 0, "misses": 0, "refused": 0}
    assert os.listdir(str(tmp_path)) == []


_SCRIPT = r"""
import json, os, sys
sys.path.insert(0, sys.argv[1])
import numpy as np
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import paddle_tpu as pt
from paddle_tpu.framework import executor as executor_mod
from paddle_tpu.monitor import stat_get
sys.path.insert(0, os.path.join(sys.argv[1], "tests"))
import test_program_store as t

lowered = []
lower_block = executor_mod.lower_block
executor_mod.lower_block = lambda block, *a, **kw: (
    lowered.append(1), lower_block(block, *a, **kw))[1]
main, startup, loss, pred = t._net()
scope = pt.Scope()
with pt.scope_guard(scope):
    exe = pt.Executor()
    exe.run(startup)
    old = {n: scope.find_var(n) for n in scope.local_var_names()}
    outs = [exe.run(main, feed=t._feed(), fetch_list=[loss, pred])
            for _ in range(3)]
    state = {n: np.asarray(scope.find_var(n)) for n in sorted(old)}
print("RESULT " + json.dumps({
    "fetches": [[o.tobytes().hex() for o in out] for out in outs],
    "state": {n: v.tobytes().hex() for n, v in state.items()},
    "consumed": sorted(n for n, v in old.items() if v.is_deleted()),
    "kept": sorted(n for n, v in old.items() if not v.is_deleted()),
    "lowered": len(lowered),
    "stats": {k: stat_get(k) for k in t.STORE_STATS + (
        "compile_cache_hits", "compile_cache_misses",
        "dropout_lowered_hw_bits", "dropout_lowered_threefry")}}))
"""


def test_a_fresh_process_hits_and_returns_the_first_ones_bits(tmp_path):
    """One script in two fresh processes against one placed cache: the
    second loads every module, lowers no block, compiles nothing anew,
    donates the state the first donated, and returns its fetches and its
    state to the bit."""
    script = tmp_path / "twice.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    runs = []
    for _ in range(2):
        done = subprocess.run([sys.executable, str(script), REPO],
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert done.returncode == 0, done.stderr[-3000:]
        line, = [ln for ln in done.stdout.splitlines()
                 if ln.startswith("RESULT ")]
        runs.append(json.loads(line[len("RESULT "):]))
    cold, warm = runs
    assert cold["stats"]["program_store_misses"] == 2
    assert cold["stats"]["program_store_hits"] == 0 and cold["lowered"] == 2
    assert warm["stats"]["program_store_hits"] == 2
    assert warm["stats"]["program_store_misses"] == 0
    assert warm["stats"]["program_store_refused"] == 0
    assert warm["lowered"] == 0
    assert warm["stats"]["compile_cache_misses"] == 0
    assert warm["stats"]["compile_cache_hits"] >= 2
    for k in ("dropout_lowered_hw_bits", "dropout_lowered_threefry"):
        assert warm["stats"][k] == cold["stats"][k]
    assert warm["fetches"] == cold["fetches"]
    assert warm["state"] == cold["state"]
    # the state the step rebinds was donated (consumed) in both
    assert cold["consumed"] and warm["consumed"] == cold["consumed"]
    assert warm["kept"] == cold["kept"]
