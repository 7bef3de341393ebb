"""Asynchronous executor pipeline: deferred non-finite guard, lazy
FetchHandles, run_async/sync, double-buffered feeds, persistent compile
cache, and the host_syncs accounting that proves the loop is fence-free.
"""
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.monitor import stat_get
from paddle_tpu.train_guard import TrainGuard


@pytest.fixture(autouse=True)
def _default_flags():
    yield
    pt.set_flags({"FLAGS_guard_resolve_interval": 64,
                  "FLAGS_feed_double_buffer": True})


def _net(lr=0.1):
    x = layers.data("x", [4])
    y = layers.data("y", [1])
    pred = layers.fc(x, 1)
    loss = layers.mean(pt.layers.square_error_cost(pred, y))
    optimizer.SGDOptimizer(lr).minimize(loss)
    return loss


def _feed(seed=0, nan=False):
    rng = np.random.RandomState(seed)
    x = rng.rand(8, 4).astype("float32")
    if nan:
        x = np.full_like(x, np.nan)
    return {"x": x, "y": (x.sum(1, keepdims=True) * 0.5).astype("float32")}


def _startup(scope=None):
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), scope=scope)
    return exe


# ---------------------------------------------------------------------------
# the tentpole invariant: a guarded async run is O(1) host syncs
# ---------------------------------------------------------------------------

def test_run_async_guarded_50_steps_o1_host_syncs():
    loss = _net()
    feed = _feed()
    exe = _startup()
    g = TrainGuard(exe, loss, handle_sigterm=False)
    # warm the jit cache so compile isn't part of the measured window
    g.step_async(feed, fetch_list=[loss])
    exe.sync()

    h0 = stat_get("host_syncs")
    res = None
    for _ in range(50):
        res = g.step_async(feed, fetch_list=[loss])
    dispatched = stat_get("host_syncs") - h0
    assert dispatched == 0, \
        f"async dispatch paid {dispatched} host syncs over 50 steps"
    out = res.sync()  # one fence + one guard resolution + one fetch read
    total = stat_get("host_syncs") - h0
    assert total <= 4, f"O(1) expected after sync, got {total}"
    assert np.isfinite(out[0]).all()
    g.close()


def test_sync_run_unchanged_semantics():
    """return_numpy=True keeps blocking-numpy semantics and resolves the
    guard at the fetch read (per-step, like PR 1)."""
    loss = _net()
    feed = _feed()
    exe = _startup()
    g = TrainGuard(exe, loss, handle_sigterm=False)
    out = g.step(feed, fetch_list=[loss])
    assert isinstance(out[0], np.ndarray)
    assert not exe._pending_guard  # resolved by the fetch read
    g.close()


# ---------------------------------------------------------------------------
# deferred guard: verdicts land late but intact, with original step ids
# ---------------------------------------------------------------------------

def test_deferred_guard_callback_gets_original_step():
    loss = _net()
    exe = _startup()
    seen = []
    g = TrainGuard(exe, loss, on_nonfinite=seen.append,
                   handle_sigterm=False)
    pt.set_flags({"FLAGS_guard_resolve_interval": 0})  # defer to close
    sk0 = stat_get("skipped_nonfinite_steps")
    for i in range(6):
        g.step_async(_feed(nan=(i == 2)))  # counter step: startup=1 -> 4
    assert seen == []                       # nothing resolved yet
    assert len(exe._pending_guard) == 6
    g.close()                               # close() resolves + fires
    assert seen == [4]
    assert stat_get("skipped_nonfinite_steps") == sk0 + 1
    assert g.skipped_steps == 1


def test_guard_resolve_interval_batches():
    loss = _net()
    exe = _startup()
    g = TrainGuard(exe, loss, handle_sigterm=False)
    pt.set_flags({"FLAGS_guard_resolve_interval": 4})
    r0 = stat_get("guard_resolutions")
    for _ in range(8):                      # no fetches -> interval rules
        g.step_async(_feed())
    assert stat_get("guard_resolutions") == r0 + 2
    assert len(exe._pending_guard) == 0
    g.close()


def test_fetch_read_resolves_guard_up_to_its_step():
    loss = _net()
    exe = _startup()
    g = TrainGuard(exe, loss, handle_sigterm=False)
    pt.set_flags({"FLAGS_guard_resolve_interval": 0})
    r1 = g.step_async(_feed(), fetch_list=[loss])
    r2 = g.step_async(_feed(), fetch_list=[loss])
    g.step_async(_feed(), fetch_list=[loss])
    assert len(exe._pending_guard) == 3
    r2[0].numpy()                           # reading step N resolves <= N
    assert len(exe._pending_guard) == 1
    r1[0].numpy()                           # older handle: nothing left <= N-1
    assert len(exe._pending_guard) == 1
    g.close()
    assert not exe._pending_guard


# ---------------------------------------------------------------------------
# FetchHandle laziness
# ---------------------------------------------------------------------------

def test_fetch_handle_lazy_and_correct():
    x = layers.data("x", [4], append_batch_size=False)
    out = layers.scale(x, scale=2.0)
    exe = pt.Executor()
    a = np.arange(4, dtype="float32")
    h0 = stat_get("host_syncs")
    (h,) = exe.run(feed={"x": a.reshape(1, 4)[0:1]}, fetch_list=[out],
                   return_numpy=False)
    assert isinstance(h, pt.FetchHandle)
    # metadata reads must not fence
    assert h.shape == (4,) or h.shape == (1, 4)
    assert str(np.dtype(str(h.dtype))) == "float32"
    assert stat_get("host_syncs") == h0
    np.testing.assert_allclose(np.asarray(h).reshape(-1), a * 2)
    assert stat_get("host_syncs") == h0 + 1
    np.asarray(h)  # cached: second read is free
    assert stat_get("host_syncs") == h0 + 1


def test_run_async_result_protocol():
    loss = _net()
    exe = _startup()
    res = exe.run_async(feed=_feed(), fetch_list=[loss])
    assert len(res) == 1
    assert isinstance(res[0], pt.FetchHandle)
    vals = res.sync()
    assert isinstance(vals[0], np.ndarray)
    assert list(res)[0] is res[0]


# ---------------------------------------------------------------------------
# double-buffered feeds
# ---------------------------------------------------------------------------

def test_feed_double_buffer_stages_device_arrays():
    loss = _net()
    exe = _startup()
    for i in range(3):
        exe.run(feed=_feed(), fetch_list=[loss])
    # ring holds the last 2 staged feeds, all device-resident
    assert len(exe._feed_ring) == 2
    for staged in exe._feed_ring:
        for v in staged.values():
            assert hasattr(v, "devices"), "feed was not device_put-staged"
    pt.set_flags({"FLAGS_feed_double_buffer": False})
    exe2 = pt.Executor()
    exe2.run(pt.default_startup_program())
    out = exe2.run(feed=_feed(), fetch_list=[loss])
    assert np.isfinite(out[0]).all()
    assert not exe2._feed_ring


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_hits_across_executors(tmp_path, monkeypatch):
    """A cache placed from outside (JAX_COMPILATION_CACHE_DIR): the
    program sets no directory of its own, the cache lands there, and a
    restarted executor's hit feeds the stat."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu import compile_cache

    # jax reads the variable at import; mid-process, set what it would
    # have read.  jax persists only programs that took about a second to
    # compile; this one is tiny, so drop its thresholds for the test.
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
        feed = _feed()
        exe = _startup()
        exe.run(feed=feed, fetch_list=[loss])
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert os.listdir(str(tmp_path)), \
            "no persistent cache entries written"

        # a "restarted" executor (fresh jit cache, same program): jax
        # serves the XLA binary from disk and its cache_hits monitoring
        # event feeds the stat
        h0 = stat_get("compile_cache_hits")
        exe2 = pt.Executor()
        exe2.run(pt.default_startup_program())
        exe2.run(feed=feed, fetch_list=[loss])
        assert stat_get("compile_cache_hits") >= h0 + 1
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


# ---------------------------------------------------------------------------
# weight normalization (satellite: WeightNormParamAttr is real now)
# ---------------------------------------------------------------------------

def test_weight_norm_param_attr_reparameterizes():
    x = layers.data("x", [4])
    y = layers.data("y", [1])
    pred = layers.fc(x, 3, param_attr=pt.WeightNormParamAttr(dim=1))
    pred = layers.fc(pred, 1, param_attr=pt.WeightNormParamAttr(dim=None))
    loss = layers.mean(pt.layers.square_error_cost(pred, y))
    optimizer.SGDOptimizer(0.05).minimize(loss)
    names = [p.name for p in pt.default_main_program().all_parameters()]
    v_names = [n for n in names if n.endswith(".w_v")]
    g_names = [n for n in names if n.endswith(".w_g")]
    assert len(v_names) == 2 and len(g_names) == 2

    exe = _startup()
    scope = pt.global_scope()
    # g seeded to ||v||: initial effective weight == plain init
    v0 = np.asarray(scope.find_var(v_names[0]))
    g0 = np.asarray(scope.find_var(g_names[0]))
    np.testing.assert_allclose(g0, np.sqrt((v0 ** 2).sum(0)), rtol=1e-5)

    feed = _feed()
    losses = [float(exe.run(feed=feed, fetch_list=[loss])[0])
              for _ in range(25)]
    assert losses[-1] < losses[0]  # fixed batch: must strictly train
    # both halves of the reparameterization trained
    assert not np.allclose(np.asarray(scope.find_var(v_names[0])), v0)
    assert not np.allclose(np.asarray(scope.find_var(g_names[0])), g0)


def test_weight_norm_dygraph_warns_and_degrades():
    from paddle_tpu import dygraph
    with dygraph.guard():
        with pytest.warns(UserWarning, match="WeightNormParamAttr"):
            fc = dygraph.Linear(4, 2,
                                param_attr=pt.WeightNormParamAttr(dim=0))
        out = fc(dygraph.to_variable(np.ones((2, 4), "float32")))
        assert tuple(out.shape) == (2, 2)
