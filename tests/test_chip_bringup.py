"""What keeps the program honest about the chip (PR 21).

``chip_smoke.py`` itself only passes on a TPU; here its two phases run at
a toy size on the CPU with the Pallas kernels in interpret mode, and the
rules around it are pinned: no TPU -> refuse, where the compile cache
lands, no CPU re-exec, one chip per replica.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY_TRAIN = dict(seq=64, hidden=128, layers=1, heads=1, ffn=256, vocab=211,
                 global_batch=8, steps=4, lr=1e-3)
TOY_SERVE = dict(hidden=64, heads=2, ffn=128, vocab=97, layers=1, slots=2,
                 max_seq=32, page_tokens=16, prefill_buckets=(8, 32),
                 prompt_lens=(5, 20), new_tokens=3,
                 mlp=dict(feat=8, hidden=32, depth=1, classes=4))


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

def test_chip_smoke_refuses_without_a_tpu():
    """The real invocation, on this CPU: non-zero, one clear line, and
    nothing on stdout that could be read as a result."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    lines = [ln for ln in r.stderr.splitlines() if "chip_smoke" in ln]
    assert len(lines) == 1 and "not 'tpu'" in lines[0]


def test_chip_smoke_train_phase_toy():
    """Kernels in interpret mode against the einsum reference, then the
    recipe through CompiledProgram over the 8 forced host devices: the
    batch is split, the loss falls, and the path taken is reported as
    what it was (the blockwise reference, no Mosaic call)."""
    out = chip_smoke.train_phase(TOY_TRAIN, on_chip=False)
    assert out["devices"] == 8 and not out["mosaic"]
    assert out["paths"] == {"blockwise": out["paths"]["blockwise"]}
    # off the chip the grad ops go through the auto-grad lowering
    assert out["grads"] == {"saved": 0,
                            "relowered": TOY_TRAIN["layers"]}
    assert out["dropout"]["hw_bits"] > 0 and not out["dropout"]["threefry"]
    assert out["losses"][-1] < out["losses"][0]


def test_chip_smoke_serve_phase_toy():
    out = chip_smoke.serve_phase(TOY_SERVE, on_chip=False)
    assert out["paths"].get("blockwise") and "pallas" not in out["paths"]
    # the bucket of two whole pages wrote page by page, the one of half a
    # page by rows
    assert out["writes"]["pages"] and out["writes"]["rows"]


def test_chip_smoke_check_raises():
    with pytest.raises(AssertionError, match="chip_smoke: boom"):
        chip_smoke.check(False, "boom")


# ---------------------------------------------------------------------------
# where the compile cache lands
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_config_writes(monkeypatch):
    """Record (and swallow) what ensure_compile_cache() would write to
    jax's cache configuration."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu import compile_cache

    writes = []
    real = jax.config.update

    def update(name, value):
        if "cache" in name:
            writes.append((name, value))
        else:
            real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    monkeypatch.setattr(cc, "reset_cache", lambda: None)
    monkeypatch.setattr(compile_cache, "_active_dir", None)
    return writes


def test_cache_placed_from_outside_is_left_alone(monkeypatch, tmp_path,
                                                 cache_config_writes):
    import jax

    from paddle_tpu import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.ensure_compile_cache() == str(tmp_path)
    assert cache_config_writes == []


def test_cache_default_is_one_fixed_dir_in_the_checkout(
        monkeypatch, cache_config_writes):
    import jax

    from paddle_tpu import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.ensure_compile_cache() == want
    assert compile_cache.ensure_compile_cache() == want
    # set once, to the fixed path: no temp name, pid or time in it
    assert cache_config_writes == [("jax_compilation_cache_dir", want)]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_backend_gets_no_persistent_cache(monkeypatch,
                                              cache_config_writes):
    from paddle_tpu import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.ensure_compile_cache() is None
    assert cache_config_writes == []


# ---------------------------------------------------------------------------
# no CPU re-exec
# ---------------------------------------------------------------------------

def test_dryrun_multichip_too_few_devices_is_an_error(monkeypatch):
    import __graft_entry__ as ge

    def no_subprocess(*a, **k):
        raise AssertionError("dryrun_multichip re-executed itself")

    monkeypatch.setattr(subprocess, "run", no_subprocess)
    monkeypatch.setattr(subprocess, "Popen", no_subprocess)
    with pytest.raises(RuntimeError, match="jax sees 8 device"):
        ge.dryrun_multichip(64)


# ---------------------------------------------------------------------------
# one chip per replica
# ---------------------------------------------------------------------------

def test_fleet_on_a_tpu_host_pins_or_refuses(monkeypatch, tmp_path):
    from paddle_tpu.serving import fleet

    assert fleet.local_tpu_chips({"JAX_PLATFORMS": "cpu"}) == 0
    monkeypatch.setattr(fleet, "local_tpu_chips", lambda env=None: 2)

    # more chip-needing replicas than chips: loud, before any spawn
    monkeypatch.setattr(fleet, "spawn_process", None)
    with pytest.raises(RuntimeError, match="3 replicas .* 2 TPU chip"):
        fleet.FleetSupervisor(replicas=3, workdir=str(tmp_path))
    # a supervisor that holds the chips itself (its process has run JAX)
    from jax._src import xla_bridge

    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: True)
    with pytest.raises(RuntimeError, match="initialised JAX"):
        fleet.FleetSupervisor(replicas=2, workdir=str(tmp_path))

    # off JAX, each replica's life is pinned to its own chip
    class Proc:
        pid = 1

        def poll(self):
            return 0

    spawned = []
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: False)
    monkeypatch.setattr(
        fleet, "spawn_process",
        lambda cmd, env, log, restart_count=0: spawned.append(env) or Proc())
    sup = fleet.FleetSupervisor(replicas=2, workdir=str(tmp_path),
                                autostart=False)
    sup._chips = sup._claim_chips()
    for rep in sup._replicas:
        sup._spawn(rep)
    assert [e["TPU_VISIBLE_CHIPS"] for e in spawned] == ["0", "1"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in spawned)


# ---------------------------------------------------------------------------
# kernels and the path taken
# ---------------------------------------------------------------------------

def test_flash_blocks_span_whole_lane_tiles_or_raise():
    from paddle_tpu.ops.pallas.flash_attention import _fit_block

    assert _fit_block(512, 2048, compiled=True) == 512
    assert _fit_block(512, 640, compiled=True) == 128
    assert _fit_block(512, 64, compiled=True) == 64   # one block: static
    assert _fit_block(512, 520) == 8                  # interpret/reference
    with pytest.raises(ValueError, match="520"):
        _fit_block(512, 520, compiled=True)


def test_short_sequence_runs_as_one_static_block():
    """A sub-128 prefill bucket: one block, indexed statically (the
    dynamic lane offset is what Mosaic refused on the chip)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (blockwise_attention,
                                                       flash_attention)

    q, k, v = (jax.random.normal(jax.random.key(i), (1, 2, 16, 128))
               for i in range(3))

    def loss(f):
        return lambda q, k, v: (f(q, k, v) ** 2).sum()

    kern = loss(lambda q, k, v: flash_attention(q, k, v, True, None,
                                                512, 512, True))
    ref = loss(lambda q, k, v: blockwise_attention(q, k, v, causal=True)[0])
    got = jax.grad(kern, (0, 1, 2))(q, k, v)
    want = jax.grad(ref, (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    assert jnp.isfinite(kern(q, k, v))


def test_attention_path_is_counted_and_a_downgrade_logged_once(caplog):
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.ops import attention_ops

    before = stat_get("attention_lowered_blockwise")
    with caplog.at_level("WARNING", logger="paddle_tpu.ops.attention"):
        attention_ops._lowered("blockwise", "test: 4-device mesh")
        attention_ops._lowered("blockwise", "test: 4-device mesh")
        attention_ops._lowered("blockwise")  # not a TPU backend: silent
    assert stat_get("attention_lowered_blockwise") == before + 3
    assert len([r for r in caplog.records
                if "test: 4-device mesh" in r.getMessage()]) == 1
    assert not pt.is_compiled_with_tpu()


def test_compiled_program_keeps_its_executable():
    from paddle_tpu import layers, optimizer

    x = layers.data("x", [4])
    y = layers.data("y", [1])
    loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
    optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    comp = pt.CompiledProgram(pt.default_main_program()).with_data_parallel(
        loss_name=loss.name)
    assert comp.executable is None
    feed = {"x": np.ones((16, 4), "float32"), "y": np.ones((16, 1), "float32")}
    first, = exe.run(comp, feed=feed, fetch_list=[loss])
    for _ in range(3):
        last, = exe.run(comp, feed=feed, fetch_list=[loss])
    assert float(last.reshape(-1)[0]) < float(first.reshape(-1)[0])
    ex = comp.executable
    assert "HloModule" in ex.as_text()
    assert ex.input_shardings[0][0][0].shard_shape((16, 4)) == (2, 4)


# ---------------------------------------------------------------------------
# native artefacts
# ---------------------------------------------------------------------------

def test_native_build_is_keyed_on_content_and_fails_loudly(monkeypatch,
                                                           tmp_path):
    import shutil

    from paddle_tpu import native

    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    (tmp_path / "ok.cc").write_text("extern \"C\" int one() { return 1; }\n")
    out = native._build("libok.so", "ok.cc", ["-shared", "-fPIC"])
    stamp = (tmp_path / "libok.so.stamp").read_text()
    # an artefact that does not match its source (it travelled with a
    # copied tree, or the source changed) is rebuilt, never loaded
    (tmp_path / "libok.so").write_bytes(b"stale")
    (tmp_path / "ok.cc").write_text("extern \"C\" int one() { return 2; }\n")
    assert native._build("libok.so", "ok.cc", ["-shared", "-fPIC"]) == out
    assert (tmp_path / "libok.so.stamp").read_text() != stamp
    assert (tmp_path / "libok.so").read_bytes() != b"stale"
    # a build that fails where g++ exists is an error, not a None
    (tmp_path / "bad.cc").write_text("this is not C++\n")
    with pytest.raises(RuntimeError, match="native build of libbad.so"):
        native._build("libbad.so", "bad.cc", ["-shared", "-fPIC"])
