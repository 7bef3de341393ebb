"""Sharded-serving tests: mesh-partitioned ShardedPredictor +
ReplicaGroupEngine under the batching/tracing front end.

The contract is the serving bit-exactness matrix extended over
topology: a caller must not be able to tell whether their request ran
on one chip, on an mp-weight-sharded group, or on any of dp
independent replica groups — against a single-device
``Predictor.run`` (``conftest.assert_logits_match``: to the
accumulation order of one matmul), at every bucket boundary, on dp-only
/ mp-only / dp×mp meshes.  Per-shard health (``worker_health``,
``/healthz``/``/statusz`` ``groups`` blocks), the degradation
contract (a failing group turns ``degraded`` but neither sinks its
requests silently nor stops its siblings), missing-shard reporting,
SIGTERM drain with in-flight sharded batches, and the mesh-aware
``clone()``/``warmup()`` fix ride along.
"""
import importlib.util
import os
import signal
import threading
import time

import numpy as np
import pytest
from conftest import assert_logits_match

import paddle_tpu as pt
from paddle_tpu import fault, layers
from paddle_tpu.inference import Predictor
from paddle_tpu.parallel import make_mesh, parse_mesh_spec
from paddle_tpu.parallel.mesh import axis_size
from paddle_tpu.serving import (OverloadedError, ReplicaGroupEngine,
                                RequestFailed, ServingEngine,
                                ShardedPredictor)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="sharded serving tests need the 8-device sim (conftest "
           "forces --xla_force_host_platform_device_count=8)")


@pytest.fixture(autouse=True)
def _reset_faults():
    fault.reset()
    yield
    fault.reset()
    pt.set_flags({"FLAGS_fault_inject": "",
                  "FLAGS_serving_group_degraded_after": 3,
                  "FLAGS_serving_mesh": ""})


def _build_mlp(feat=6, hidden=16, classes=4, depth=2, seed=0):
    """Fresh in-process MLP predictor (own program + scope).  Every
    weight's last dim is mp=2-divisible — the megatron divisibility
    rule the bit-exact contract assumes (an indivisible weight
    replicates, and contracting a still-sharded activation against it
    lets GSPMD partial-sum across devices, drifting low-order bits)."""
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    startup.random_seed = main.random_seed = seed
    with pt.program_guard(main, startup):
        x = layers.data("x", [feat])
        h = x
        for i in range(depth):
            h = layers.fc(h, hidden, act="relu", name=f"sh_fc{i}_{seed}")
        out = layers.fc(h, classes, name=f"sh_head_{seed}")
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    return Predictor(main, ["x"], [out], scope=scope)


@pytest.fixture(scope="module")
def small_model():
    p = _build_mlp()
    rng = np.random.RandomState(0)
    xs = rng.rand(64, 6).astype("float32")
    return p, xs


# ---------------------------------------------------------------------------
# mesh-spec parsing (the FLAGS_serving_mesh / --mesh surface)
# ---------------------------------------------------------------------------

def test_parse_mesh_spec_forms():
    assert parse_mesh_spec("dp=4,mp=2") == {"dp": 4, "mp": 2}
    assert parse_mesh_spec("dp4,mp2") == {"dp": 4, "mp": 2}
    assert parse_mesh_spec(" dp=2 , ep=4 ") == {"dp": 2, "ep": 4}
    assert parse_mesh_spec("") == {}
    with pytest.raises(ValueError, match="unknown mesh axis"):
        parse_mesh_spec("xx=2")
    with pytest.raises(ValueError, match=">= 1"):
        parse_mesh_spec("dp=0")
    with pytest.raises(ValueError, match="bad mesh spec"):
        parse_mesh_spec("dp")


def test_axis_size():
    mesh = make_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
    assert axis_size(mesh, "dp") == 2
    assert axis_size(mesh, "dp", "mp") == 4
    assert axis_size(mesh, "ep") == 1


# ---------------------------------------------------------------------------
# bit-exactness: dp-only / mp-only / dp x mp, at every bucket boundary
# ---------------------------------------------------------------------------

TOPOLOGIES = [
    pytest.param(dict(groups=4, mp=1), id="dp-only"),
    pytest.param(dict(groups=1, mp=2), id="mp-only"),
    pytest.param(dict(groups=2, mp=2), id="dpxmp"),
]


@pytest.mark.parametrize("topo", TOPOLOGIES)
def test_replica_groups_bit_exact_across_buckets(small_model, topo):
    """Engine outputs are those of single-device Predictor.run at
    sizes 1 / b-1 / b / b+1 (b+1 exercises the chunked oversize path
    riding the sharded pool)."""
    p, xs = small_model
    b = 4
    with ReplicaGroupEngine(p, max_batch=b, max_delay_ms=1.0,
                            deadline_ms=60000, **topo) as eng:
        for size in (1, b - 1, b, b + 1):
            ref = p.run({"x": xs[:size]})[0]
            got = eng.predict({"x": xs[:size]})[0]
            assert_logits_match(got, ref, f"{topo}: size {size}")


@pytest.mark.parametrize("topo", TOPOLOGIES)
def test_concurrent_single_rows_bit_exact(small_model, topo):
    """Concurrent 1-row submitters get batched across replica groups;
    every caller still reads exactly the single-device answer."""
    p, xs = small_model
    ref = p.run({"x": xs[:16]})[0]
    with ReplicaGroupEngine(p, max_batch=4, max_delay_ms=2.0,
                            deadline_ms=60000, **topo) as eng:
        futs = [eng.submit({"x": xs[i:i + 1]}) for i in range(16)]
        for i, f in enumerate(futs):
            assert_logits_match(f.result(60)[0], ref[i:i + 1])


def test_sharded_predictor_run_matches_plain(small_model):
    """ShardedPredictor.run (no engine) is bit-exact vs the plain
    Predictor for every bucket size, including the GEMM-padded 1-row
    path on a weight-sharded mesh."""
    p, xs = small_model
    sp = ShardedPredictor(p.program, p.feed_names, p.fetch_names,
                          scope=p.scope,
                          mesh=make_mesh({"mp": 2},
                                         devices=jax.devices()[:2]))
    for size in (1, 3, 4, 8):
        ref = p.run({"x": xs[:size]})[0]
        assert_logits_match(sp.run({"x": xs[:size]})[0], ref)


# ---------------------------------------------------------------------------
# predictor contract: clone / warmup / cache_info / placement
# ---------------------------------------------------------------------------

def test_mesh_aware_clone_shares_executables(small_model):
    p, xs = small_model
    sp = ShardedPredictor(p.program, p.feed_names, p.fetch_names,
                          scope=p.scope,
                          mesh=make_mesh({"mp": 2},
                                         devices=jax.devices()[:2]))
    sp.run({"x": xs[:4]})
    c = sp.clone()
    assert type(c) is ShardedPredictor
    assert c.mesh is sp.mesh
    assert c._cache is sp._cache          # shared sharded executables
    assert c.scope is sp.scope            # shared placed weight shards
    assert_logits_match(c.run({"x": xs[:4]})[0],
                        p.run({"x": xs[:4]})[0])


def test_mesh_aware_warmup_primes_executed_buckets(small_model):
    """warmup() on a weight-sharded mesh must prime the executable
    1-row requests actually hit (the GEMM-padded 2-row form), so the
    first real request compiles nothing."""
    p, xs = small_model
    sp = ShardedPredictor(p.program, p.feed_names, p.fetch_names,
                          scope=p.scope,
                          mesh=make_mesh({"mp": 2},
                                         devices=jax.devices()[:2]))
    compiled = sp.warmup([{"x": (1, 6)}, {"x": (4, 6)}])
    assert compiled == 2
    n_before = len(sp.cache_info()["signatures"])
    sp.run({"x": xs[:1]})
    sp.run({"x": xs[:4]})
    assert len(sp.cache_info()["signatures"]) == n_before


def test_cache_info_names_the_mesh(small_model):
    p, xs = small_model
    sp = ShardedPredictor(p.program, p.feed_names, p.fetch_names,
                          scope=p.scope,
                          mesh=make_mesh({"mp": 2},
                                         devices=jax.devices()[:2]))
    sp.run({"x": xs[:2]})
    info = sp.cache_info()
    assert info["mesh"] == "mp=2"
    assert info["devices"] == [0, 1]
    assert info["signatures"]  # XLA manifests still attached


def test_placement_reports_missing_shards(small_model):
    p, xs = small_model
    sp = ShardedPredictor(p.program, p.feed_names, p.fetch_names,
                          scope=p.scope,
                          mesh=make_mesh({"mp": 2},
                                         devices=jax.devices()[:2]))
    assert sp.placement()["missing_shards"] == []
    assert sp.placement(live_ids={0})["missing_shards"] == [1]


def test_plain_predictor_clone_still_plain(small_model):
    """The mesh-aware clone() must not change the base contract: a
    plain Predictor's clone is a plain Predictor sharing scope."""
    p, xs = small_model
    c = p.clone()
    assert type(c) is Predictor
    assert c.scope is p.scope
    assert np.array_equal(c.run({"x": xs[:2]})[0],
                          p.run({"x": xs[:2]})[0])


# ---------------------------------------------------------------------------
# per-shard health: worker_health / healthz / statusz
# ---------------------------------------------------------------------------

def test_per_shard_health_fields(small_model):
    p, xs = small_model
    with ReplicaGroupEngine(p, groups=2, mp=2, max_batch=4,
                            max_delay_ms=1.0,
                            deadline_ms=60000) as eng:
        for i in range(8):
            eng.predict({"x": xs[i:i + 1]})
        health = eng.worker_health()
        assert len(health) == 2
        for g in health:
            for field in ("worker", "batches", "failures",
                          "consecutive_failures", "degraded",
                          "in_flight_rows", "rows_total", "last_batch",
                          "predict_ms", "avg_batch_rows", "mesh",
                          "devices", "missing_shards", "status"):
                assert field in g, f"worker_health missing {field!r}"
            assert g["status"] == "ok"
            assert g["mesh"] == "mp=2"
            assert len(g["devices"]) == 2
        assert health[0]["devices"] != health[1]["devices"]  # disjoint
        # at least one group served something, and the totals add up
        assert sum(g["batches"] for g in health) >= 1
        assert sum(g["rows_total"] for g in health) == 8
        # /healthz and /statusz carry the same per-group block
        hz = eng.health()
        assert hz["status"] == "ok"
        assert [g["status"] for g in hz["groups"]] == ["ok", "ok"]
        sz = eng.introspect()
        assert len(sz["groups"]) == 2
        assert sz["replica_groups"] == {"groups": 2,
                                        "group_axes": {"mp": 2, "ep": 1},
                                        "devices_per_group": 2}
        # executables inventory names which shard set each runs on
        assert all("mesh" in e for e in sz["executables"])


def test_missing_shards_flips_group_and_healthz(small_model):
    """A group whose mesh devices vanish from the live set reports
    missing_shards; /healthz degrades while siblings stay ok."""
    p, xs = small_model
    with ReplicaGroupEngine(p, groups=2, mp=1, max_batch=4,
                            max_delay_ms=1.0,
                            deadline_ms=60000) as eng:
        eng.predict({"x": xs[:2]})
        victim = eng._pool[1]
        orig = victim.placement
        victim.placement = lambda live_ids=None: orig(
            live_ids={d for d in range(8) if d not in
                      victim.device_ids()})
        try:
            health = eng.worker_health()
            assert health[0]["status"] == "ok"
            assert health[1]["status"] == "missing_shards"
            assert health[1]["missing_shards"] == victim.device_ids()
            assert eng.health()["status"] == "degraded"
        finally:
            victim.placement = orig
        assert eng.health()["status"] == "ok"


# ---------------------------------------------------------------------------
# degradation contract: one poisoned group, siblings keep serving
# ---------------------------------------------------------------------------

def test_serve_batch_fail_isolated_to_one_group(small_model):
    """serve_batch:fail@1 with degraded_after=1: the one group that
    picked the poisoned batch turns degraded (visible in /healthz),
    its requests get a real error, every other group keeps serving
    bit-exact answers — and one later success clears the streak."""
    from paddle_tpu.monitor import stat_get

    p, xs = small_model
    pt.set_flags({"FLAGS_serving_group_degraded_after": 1})
    fault.configure("serve_batch:fail@1")
    fails_before = stat_get("serving_batch_failures")
    ref = p.run({"x": xs[:4]})[0]
    with ReplicaGroupEngine(p, groups=4, mp=1, max_batch=4,
                            max_delay_ms=1.0,
                            deadline_ms=60000) as eng:
        first = eng.submit({"x": xs[:4]})
        with pytest.raises(RequestFailed, match="injected"):
            first.result(60)
        health = eng.worker_health()
        degraded = [g for g in health if g["status"] == "degraded"]
        assert len(degraded) == 1, \
            "exactly the group that ran the poisoned batch degrades"
        assert degraded[0]["consecutive_failures"] == 1
        assert eng.health()["status"] == "degraded"
        assert eng.stats()["groups_degraded"] == 1
        # the other three groups never saw a failure
        assert all(g["failures"] == 0 for g in health
                   if g["worker"] != degraded[0]["worker"])
        # siblings (and, eventually, the degraded group itself) keep
        # serving: every follow-up request completes bit-exact
        futs = [eng.submit({"x": xs[:4]}) for _ in range(8)]
        for f in futs:
            assert_logits_match(f.result(60)[0], ref)
        # success on the degraded group resets its streak; drive
        # traffic until every group served at least one ok batch
        deadline = time.monotonic() + 30
        while eng.stats()["groups_degraded"]:
            assert time.monotonic() < deadline, \
                "degraded flag never cleared"
            eng.predict({"x": xs[:4]})
    assert stat_get("serving_batch_failures") == fails_before + 1


# ---------------------------------------------------------------------------
# SIGTERM drain with in-flight sharded batches
# ---------------------------------------------------------------------------

def test_sigterm_drains_sharded_batches_then_rejects(small_model):
    p, xs = small_model
    eng = ReplicaGroupEngine(p, groups=2, mp=2, max_batch=4,
                             max_delay_ms=2.0, deadline_ms=60000)
    eng.install_sigterm()
    try:
        futs = [eng.submit({"x": xs[i:i + 1]}) for i in range(12)]
        os.kill(os.getpid(), signal.SIGTERM)
        ref = p.run({"x": xs[:12]})[0]
        # every in-flight sharded batch completes with a real answer
        for i, f in enumerate(futs):
            assert_logits_match(f.result(60)[0], ref[i:i + 1])
        deadline = time.monotonic() + 30
        while any(t.is_alive() for t in eng._threads):
            assert time.monotonic() < deadline, "drain did not finish"
            time.sleep(0.01)
        with pytest.raises(OverloadedError, match="draining"):
            eng.submit({"x": xs[:1]})
    finally:
        eng.close()
        signal.signal(signal.SIGTERM, signal.SIG_DFL)


# ---------------------------------------------------------------------------
# topology resolution (flags / spec / kwargs) + guardrails
# ---------------------------------------------------------------------------

def test_topology_from_flag_and_spec(small_model):
    p, xs = small_model
    pt.set_flags({"FLAGS_serving_mesh": "dp=2,mp=2"})
    with ReplicaGroupEngine(p, max_batch=4, max_delay_ms=1.0,
                            deadline_ms=60000) as eng:
        assert eng.replica_groups == 2
        assert eng.group_axes == {"mp": 2, "ep": 1}
    # an explicit mesh_spec wins over the flag
    with ReplicaGroupEngine(p, mesh_spec="dp=4", max_batch=4,
                            max_delay_ms=1.0, deadline_ms=60000) as eng:
        assert eng.replica_groups == 4
        assert eng.group_axes == {"mp": 1, "ep": 1}


def test_topology_guardrails(small_model):
    p, _ = small_model
    with pytest.raises(ValueError, match="needs"):
        ReplicaGroupEngine(p, groups=8, mp=2)   # 16 devices on an 8-sim
    # a training topology string must not silently serve on a
    # fraction of the devices
    with pytest.raises(ValueError, match="does not serve over"):
        ReplicaGroupEngine(p, mesh_spec="dp=2,pp=4")
    # a malformed flag must not break a fully-kwarg'd constructor
    pt.set_flags({"FLAGS_serving_mesh": "dp=garbage"})
    with ReplicaGroupEngine(p, groups=2, mp=1, ep=1, max_batch=4,
                            max_delay_ms=1.0, deadline_ms=60000) as eng:
        assert eng.replica_groups == 2
    pt.set_flags({"FLAGS_serving_mesh": ""})
    sp = ShardedPredictor(p.program, p.feed_names, p.fetch_names,
                          scope=p.scope,
                          mesh=make_mesh({"mp": 2},
                                         devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="unplaced"):
        ReplicaGroupEngine(sp, groups=2)
    with pytest.raises(ValueError):
        ShardedPredictor(p.program, p.feed_names, p.fetch_names,
                         scope=p.scope)         # no mesh


# ---------------------------------------------------------------------------
# mesh-partitioned generation (Llama decode over mp kv-heads)
# ---------------------------------------------------------------------------

def test_generation_mesh_partitioned_bit_exact():
    """A GenerationEngine on an mp=2 mesh (weights sharded, KV page
    pools sharded over kv-heads) emits the SAME token streams as
    the single-device engine with the same seed."""
    from paddle_tpu.serving import GenerationEngine

    model = dict(vocab_size=64, hidden=32, num_layers=2, num_heads=4,
                 num_kv_heads=4, intermediate=64)
    prompts = [np.arange(3, 9, dtype="int64"),
               np.arange(5, 9, dtype="int64")]

    def run(mesh, scope=None):
        eng = GenerationEngine(model, num_slots=2, max_seq_len=32,
                               max_new_tokens=8, seed=7, mesh=mesh,
                               scope=scope, deadline_ms=60000)
        try:
            return ([eng.generate(q, 8)["tokens"] for q in prompts],
                    eng.stats(), eng.scope)
        finally:
            eng.close()

    # the meshed engine SHARES the reference engine's scope (the
    # documented zero-copy handoff): same weights, so any token
    # divergence is the mesh partitioning — not the global op-seed
    # advancing between two in-process builds
    ref_tokens, _, scope = run(None)
    mesh = make_mesh({"mp": 2}, devices=jax.devices()[:2])
    got_tokens, stats, _ = run(mesh, scope=scope)
    assert got_tokens == ref_tokens
    assert stats["mesh"] == "mp=2"
    assert stats["kv_shard_axis"] == "mp"


# ---------------------------------------------------------------------------
# loadgen --sharded SLO contract
# ---------------------------------------------------------------------------

def _load_loadgen():
    path = os.path.join(REPO, "tools", "serving_loadgen.py")
    spec = importlib.util.spec_from_file_location("serving_loadgen",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_loadgen_slo_fails_on_degraded_group():
    lg = _load_loadgen()
    rep = {"mode": "closed", "latency_ms": {"p99": 5.0},
           "shed_rate": 0.0,
           "groups": [{"worker": 0, "status": "ok"},
                      {"worker": 1, "status": "degraded",
                       "mesh": "mp=2", "devices": [2, 3]}]}
    slo = lg.check_slo(rep, fail_degraded=True)
    assert not slo["ok"]
    assert any("degraded" in v for v in slo["violations"])
    # same contract against an embedded live-server /statusz block
    # (the real endpoint nests the groups under "engine")
    rep2 = {"mode": "closed", "latency_ms": {"p99": 5.0},
            "statusz": {"engine": {"groups": [
                {"worker": 0, "status": "missing_shards"}]}}}
    slo2 = lg.check_slo(rep2, fail_degraded=True)
    assert not slo2["ok"]
    # and a healthy report passes
    assert lg.check_slo(rep2 | {"statusz": {"engine": {"groups": [
        {"worker": 0, "status": "ok"}]}}}, fail_degraded=True)["ok"]
