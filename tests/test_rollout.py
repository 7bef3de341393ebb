"""Safe weight rollout tests: in-place hot-swap discipline at every
layer (Predictor -> ServingEngine -> HTTP /swap -> fleet) plus the
router's canary traffic-shift with burn-rate auto-revert.

The load-bearing contracts:

* **Validated before applied** — structural drift (shape/dtype/missing
  name) raises :class:`SwapMismatch` with NOTHING flipped; the old
  weights keep serving bit-exactly.
* **Atomic or rolled back** — a commit failure mid-swap (the
  ``weight_swap`` fault site) restores every already-flipped array; a
  torn mix of versions is never observable.
* **Zero recompiles** — the compiled executables outlive the weights:
  the predictor's signature cache must not grow across a swap.
* **Version honesty** — every data-plane HTTP reply names the weights
  version that answered it (``X-PaddleTPU-Weights-Version``), bumped
  only on successful swap/revert.
* **Warming replicas shed** — a replica gated on warmup refuses
  data-plane POSTs with an explicit 503 until warmup finishes (an
  early request would race the warmup pass on donated buffers).
* **Canary verdicts** — a NaN-poisoned checkpoint on the canary
  minority burns the short-window SLO judge and auto-reverts; a clean
  checkpoint soaks and promotes fleet-wide.
"""
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
from conftest import assert_logits_match

import paddle_tpu as pt
from paddle_tpu import fault, layers
from paddle_tpu.flags import set_flags
from paddle_tpu.framework.core import reset_unique_name
from paddle_tpu.inference import Predictor, SwapMismatch
from paddle_tpu.serving import GenerationEngine, ServingEngine, serve
from paddle_tpu.serving.replica import build_synthetic_checkpoint
from paddle_tpu.serving.router import Router, RouterServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIMS = dict(feat=8, hidden=16, depth=1, classes=4)
VERSION_HEADER = "X-PaddleTPU-Weights-Version"


def _build_replica_predictor(seed=0):
    """A predictor structurally identical to the synthetic-MLP replica
    (``rep_fc0``/``rep_head`` parameter names), so checkpoints minted
    by :func:`build_synthetic_checkpoint` swap onto it."""
    reset_unique_name()
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    startup.random_seed = main.random_seed = seed
    with pt.program_guard(main, startup):
        x = layers.data("x", [DIMS["feat"]])
        h = layers.fc(x, DIMS["hidden"], act="relu", name="rep_fc0")
        out = layers.fc(h, DIMS["classes"], name="rep_head")
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    return Predictor(main, ["x"], [out], scope=scope)


def _ckpt(tmp_path, name, seed, poison_nan=False, **overrides):
    d = str(tmp_path / name)
    build_synthetic_checkpoint(d, seed=seed, poison_nan=poison_nan,
                               **{**DIMS, **overrides})
    return d


def _probe():
    return np.linspace(-1.0, 1.0, DIMS["feat"],
                       dtype="float32").reshape(1, DIMS["feat"])


def _mlp_reference(params, x):
    """Numpy forward of the rep MLP from raw checkpoint arrays."""
    h = np.maximum(x @ params["rep_fc0.w_0"] + params["rep_fc0.w_1"],
                   0.0)
    return h @ params["rep_head.w_0"] + params["rep_head.w_1"]


def _post(url, doc, timeout=30.0):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read()), dict(r.headers)


# ---------------------------------------------------------------------------
# Predictor layer: validate -> commit-or-rollback -> revert
# ---------------------------------------------------------------------------

def test_predictor_swap_bit_exact_no_recompile(tmp_path):
    pred = _build_replica_predictor(seed=0)
    x = _probe()
    before = pred.run({"x": x})[0]

    ck = _ckpt(tmp_path, "ck_v2", seed=2)
    from paddle_tpu import io
    params = io._read(os.path.join(ck, "__params__"))
    expected = _mlp_reference(params, x)
    assert not np.array_equal(before, expected), \
        "seed 2 checkpoint must actually change the function"

    cached_sigs = set(pred._cache)
    res = pred.swap_weights(ck)
    assert res["replaced"] == len(params)
    after = pred.run({"x": x})[0]
    np.testing.assert_array_equal(after, expected.astype(after.dtype))
    # the executables outlived the weights: same signature cache, no
    # recompile for the already-warm shape
    assert set(pred._cache) == cached_sigs

    # single-level revert restores the original arrays bit-exactly;
    # a revert is itself a swap, so reverting AGAIN toggles back to
    # the checkpoint (the retained level is always "what I replaced")
    pred.revert_weights()
    np.testing.assert_array_equal(pred.run({"x": x})[0], before)
    pred.revert_weights()
    np.testing.assert_array_equal(pred.run({"x": x})[0], after)


def test_predictor_swap_mismatch_applies_nothing(tmp_path):
    pred = _build_replica_predictor(seed=0)
    x = _probe()
    before = pred.run({"x": x})[0]
    bad = _ckpt(tmp_path, "ck_wide", seed=3, hidden=32)
    with pytest.raises(SwapMismatch) as e:
        pred.swap_weights(bad)
    assert "shape" in str(e.value)
    np.testing.assert_array_equal(pred.run({"x": x})[0], before)
    with pytest.raises(SwapMismatch):
        pred.swap_weights(str(tmp_path / "nonexistent"))


def test_predictor_swap_fault_rolls_back(tmp_path):
    pred = _build_replica_predictor(seed=0)
    x = _probe()
    before = pred.run({"x": x})[0]
    ck = _ckpt(tmp_path, "ck_v2", seed=2)
    fault.configure("weight_swap:fail@2")
    try:
        with pytest.raises(fault.InjectedFault):
            pred.swap_weights(ck)  # dies after flipping one array
    finally:
        fault.configure("")
    # rollback restored the flipped array: still the OLD function,
    # never a torn mix of versions
    np.testing.assert_array_equal(pred.run({"x": x})[0], before)
    with pytest.raises(SwapMismatch):
        pred.revert_weights()  # a failed swap retains nothing


# ---------------------------------------------------------------------------
# Engine + HTTP: /swap taxonomy, version header, warming shed
# ---------------------------------------------------------------------------

def test_http_swap_versions_and_refusals(tmp_path):
    eng = ServingEngine(_build_replica_predictor(seed=0), workers=1,
                        max_batch=2, max_delay_ms=1.0,
                        deadline_ms=60000.0)
    srv = serve(eng, port=0)
    try:
        x = _probe()
        code, doc, hdr = _post(srv.url + "/predict",
                               {"inputs": {"x": x.tolist()}})
        assert code == 200 and hdr[VERSION_HEADER] == "1"
        before = doc["outputs"][0]

        # structural drift -> 409, nothing flipped
        bad = _ckpt(tmp_path, "ck_wide", seed=3, hidden=32)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url + "/swap", {"dir": bad})
        assert e.value.code == 409
        assert json.loads(e.value.read())["error"] == "swap_mismatch"
        assert eng.weights_version == 1

        # clean swap -> 200, version bump, header flips, bit-exact
        ck = _ckpt(tmp_path, "ck_v2", seed=2)
        code, doc, _ = _post(srv.url + "/swap", {"dir": ck})
        assert code == 200 and doc["weights_version"] == 2
        assert doc["swap_ms"] >= 0
        from paddle_tpu import io
        params = io._read(os.path.join(ck, "__params__"))
        code, doc, hdr = _post(srv.url + "/predict",
                               {"inputs": {"x": x.tolist()}})
        assert code == 200 and hdr[VERSION_HEADER] == "2"
        np.testing.assert_allclose(np.asarray(doc["outputs"][0]),
                                   _mlp_reference(params, x),
                                   rtol=0, atol=0)
        assert doc["outputs"][0] != before

        # /swap revert -> 200, version bumps again (versions are
        # monotonic per replica: a revert is a NEW rollout decision)
        code, doc, _ = _post(srv.url + "/swap", {"revert": True})
        assert code == 200 and doc["weights_version"] == 3
        code, doc, hdr = _post(srv.url + "/predict",
                               {"inputs": {"x": x.tolist()}})
        assert hdr[VERSION_HEADER] == "3"
        assert doc["outputs"][0] == before

        # draining -> 503 overloaded (old weights keep serving)
        with eng._cv:
            eng._draining = True
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(srv.url + "/swap", {"dir": ck})
            assert e.value.code == 503
            assert json.loads(e.value.read())["error"] == "overloaded"
        finally:
            with eng._cv:
                eng._draining = False
        assert eng.weights_version == 3
    finally:
        srv.close()
        eng.close()


def test_engine_swap_under_load_never_torn(tmp_path):
    """Swap while requests stream through: every answer must be the
    answer of exactly ONE version — the pre-swap function or the
    post-swap function (``conftest.assert_logits_match``: the other
    version's answer is 0.1 away), never a mix (and the engine must
    not shed: a swap pauses, it never drops)."""
    eng = ServingEngine(_build_replica_predictor(seed=0), workers=2,
                        max_batch=4, max_delay_ms=1.0,
                        deadline_ms=60000.0)
    try:
        ck = _ckpt(tmp_path, "ck_v2", seed=2)
        from paddle_tpu import io
        params = io._read(os.path.join(ck, "__params__"))
        x = _probe()
        old = eng.submit({"x": x}).result(30.0)[0]
        new = _mlp_reference(params, x).astype(np.asarray(old).dtype)

        futs = [eng.submit({"x": x}) for _ in range(16)]
        res = eng.swap_weights(ck, timeout_s=30.0)
        futs += [eng.submit({"x": x}) for _ in range(16)]
        assert res["weights_version"] == 2
        def answers(got, want):
            try:
                assert_logits_match(got, want)
            except AssertionError:
                return False
            return True

        assert not answers(old, new), "the swap must change the function"
        for f in futs:
            got = np.asarray(f.result(30.0)[0])
            assert answers(got, old) or answers(got, new), \
                "torn or corrupted response across the swap boundary"
        # post-swap requests all serve the new function
        got = np.asarray(eng.submit({"x": x}).result(30.0)[0])
        assert_logits_match(got, new, "after the swap")
    finally:
        eng.close()


def test_http_warming_replica_sheds():
    """A replica gated on warmup refuses data-plane POSTs outright:
    admission before warmup would race the warmup pass's direct
    program runs on donated buffers (SIGABRT, not an error reply)."""
    import tools.serving_loadgen as lg
    reset_unique_name()
    predictor, shapes = lg.build_synthetic(feat=8, hidden=16, depth=1,
                                           classes=4)
    eng = ServingEngine(predictor, workers=1, max_batch=2,
                        max_delay_ms=1.0, deadline_ms=60000.0,
                        ready_requires_warmup=True)
    srv = serve(eng, port=0)
    try:
        x = _probe()
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url + "/predict", {"inputs": {"x": x.tolist()}})
        assert e.value.code == 503
        doc = json.loads(e.value.read())
        assert doc["reason"] == "warming"
        assert e.value.headers.get("Retry-After")
        assert e.value.headers.get(VERSION_HEADER) == "1"

        eng.warmup(shapes)
        code, _, _ = _post(srv.url + "/predict",
                           {"inputs": {"x": x.tolist()}})
        assert code == 200
    finally:
        srv.close()
        eng.close()


# ---------------------------------------------------------------------------
# Generation: decode-boundary swap
# ---------------------------------------------------------------------------

MODEL = dict(vocab_size=61, hidden=32, num_layers=2, num_heads=4,
             num_kv_heads=2, intermediate=64)


def _gen_engine(seed):
    return GenerationEngine(MODEL, num_slots=2, max_seq_len=48,
                            max_new_tokens=6, attn_impl="xla",
                            seed=seed, queue_cap=32,
                            deadline_ms=600000.0)


def test_generation_swap_decode_boundary():
    eng_a = _gen_engine(seed=0)
    eng_b = _gen_engine(seed=1)
    try:
        prompt = [3, 14, 15, 9, 2]
        want = eng_b.submit(list(prompt), max_new_tokens=6) \
                    .result(120.0)["tokens"]
        arrays = {n: np.array(eng_b.scope.find_var(n))
                  for n in eng_a._weight_names()}

        # boundary swap with the scheduler live: first run A so its
        # thread + grid are hot, then commit between grid steps
        eng_a.submit(list(prompt), max_new_tokens=6).result(120.0)
        res = eng_a.swap_weights(arrays)
        assert res["weights_version"] == 2
        got = eng_a.submit(list(prompt), max_new_tokens=6) \
                   .result(120.0)["tokens"]
        assert got == want, "post-swap decode must match the donor " \
                            "engine token-for-token"
        # structural drift refused before anything flips
        with pytest.raises(SwapMismatch):
            eng_a.swap_weights({n: v for n, v in list(arrays.items())[1:]})
        assert eng_a.weights_version == 2
    finally:
        eng_a.close()
        eng_b.close()


# ---------------------------------------------------------------------------
# Router canary: NaN burn -> auto-revert; clean soak -> promote
# ---------------------------------------------------------------------------

def _canary_fleet(tmp_path, n=3):
    # the fleet must start bit-identical for the revert/promote
    # checks: swap a common baseline checkpoint onto every engine
    # (fresh-build init is not seed-reproducible across processes
    # either — real fleets converge the same way, by checkpoint)
    base = _ckpt(tmp_path, "ck_base", seed=5)
    engines, servers = [], []
    for _ in range(n):
        eng = ServingEngine(_build_replica_predictor(),
                            workers=1, max_batch=4, max_delay_ms=1.0,
                            deadline_ms=60000.0)
        eng.swap_weights(base)
        engines.append(eng)
        servers.append(serve(eng, port=0))
    return engines, servers


def _pump_until(router, server, deadline_s, stop):
    """Drive traffic through the router + its judge until ``stop()``
    (deterministic: poll_once() runs the canary evaluation inline)."""
    x = _probe().tolist()
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        for _ in range(6):
            try:
                _post(server.url + "/predict", {"inputs": {"x": x}},
                      timeout=10.0)
            except urllib.error.HTTPError:
                pass  # canary-side failures are the evidence
        router.poll_once()
        st = router.canary_status()
        if stop(st):
            return st
        time.sleep(0.05)
    return router.canary_status()


def test_canary_revert_and_promote(tmp_path):
    set_flags({"FLAGS_serving_check_outputs": True})
    engines, servers = _canary_fleet(tmp_path, 3)
    router = Router([s.url for s in servers], autostart=False,
                    poll_interval_ms=100.0, stale_ms=5000.0)
    front = RouterServer(router).start()
    try:
        router.poll_once()
        assert router.healthz()[1]["routable"] == 3

        # --- poisoned canary: burn conviction + fleet-wide revert ---
        ck_bad = _ckpt(tmp_path, "ck_bad", seed=7, poison_nan=True)
        started = router.canary(ck_bad, fraction=0.3, soak_s=30.0)
        assert started["state"] == "soaking"
        assert len(started["urls"]) == 1  # minority: ceil(.3*3)=1
        st = _pump_until(
            router, front, 60.0,
            lambda s: not s["active"]
            and (s["last"] or {}).get("state") in ("reverted",
                                                   "promoted"))
        assert st["last"]["state"] == "reverted", st["last"]
        assert st["last"]["reason"].startswith("burn:")
        assert st["counters"]["canary_reverts"] == 1
        # reverted replicas answer with the ORIGINAL function again
        x = _probe()
        base = engines[-1].submit({"x": x}).result(30.0)[0]
        for eng in engines:
            got = eng.submit({"x": x}).result(30.0)[0]
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(base))

        # --- clean canary: full soak, then fleet-wide promotion ---
        ck_good = _ckpt(tmp_path, "ck_good", seed=2)
        router.canary(ck_good, fraction=0.3, soak_s=1.5)
        st = _pump_until(
            router, front, 60.0,
            lambda s: not s["active"]
            and (s["last"] or {}).get("state") in ("reverted",
                                                   "promoted"))
        assert st["last"]["state"] == "promoted", st["last"]
        assert st["counters"]["canary_promotions"] == 1
        assert st["counters"]["canary_reverts"] == 1  # no false revert
        from paddle_tpu import io
        params = io._read(os.path.join(ck_good, "__params__"))
        want = _mlp_reference(params, x)
        for eng in engines:  # EVERY replica now serves the new version
            got = eng.submit({"x": x}).result(30.0)[0]
            np.testing.assert_array_equal(
                np.asarray(got), want.astype(np.asarray(got).dtype))
            assert eng.weights_version >= 2
    finally:
        set_flags({"FLAGS_serving_check_outputs": False})
        front.close()
        router.close()
        for s in servers:
            s.close()
        for e in engines:
            e.close()


def test_canary_fleet_level_atomicity(tmp_path):
    """A refused canary swap (structural drift) must leave ZERO
    replicas on the new version — already-swapped minority reverted."""
    engines, servers = _canary_fleet(tmp_path, 2)
    router = Router([s.url for s in servers], autostart=False)
    try:
        router.poll_once()
        bad = _ckpt(tmp_path, "ck_wide", seed=3, hidden=32)
        with pytest.raises(RuntimeError, match="refused"):
            router.canary(bad, fraction=0.5, soak_s=5.0)
        assert not router.canary_status()["active"]
        for eng in engines:
            assert eng.weights_version == 2  # baseline swap only
        # and a fleet that cannot split refuses outright
        solo = Router([servers[0].url], autostart=False)
        try:
            solo.poll_once()
            with pytest.raises(RuntimeError, match="split"):
                solo.canary(bad, fraction=0.5, soak_s=5.0)
        finally:
            solo.close()
    finally:
        router.close()
        for s in servers:
            s.close()
        for e in engines:
            e.close()

# ---------------------------------------------------------------------------
# Fleet: one-replica-at-a-time hot swap across real replica processes
# ---------------------------------------------------------------------------

def test_fleet_hot_swap_converges(tmp_path):
    from paddle_tpu.serving.fleet import FleetSupervisor
    argv = ["--feat", "4", "--hidden", "8", "--depth", "1",
            "--classes", "2", "--workers", "1", "--max-batch", "4",
            "--max-delay-ms", "1", "--deadline-ms", "60000"]
    ck = str(tmp_path / "ck_v2")
    build_synthetic_checkpoint(ck, feat=4, hidden=8, depth=1,
                               classes=2, seed=9)
    sup = FleetSupervisor(replicas=2, replica_argv=argv,
                          max_restarts=2, backoff_ms=100.0,
                          workdir=str(tmp_path))
    try:
        urls = sup.wait_ready(timeout_s=240)
        rep = sup.hot_swap(ck)
        assert rep["converged"], rep
        assert [r["weights_version"] for r in rep["replicas"]] == [2, 2]
        assert all(r["swap_status"] == 200 and not r.get("fallback")
                   for r in rep["replicas"])
        # every replica answers under the new version
        from paddle_tpu import io
        params = io._read(os.path.join(ck, "__params__"))
        x = np.linspace(-1.0, 1.0, 4, dtype="float32").reshape(1, 4)
        for url in urls:
            code, doc, hdr = _post(url + "/predict",
                                   {"inputs": {"x": x.tolist()}})
            assert code == 200 and hdr[VERSION_HEADER] == "2"
            assert_logits_match(doc["outputs"][0],
                                _mlp_reference(params, x), url)
    finally:
        sup.close()
