"""High-level API tests: metrics module, 2.0 namespaces, hapi Model.

Reference analogs: tests/unittests/test_metrics.py, test_model.py
(hapi), and the paddle 2.0 namespace surface.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import metric, metrics, nn, optimizer
from paddle_tpu.reader import TensorDataset


# ---------------------------------------------------------------------------
# fluid metrics
# ---------------------------------------------------------------------------
def test_accuracy_metric_weighted_stream():
    m = metrics.Accuracy()
    m.update(0.8, weight=10)
    m.update(0.6, weight=30)
    np.testing.assert_allclose(m.eval(), (8 + 18) / 40)
    m.reset()
    with pytest.raises(ValueError):
        m.eval()


def test_precision_recall():
    preds = np.array([1, 1, 0, 1, 0])
    labels = np.array([1, 0, 0, 1, 1])
    p = metrics.Precision()
    r = metrics.Recall()
    p.update(preds, labels)
    r.update(preds, labels)
    np.testing.assert_allclose(p.eval(), 2 / 3)   # tp=2, fp=1
    np.testing.assert_allclose(r.eval(), 2 / 3)   # tp=2, fn=1


def test_auc_matches_exact():
    rng = np.random.RandomState(0)
    pos = rng.uniform(0.4, 1.0, 200)
    neg = rng.uniform(0.0, 0.6, 200)
    preds = np.concatenate([pos, neg])
    labels = np.concatenate([np.ones(200), np.zeros(200)]).astype("int64")
    m = metrics.Auc()
    m.update(preds, labels)
    # exact AUC by rank statistic
    order = np.argsort(preds)
    ranks = np.empty(len(preds))
    ranks[order] = np.arange(1, len(preds) + 1)
    exact = (ranks[labels == 1].sum() - 200 * 201 / 2) / (200 * 200)
    np.testing.assert_allclose(m.eval(), exact, atol=5e-3)


def test_composite_metric():
    c = metrics.CompositeMetric()
    c.add_metric(metrics.Precision())
    c.add_metric(metrics.Recall())
    c.update(np.array([1, 0]), np.array([1, 1]))
    assert c.eval() == [1.0, 0.5]


# ---------------------------------------------------------------------------
# 2.0 metric namespace
# ---------------------------------------------------------------------------
def test_metric20_topk_accuracy():
    m = metric.Accuracy(topk=(1, 2))
    pred = np.array([[0.1, 0.7, 0.2], [0.6, 0.3, 0.1]])
    label = np.array([[1], [2]])
    correct = m.compute(pred, label)
    m.update(correct)
    acc1, acc2 = m.accumulate()
    np.testing.assert_allclose(acc1, 0.5)   # sample0 top1 correct
    np.testing.assert_allclose(acc2, 0.5)   # label 2 not in top2 of s1


# ---------------------------------------------------------------------------
# nn namespace + hapi Model
# ---------------------------------------------------------------------------
def _toy_data(n=64, d=6, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, d).astype("float32")
    y = (x.sum(1) > d / 2).astype("int64")[:, None]
    return x, y


def test_nn_namespace_builds_and_runs():
    from paddle_tpu import dygraph
    with dygraph.guard():
        net = nn.Sequential(
            nn.Linear(6, 16), nn.ReLU(), nn.Linear(16, 2))
        x = dygraph.to_variable(np.ones((3, 6), "float32"))
        out = net(x)
        assert tuple(out.shape) == (3, 2)
        loss = nn.CrossEntropyLoss()(out, dygraph.to_variable(
            np.zeros((3, 1), "int64")))
        assert np.isfinite(float(np.asarray(loss.numpy()).reshape(-1)[0]))
        mse = nn.MSELoss()(out, dygraph.to_variable(
            np.zeros((3, 2), "float32")))
        l1 = nn.L1Loss()(out, dygraph.to_variable(
            np.zeros((3, 2), "float32")))
        assert float(mse.numpy().reshape(-1)[0]) >= 0
        assert float(l1.numpy().reshape(-1)[0]) >= 0
        y = nn.functional.relu(x)
        assert tuple(y.shape) == (3, 6)


def test_hapi_model_fit_evaluate_predict(tmp_path):
    x, y = _toy_data()
    from paddle_tpu import dygraph
    from paddle_tpu.ops.registry import reset_op_seed
    # the draw of the weights and the shuffle follow process-wide counters:
    # pinned, so that the loss this test holds to a threshold does not
    # depend on which test files the worker ran before this one
    np.random.seed(0)
    reset_op_seed()
    with dygraph.guard():
        net = nn.Sequential(nn.Linear(6, 16), nn.Tanh(),
                            nn.Linear(16, 2))
    model = pt.Model(net)
    model.prepare(optimizer=optimizer.AdamOptimizer(5e-2),
                  loss=nn.CrossEntropyLoss(),
                  metrics=metric.Accuracy())
    ds = TensorDataset(x, y)
    hist = model.fit(ds, batch_size=16, epochs=25, verbose=0)
    assert hist["loss"][-1] < 0.5 * hist["loss"][0]

    res = model.evaluate(ds, batch_size=16, verbose=0)
    assert res["loss"] is not None and res["acc"] > 0.7, res

    preds = model.predict(TensorDataset(x), batch_size=16)
    assert len(preds) == 4 and preds[0].shape == (16, 2)

    # save / load roundtrip preserves the metric
    path = str(tmp_path / "hapi_model")
    model.save(path)
    with dygraph.guard():
        net2 = nn.Sequential(nn.Linear(6, 16), nn.Tanh(),
                             nn.Linear(16, 2))
    model2 = pt.Model(net2)
    model2.prepare(loss=nn.CrossEntropyLoss(),
                   metrics=metric.Accuracy())
    model2.load(path)
    res2 = model2.evaluate(ds, batch_size=16, verbose=0)
    np.testing.assert_allclose(res2["acc"], res["acc"])


def test_static_namespace():
    from paddle_tpu import static
    main, startup = static.Program(), static.Program()
    startup._is_startup = True
    with static.program_guard(main, startup):
        x = static.data("sx", [4], dtype="float32")
        w = static.create_parameter([4, 2], "float32")
        out = pt.layers.matmul(x, w)
    exe = static.Executor()
    exe.run(startup)
    got = exe.run(main, feed={"sx": np.ones((3, 4), "float32")},
                  fetch_list=[out])
    assert np.asarray(got[0]).shape == (3, 2)
    spec = static.InputSpec([None, 4], "float32", "x")
    assert "InputSpec" in repr(spec)


def test_io20_namespace():
    from paddle_tpu import io
    assert io.DataLoader is pt.DataLoader
    ds = io.TensorDataset(np.arange(6).reshape(3, 2))
    assert len(ds) == 3


# ---------------------------------------------------------------------------
# callbacks (VERDICT r3 #9)
# ---------------------------------------------------------------------------

def _cb_model():
    import paddle_tpu as pt
    from paddle_tpu import nn, hapi
    import paddle_tpu.optimizer as opt
    from paddle_tpu.nn import CrossEntropyLoss
    with pt.dygraph.guard():
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    m = hapi.Model(net)
    m.prepare(optimizer=opt.AdamOptimizer(1e-2),
              loss=CrossEntropyLoss())
    return m


def _cb_data(n=32):
    rng = np.random.RandomState(0)
    x = rng.randn(n, 4).astype("float32")
    y = (x.sum(1) > 0).astype("int64")[:, None]
    return [(x[i], y[i]) for i in range(n)]


def test_callbacks_hooks_fire_in_order():
    from paddle_tpu.hapi import Callback

    events = []

    class Recorder(Callback):
        def on_train_begin(self, logs=None):
            events.append("train_begin")

        def on_epoch_begin(self, epoch, logs=None):
            events.append(f"epoch_begin_{epoch}")

        def on_train_batch_end(self, step, logs=None):
            if step == 0:
                events.append(f"batch_end_{step}")
                assert "loss" in (logs or {})

        def on_epoch_end(self, epoch, logs=None):
            events.append(f"epoch_end_{epoch}")

        def on_train_end(self, logs=None):
            events.append("train_end")

    m = _cb_model()
    m.fit(_cb_data(), batch_size=8, epochs=2, verbose=0,
          callbacks=[Recorder()])
    assert events == ["train_begin", "epoch_begin_0", "batch_end_0",
                      "epoch_end_0", "epoch_begin_1", "batch_end_0",
                      "epoch_end_1", "train_end"]


def test_model_checkpoint_callback(tmp_path):
    from paddle_tpu.hapi import ModelCheckpoint

    m = _cb_model()
    save_dir = str(tmp_path / "ckpt")
    m.fit(_cb_data(), batch_size=8, epochs=2, verbose=0,
          callbacks=[ModelCheckpoint(save_freq=1, save_dir=save_dir)])
    import os
    assert os.path.exists(os.path.join(save_dir, "0.pdparams"))
    assert os.path.exists(os.path.join(save_dir, "1.pdparams"))
    assert os.path.exists(os.path.join(save_dir, "final.pdparams"))
    # weights reload into a fresh model
    m2 = _cb_model()
    m2.load(os.path.join(save_dir, "final"))


def test_early_stopping_callback():
    from paddle_tpu.hapi import EarlyStopping

    m = _cb_model()
    # patience 0 + impossible baseline: stops after the first epoch
    es = EarlyStopping(monitor="loss", mode="min", patience=0,
                       baseline=-1e9, verbose=0)
    m.fit(_cb_data(), batch_size=8, epochs=50, verbose=0,
          callbacks=[es])
    assert m.stop_training


# ---------------------------------------------------------------------------
# paddle.tensor / paddle.amp namespaces (VERDICT r3 #9)
# ---------------------------------------------------------------------------

def test_tensor_namespace_smoke():
    import paddle_tpu as pt
    import paddle_tpu.tensor as T

    main_p, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main_p, startup):
        x = pt.layers.data("x", [3, 4], append_batch_size=False)
        y = T.add(T.multiply(x, x), T.ones_like(x))
        s = T.sum(y, dim=1)
        mx = T.argmax(y, axis=1)
        lse = T.logsumexp(x, axis=1)
        tri = T.tril(x)
        top_v, top_i = T.topk(x, k=2)
    exe = pt.Executor()
    exe.run(startup)
    xv = np.arange(12, dtype="float32").reshape(3, 4)
    sv, mv, lv, tv, tvv = exe.run(
        main_p, feed={"x": xv}, fetch_list=[s, mx, lse, tri, top_v])
    np.testing.assert_allclose(np.asarray(sv), (xv * xv + 1).sum(1))
    np.testing.assert_allclose(np.asarray(mv), np.argmax(xv * xv + 1, 1))
    np.testing.assert_allclose(
        np.asarray(lv),
        np.log(np.exp(xv - xv.max(1, keepdims=True)).sum(1))
        + xv.max(1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(tv), np.tril(xv))
    np.testing.assert_allclose(np.asarray(tvv), np.sort(xv, 1)[:, -2:][:, ::-1])


def test_amp_namespace_smoke():
    import paddle_tpu as pt
    from paddle_tpu import amp

    with pt.dygraph.guard():
        import paddle_tpu.dygraph as dg
        lin = pt.nn.Linear(4, 4)
        x = dg.to_variable(np.ones((2, 4), "float32"))
        with amp.auto_cast():
            y = lin(x)
        scaler = amp.GradScaler(init_loss_scaling=128.0)
        loss = pt.layers.reduce_mean(y)
        scaled = scaler.scale(loss)
        assert scaled is not None
    assert callable(amp.decorate)


def test_hapi_model_full_train_state_resume(tmp_path):
    """save/load now carries optimizer accumulators (.pdopt): resuming
    from a checkpoint continues the EXACT Adam trajectory (reference
    Model.save training=True contract)."""
    x, y = _toy_data()
    ds = TensorDataset(x, y)

    def build(seed_net=None):
        from paddle_tpu import dygraph
        with dygraph.guard():
            net = nn.Sequential(nn.Linear(6, 16), nn.Tanh(),
                                nn.Linear(16, 2))
        m = pt.Model(net)
        m.prepare(optimizer.AdamOptimizer(
            5e-2, parameter_list=net.parameters()),
            loss=nn.CrossEntropyLoss())
        return m

    model = build()
    model.fit(ds, batch_size=16, epochs=5, verbose=0)
    path = str(tmp_path / "resume_ck")
    model.save(path)
    import os
    assert os.path.exists(path + ".pdopt")  # optimizer state on disk
    direct = model.fit(ds, batch_size=16, epochs=3, shuffle=False,
                       verbose=0)["loss"]

    resumed = build()
    resumed.load(path)
    replay = resumed.fit(ds, batch_size=16, epochs=3, shuffle=False,
                         verbose=0)["loss"]
    np.testing.assert_allclose(replay, direct, rtol=1e-5, atol=1e-6)


def test_hapi_model_inference_export(tmp_path):
    """save(training=False) exports via jit.save using specs inferred
    from the first fit batch; Predictor + jit.load serve it."""
    x, y = _toy_data()
    from paddle_tpu import dygraph
    with dygraph.guard():
        net = nn.Sequential(nn.Linear(6, 16), nn.Tanh(),
                            nn.Linear(16, 2))
    model = pt.Model(net)
    model.prepare(optimizer.AdamOptimizer(
        5e-2, parameter_list=net.parameters()),
        loss=nn.CrossEntropyLoss())
    model.fit(TensorDataset(x, y), batch_size=16, epochs=2, verbose=0)
    assert model._inputs is not None  # specs inferred from fit
    with dygraph.guard():
        want = np.asarray(net(dygraph.to_variable(x[:16])).numpy())
    d = str(tmp_path / "hapi_infer")
    model.save(d, training=False)
    with dygraph.guard():
        got = pt.jit.load(d)(x[:16])
        np.testing.assert_allclose(np.asarray(got.numpy()), want,
                                   rtol=1e-5, atol=1e-6)


def test_hapi_distributed_fit_with_resume(tmp_path):
    """Book MLP under real 2-process DP (launch + DataParallel grad
    allreduce) with a checkpoint resume mid-run (VERDICT r4 #10)."""
    import json
    import os
    import subprocess
    import sys

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(os.path.dirname(__file__),
                          "hapi_dist_worker.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "2", "--coordinator_port", "23873",
           script, str(tmp_path)]
    r = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=280)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    res = {}
    for rank in (0, 1):
        p = tmp_path / f"hapi_result.{rank}.json"
        assert p.exists(), (r.stdout[-2000:], r.stderr[-2000:])
        res[rank] = json.loads(p.read_text())
    # training converged under DP
    for rank in (0, 1):
        assert res[rank]["last_loss"] < res[rank]["first_loss"] * 0.5
    # grad allreduce kept both ranks' parameters identical
    np.testing.assert_allclose(res[0]["param_sum"], res[1]["param_sum"],
                               rtol=1e-5)
    np.testing.assert_allclose(res[0]["param_absmax"],
                               res[1]["param_absmax"], rtol=1e-5)
    # checkpoint resume replays the direct trajectory on every rank
    for rank in (0, 1):
        np.testing.assert_allclose(res[rank]["resume_losses"],
                                   res[rank]["direct_losses"],
                                   rtol=1e-4, atol=1e-5)


def test_hapi_inference_export_is_deterministic_with_dropout(tmp_path):
    """save(training=False) must trace in eval mode: a net with dropout
    exported right after fit() (which leaves the net in train mode)
    has to serve deterministic outputs."""
    x, y = _toy_data()
    from paddle_tpu import dygraph
    with dygraph.guard():
        net = nn.Sequential(nn.Linear(6, 16), nn.Dropout(0.5),
                            nn.Linear(16, 2))
    model = pt.Model(net)
    model.prepare(optimizer.AdamOptimizer(
        5e-2, parameter_list=net.parameters()),
        loss=nn.CrossEntropyLoss())
    model.fit(TensorDataset(x, y), batch_size=16, epochs=1, verbose=0)
    d = str(tmp_path / "dropout_infer")
    model.save(d, training=False)
    assert getattr(net, "training", False)  # fit's train mode restored
    with dygraph.guard():
        loaded = pt.jit.load(d)
        o1 = np.asarray(loaded(x[:8]).numpy())
        o2 = np.asarray(loaded(x[:8]).numpy())
    np.testing.assert_array_equal(o1, o2)  # no live dropout
    with dygraph.guard():
        net.eval()
        want = np.asarray(net(dygraph.to_variable(x[:8])).numpy())
    np.testing.assert_allclose(o1, want, rtol=1e-5, atol=1e-6)
