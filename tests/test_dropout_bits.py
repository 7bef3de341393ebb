"""The ``dropout`` op draws its mask bits from XLA's RngBitGenerator
(``ops/nn_ops.py`` ``_draw_mask_bits``), keyed by the key ``ctx.rng(op)``
hands it: the distribution, the replay in the backward, the independence
of sites and steps, the indifference to the process's default PRNG, what
the lowered step holds, and the per-shard draw under a ``dp`` mesh."""
import re
import types

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.monitor import stat_get

SHAPE = [8, 128, 768]
COUNTERS = ("dropout_lowered_hw_bits", "dropout_lowered_threefry")


def _counts():
    return {c: stat_get(c) for c in COUNTERS}


def _moved(before):
    return tuple(stat_get(c) - before[c] for c in COUNTERS)


def _dropout_program(shape, probs, upscale=True):
    """One dropout site per entry of ``probs`` over the same input of
    ones, so an output is its site's keep mask (times the scale)."""
    x = fluid.data(name="x", shape=shape, append_batch_size=False,
                   stop_gradient=False)
    impl = "upscale_in_train" if upscale else "downgrade_in_infer"
    return x, [layers.dropout(x, dropout_prob=p,
                              dropout_implementation=impl) for p in probs]


def _masks(outs, steps=1, shape=SHAPE):
    """Keep masks of ``outs`` over ``steps`` runs of a fresh Executor."""
    exe = fluid.Executor()
    ones = np.ones(shape, "float32")
    return [[np.asarray(o) != 0 for o in
             exe.run(feed={"x": ones}, fetch_list=list(outs))]
            for _ in range(steps)]


@pytest.mark.parametrize("p", [0.1, 0.3, 0.5])
def test_keep_rate_is_the_uint8_threshold(p):
    _, (out,) = _dropout_program(SHAPE, [p])
    before = _counts()
    (keep,), = _masks([out])
    assert _moved(before) == (1, 0)
    q = 1.0 - round(p * 256) / 256.0
    sigma = (q * (1.0 - q) / keep.size) ** 0.5
    assert abs(keep.mean() - q) < 3 * sigma, (keep.mean(), q, sigma)
    # upscale_in_train: what is kept is scaled by 1 / (1 - p)
    exe = fluid.Executor()
    ov, = exe.run(feed={"x": np.ones(SHAPE, "float32")}, fetch_list=[out])
    np.testing.assert_allclose(np.unique(ov), [0.0, 1.0 / (1.0 - p)],
                               rtol=1e-6)


def test_gradient_is_nonzero_exactly_where_the_output_is():
    """The backward regenerates the forward's mask from the key."""
    from paddle_tpu.framework.backward import gradients

    x, (out,) = _dropout_program(SHAPE, [0.3])
    (gx,) = gradients(layers.reduce_sum(out), x)
    ov, gv = fluid.Executor().run(
        feed={"x": np.ones(SHAPE, "float32")}, fetch_list=[out, gx])
    assert 0.6 < (ov != 0).mean() < 0.8
    np.testing.assert_array_equal(gv != 0, ov != 0)
    np.testing.assert_allclose(gv, ov, rtol=1e-6)


def test_sites_and_steps_draw_apart_and_a_seed_and_step_repeat():
    _, outs = _dropout_program(SHAPE, [0.5, 0.5])
    (a1, b1), (a2, b2) = _masks(outs, steps=2)
    # independent draws agree on about half their elements
    for one, other in ((a1, b1), (a1, a2), (b1, b2)):
        assert 0.45 < (one == other).mean() < 0.55
    (c1, d1), = _masks(outs)
    np.testing.assert_array_equal(a1, c1)
    np.testing.assert_array_equal(b1, d1)


@pytest.mark.parametrize("impl", ["rbg", "threefry2x32"])
def test_masks_do_not_depend_on_the_process_default_prng(impl):
    _, outs = _dropout_program([8, 64, 128], [0.1, 0.5])
    want = _masks(outs, steps=2, shape=[8, 64, 128])
    old = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", impl)
    try:
        got = _masks(outs, steps=2, shape=[8, 64, 128])
    finally:
        jax.config.update("jax_default_prng_impl", old)
    for w_step, g_step in zip(want, got):
        for w, g in zip(w_step, g_step):
            np.testing.assert_array_equal(w, g)


def test_mask_output_is_the_mask_of_out():
    """The ``Mask``-output branch draws through the same function."""
    from paddle_tpu.framework.layer_helper import LayerHelper

    x = fluid.data(name="x", shape=[8, 64, 128], append_batch_size=False)
    helper = LayerHelper("dropout")
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference("uint8")
    helper.append_op("dropout", inputs={"X": [x]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"dropout_prob": 0.25, "is_test": False,
                            "dropout_implementation": "upscale_in_train"})
    before = _counts()
    ov, mv = fluid.Executor().run(
        feed={"x": np.ones([8, 64, 128], "float32")}, fetch_list=[out, mask])
    assert _moved(before) == (1, 0)
    assert mv.dtype == np.uint8 and set(np.unique(mv)) == {0, 1}
    np.testing.assert_array_equal(mv == 1, ov != 0)
    assert abs(mv.mean() - 0.75) < 0.01


def test_backend_without_a_bit_generator_falls_back_to_threefry(
        monkeypatch, caplog):
    from paddle_tpu.ops import nn_ops

    monkeypatch.setattr(jax, "default_backend", lambda: "abacus")
    monkeypatch.setattr(nn_ops, "_dropout_logged", set())
    _, (out,) = _dropout_program([8, 64, 128], [0.5])
    before = _counts()
    with caplog.at_level("WARNING", logger="paddle_tpu.ops.nn"):
        (keep,), (again,) = _masks([out], steps=2, shape=[8, 64, 128])
    assert _moved(before) == (0, 1)
    assert 0.49 < keep.mean() < 0.51 and (keep != again).any()
    said = [r for r in caplog.records if "threefry" in r.getMessage()]
    assert len(said) == 1 and "abacus" in said[0].getMessage()


@pytest.mark.parametrize("mesh_shape,axis_names,shape,want", [
    ({}, (), (8, 4), None),                          # no mesh: one draw
    ({"dp": 1}, (), (8, 4), None),                   # one device
    ({"dp": 4}, ("dp",), (2, 4), None),              # manual: local already
    ({"dp": 4}, (), (8, 4), (("dp",), None)),        # GSPMD: per dp shard
    ({"dp": 2, "mp": 2}, (), (8, 4), (("dp",), "mp")),
    ({"dp": 4}, (), (6, 4), None),                   # does not divide
    ({"dp": 2, "zero": 2}, (), (8, 4), None),        # an axis unknown
    ({"dp": 4}, (), (), None),                       # a scalar
])
def test_mask_route_follows_the_mesh(mesh_shape, axis_names, shape, want):
    from paddle_tpu.ops.nn_ops import _mask_route

    mesh = types.SimpleNamespace(shape=mesh_shape) if mesh_shape else None
    ctx = types.SimpleNamespace(mesh=mesh, axis_names=axis_names)
    assert _mask_route(ctx, shape) == (mesh, want)


# ---------------------------------------------------------------------------
# the BERT train step
# ---------------------------------------------------------------------------

LAYERS, BATCH, SEQ, HIDDEN, PRED = 2, 8, 64, 128, 10
SITES = 1 + 2 * LAYERS          # the embeddings, then two a layer


def _bert_step(n_devices):
    """(lowered step, its arguments' scope values) of a 2-layer BERT
    through ``build_sharded_step`` over ``n_devices`` host devices."""
    from paddle_tpu.models.bert import build_bert_train_programs
    from paddle_tpu.parallel import build_sharded_step, dp_mesh

    main_p, startup, feed_names, loss, _ = build_bert_train_programs(
        dict(batch_size=BATCH, seq_len=SEQ, vocab_size=211, hidden=HIDDEN,
             num_layers=LAYERS, num_heads=2, intermediate=256,
             max_predictions=PRED, use_flash=True, dropout=0.1))
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    mesh = dp_mesh(n_devices, devices=jax.devices()[:n_devices])
    fn, mut_in, const_in, _ = build_sharded_step(
        main_p, feed_names, [loss.name], mesh)
    rng = np.random.RandomState(7)
    feed = {
        "input_ids": rng.randint(0, 211, (BATCH, SEQ)).astype("int32"),
        "token_type_ids": np.zeros((BATCH, SEQ), "int32"),
        "attn_mask": np.ones((BATCH, SEQ), "float32"),
        "mlm_positions": np.sort(np.stack(
            [rng.choice(SEQ, PRED, replace=False) for _ in range(BATCH)]),
            axis=1).astype("int32"),
        "mlm_labels": rng.randint(0, 211, (BATCH, PRED)).astype("int32"),
        "mlm_weights": np.ones((BATCH, PRED), "float32"),
    }
    args = (tuple(feed[n] for n in feed_names),
            tuple(scope.find_var(n) for n in mut_in),
            tuple(scope.find_var(n) for n in const_in), np.int32(1))
    return fn, args


def _threefry_operand_sizes(text):
    """Element counts of every tensor a threefry call of the lowered
    module takes (key folds take a word or two; a mask-shaped draw took
    thousands)."""
    sizes = []
    for line in text.splitlines():
        if "threefry" in line and "call" in line:
            for dims in re.findall(r"tensor<((?:\d+x)*)ui32>", line):
                sizes.append(int(np.prod([int(d) for d in
                                          dims.split("x") if d] or [1])))
    return sizes


@pytest.mark.parametrize("n_devices", [1, 4])
def test_bert_step_draws_once_a_site_and_no_mask_shaped_threefry(n_devices):
    before = _counts()
    fn, args = _bert_step(n_devices)
    text = fn.lower(*args).as_text()
    assert _moved(before) == (SITES, 0)
    draws = re.findall(
        r"stablehlo\.rng_bit_generator.*-> \(tensor<2xui64>, "
        r"tensor<([0-9x]+)xui8>\)", text)
    # under the mesh a draw has its shard's shape, inside shard_map
    shard = f"{BATCH // n_devices}x{SEQ}x{HIDDEN}"
    assert draws and set(draws) == {shard}, draws
    if n_devices == 1:
        # a site's forward draw and its regeneration in the backward
        assert len(draws) == 2 * SITES
    else:
        # one jitted shard_map callable serves every site of a shape
        assert len(draws) <= 2 and 'manual_axes={"dp"}' in text
    sizes = _threefry_operand_sizes(text)
    assert sizes and max(sizes) <= 4, max(sizes)
    fetches, _, _ = fn(*args)
    assert np.isfinite(np.asarray(fetches[0])).all()


def test_shards_of_a_dp_mesh_draw_different_masks():
    """``chip_smoke.py``'s check of the per-shard draw, on four host
    devices: the shards' masks differ pairwise, a step repeats, the next
    does not."""
    import chip_smoke

    before = _counts()
    agree = chip_smoke.check_dropout_shards(jax.devices()[:4], 64, 128)
    assert _moved(before) == (1, 0)
    assert len(agree) == 6
