"""Flash / ring / Ulysses attention tests (new TPU capability;
reference had no fused-training attention or sequence parallelism)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.ops.pallas import blockwise_attention, flash_attention
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.mesh import shard_map_compat
from paddle_tpu.parallel.ring import ring_attention, ulysses_attention

B, H, S, D = 2, 4, 128, 32


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, H, S, D).astype("float32")),
            jnp.asarray(rng.randn(B, H, S, D).astype("float32")),
            jnp.asarray(rng.randn(B, H, S, D).astype("float32")))


def _naive(q, k, v, causal=False):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_naive(causal):
    q, k, v = _qkv()
    out, _ = blockwise_attention(q, k, v, causal=causal, block_k=32)
    np.testing.assert_allclose(out, _naive(q, k, v, causal), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_kernel_matches_naive(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, None, 64, 32, True)  # interpret
    np.testing.assert_allclose(out, _naive(q, k, v, causal), atol=2e-5)


def test_flash_gradients_match_naive():
    q, k, v = _qkv()
    g1 = jax.grad(lambda q: (flash_attention(
        q, k, v, True, None, 64, 64, True) ** 2).sum())(q)
    g2 = jax.grad(lambda q: (_naive(q, k, v, True) ** 2).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    """Sequence sharded over sp=8: ring result == full attention."""
    from jax.sharding import PartitionSpec as P
    q, k, v = _qkv()
    mesh = make_mesh({"sp": 8})

    ring = jax.jit(shard_map_compat(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
        mesh, in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp")))
    out = ring(q, k, v)
    np.testing.assert_allclose(out, _naive(q, k, v, causal), atol=2e-5)


def test_ring_attention_gradients():
    from jax.sharding import PartitionSpec as P
    q, k, v = _qkv()
    mesh = make_mesh({"sp": 8})

    def ring_loss(q, k, v):
        f = shard_map_compat(
            lambda a, b, c: ring_attention(a, b, c, "sp", causal=True),
            mesh, in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"))
        return (f(q, k, v) ** 2).sum()

    g1 = jax.jit(jax.grad(ring_loss))(q, k, v)
    g2 = jax.grad(lambda q: (_naive(q, k, v, True) ** 2).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    from jax.sharding import PartitionSpec as P
    q, k, v = _qkv()
    mesh = make_mesh({"sp": 4})  # H=4 heads divisible by 4

    uly = jax.jit(shard_map_compat(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=causal),
        mesh, in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp")))
    out = uly(q, k, v)
    np.testing.assert_allclose(out, _naive(q, k, v, causal), atol=2e-5)


def test_flash_attention_op_and_layer():
    """Static-graph flash_attention op: forward + grads flow."""
    rng = np.random.RandomState(0)
    main, startup = pt.default_main_program(), pt.default_startup_program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [B, H, S, D], append_batch_size=False)
        q = layers.fc(x, D, num_flatten_dims=3)
        out = layers.flash_attention(q, x, x, causal=True)
        loss = layers.mean(out)
        from paddle_tpu import optimizer
        optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    xv = rng.randn(B, H, S, D).astype("float32")
    l0 = float(exe.run(main, feed={"x": xv}, fetch_list=[loss])[0])
    for _ in range(3):
        l1 = float(exe.run(main, feed={"x": xv}, fetch_list=[loss])[0])
    assert np.isfinite(l1) and l1 != l0


def _naive_bias(q, k, v, bias_rows):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    s = s + bias_rows[:, None, None, :]
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _pad_bias(seed=3):
    rng = np.random.RandomState(seed)
    mask = (rng.rand(B, S) < 0.8).astype("float32")
    mask[:, :4] = 1.0  # at least a few attended positions
    return jnp.asarray((mask - 1.0) * 10000.0)


def test_blockwise_bias_matches_naive():
    q, k, v = _qkv()
    bias = _pad_bias()
    out, _ = blockwise_attention(q, k, v, block_k=32, bias=bias)
    np.testing.assert_allclose(out, _naive_bias(q, k, v, bias), atol=2e-5)


def test_pallas_bias_kernel_matches_naive():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bias
    q, k, v = _qkv()
    bias = _pad_bias()
    out = flash_attention_bias(q, k, v, bias, False, None, 64, 32, True)
    np.testing.assert_allclose(out, _naive_bias(q, k, v, bias), atol=2e-5)


def test_flash_bias_gradients_match_naive():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bias
    q, k, v = _qkv()
    bias = _pad_bias()
    g1 = jax.grad(lambda q: (flash_attention_bias(
        q, k, v, bias, False, None, 64, 64, True) ** 2).sum())(q)
    g2 = jax.grad(lambda q: (_naive_bias(q, k, v, bias) ** 2).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=1e-3)


def test_bert_flash_matches_unfused():
    """BERT encoder loss parity: flash path vs unfused reference math
    (dropout off so the graphs are numerically comparable)."""
    from paddle_tpu.models import build_bert_pretrain

    losses = []
    ref_params = None
    for use_flash in (False, True):
        main, startup = pt.Program(), pt.Program()
        startup._is_startup = True
        with pt.program_guard(main, startup):
            feeds, outs = build_bert_pretrain(
                batch_size=2, seq_len=32, vocab_size=128, hidden=32,
                num_layers=2, num_heads=2, intermediate=64, dropout=0.0,
                use_flash=use_flash)
        scope = pt.Scope()
        exe = pt.Executor()
        main.random_seed = startup.random_seed = 7
        exe.run(startup, scope=scope)
        # same weights for both graphs: params are created in the same
        # order, so copy run-1's initialized values positionally
        pnames = [p.name for p in main.global_block().all_parameters()]
        if ref_params is None:
            ref_params = [np.asarray(scope.find_var(n)) for n in pnames]
        else:
            assert len(pnames) == len(ref_params)
            for n, val in zip(pnames, ref_params):
                assert np.asarray(scope.find_var(n)).shape == val.shape
                scope.set_var(n, val)
        rng = np.random.RandomState(0)
        feed = {
            "input_ids": rng.randint(0, 128, (2, 32)).astype("int64"),
            "token_type_ids": np.zeros((2, 32), "int64"),
            "attn_mask": (rng.rand(2, 32) < 0.9).astype("float32"),
            "mlm_mask": (rng.rand(2, 32) < 0.15).astype("float32"),
            "mlm_labels": rng.randint(0, 128, (2, 32)).astype("int64"),
        }
        loss, = exe.run(main, feed=feed, fetch_list=[outs["loss"]],
                        scope=scope)
        losses.append(float(np.asarray(loss)))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)


def test_einsum_impl_matches_unfused_both_layouts():
    """impl='xla' einsum attention == the reference matmul chain, in both
    bhsd and the transpose-free bshd layout, incl. bias and causal."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    B, H, S, D = 2, 3, 16, 8
    q = rng.randn(B, H, S, D).astype("float32")
    k = rng.randn(B, H, S, D).astype("float32")
    v = rng.randn(B, H, S, D).astype("float32")
    bias = np.where(rng.rand(B, S) < 0.2, -1e4, 0.0).astype("float32")

    def ref(q, k, v, bias, causal):
        s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = s + bias[:, None, None, :]
        if causal:
            s = np.where(np.tril(np.ones((S, S), bool))[None, None],
                         s, -1e30)
        e = np.exp(s - s.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        return np.einsum("bhqk,bhkd->bhqd", p, v)

    import paddle_tpu as pt
    from paddle_tpu import layers

    for causal in (False, True):
        for layout in ("bhsd", "bshd"):
            main, startup = pt.Program(), pt.Program()
            startup._is_startup = True
            with pt.program_guard(main, startup):
                shp = [B, H, S, D] if layout == "bhsd" else [B, S, H, D]
                qv = layers.data("q", shp, append_batch_size=False)
                kv = layers.data("k", shp, append_batch_size=False)
                vv = layers.data("v", shp, append_batch_size=False)
                bv = layers.data("bias", [B, S], append_batch_size=False)
                out = layers.flash_attention(qv, kv, vv, bias=bv,
                                             causal=causal, impl="xla",
                                             layout=layout, is_test=True)
            exe = pt.Executor()
            exe.run(startup)
            feed_q = q if layout == "bhsd" else q.transpose(0, 2, 1, 3)
            feed_k = k if layout == "bhsd" else k.transpose(0, 2, 1, 3)
            feed_v = v if layout == "bhsd" else v.transpose(0, 2, 1, 3)
            got, = exe.run(main, feed={"q": feed_q, "k": feed_k,
                                       "v": feed_v, "bias": bias},
                           fetch_list=[out])
            got = np.asarray(got)
            if layout == "bshd":
                got = got.transpose(0, 2, 1, 3)
            np.testing.assert_allclose(got, ref(q, k, v, bias, causal),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"{layout} causal={causal}")


def test_einsum_impl_dropout_statistics():
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers

    B, H, S, D = 2, 2, 32, 8
    qv = layers.data("q", [B, H, S, D], append_batch_size=False)
    out = layers.flash_attention(qv, qv, qv, impl="xla",
                                 dropout_prob=0.5)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    x = np.random.RandomState(1).rand(B, H, S, D).astype("float32")
    o1, = exe.run(feed={"q": x}, fetch_list=[out])
    o2, = exe.run(feed={"q": x}, fetch_list=[out])
    # dropout active: stochastic across steps, but finite and same shape
    assert not np.allclose(np.asarray(o1), np.asarray(o2))
    assert np.isfinite(np.asarray(o1)).all()


# ---------------------------------------------------------------------------
# packed-QKV kernels (transpose-free [B, S, 3H] path)
# ---------------------------------------------------------------------------

PB, PS, PH, PNH = 2, 128, 256, 4  # head_dim 64, two heads per lane chunk


def _packed_ref(qkv, bias=None, causal=False, nh=PNH):
    b, s, three_h = qkv.shape
    h = three_h // 3
    d = h // nh
    x = qkv.reshape(b, s, 3, nh, d)
    q, k, v = x[:, :, 0], x[:, :, 1], x[:, :, 2]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if bias is not None:
        sc = sc + bias[:, None, None, :]
    if causal:
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None],
                       sc, -1e30)
    p = jax.nn.softmax(sc, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, h)


@pytest.mark.parametrize("causal", [False, True])
def test_packed_flash_matches_naive(causal):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_packed

    rng = np.random.RandomState(0)
    qkv = jnp.asarray(rng.randn(PB, PS, 3 * PH).astype("float32"))
    out = flash_attention_packed(qkv, PNH, causal, None, 64, 32, True)
    ref = _packed_ref(qkv, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_packed_flash_grads_match_naive(causal):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_packed

    rng = np.random.RandomState(1)
    qkv = jnp.asarray(rng.randn(PB, PS, 3 * PH).astype("float32"))
    g1 = jax.grad(lambda x: (flash_attention_packed(
        x, PNH, causal, None, 64, 32, True) ** 2).sum())(qkv)
    g2 = jax.grad(lambda x: (_packed_ref(x, causal=causal) ** 2).sum())(qkv)
    scale = float(jnp.abs(g2).max())
    np.testing.assert_allclose(np.asarray(g1) / scale,
                               np.asarray(g2) / scale, atol=2e-2)


def test_packed_flash_bias_and_grads():
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention_packed_bias)

    rng = np.random.RandomState(2)
    qkv = jnp.asarray(rng.randn(PB, PS, 3 * PH).astype("float32"))
    bias = jnp.asarray(
        np.where(rng.rand(PB, PS) > 0.2, 0.0, -1e4).astype("float32"))
    out = flash_attention_packed_bias(qkv, bias, PNH, False, None, 64, 32,
                                      True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_packed_ref(qkv, bias)),
                               atol=2e-2, rtol=2e-2)
    g1 = jax.grad(lambda x, b: (flash_attention_packed_bias(
        x, b, PNH, False, None, 64, 32, True) ** 2).sum(), (0, 1))(qkv, bias)
    g2 = jax.grad(lambda x, b: (_packed_ref(x, b) ** 2).sum(), (0, 1))(
        qkv, bias)
    for a, b_ in zip(g1, g2):
        scale = float(jnp.abs(b_).max())
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b_) / scale, atol=2e-2)


def test_packed_flash_head_dim_128():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_packed

    rng = np.random.RandomState(3)
    qkv = jnp.asarray(rng.randn(PB, PS, 3 * 256).astype("float32"))
    out = flash_attention_packed(qkv, 2, False, None, 64, 32, True)
    ref = _packed_ref(qkv, nh=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_flash_attention_qkv_op_and_layer():
    """Static-graph flash_attention_qkv op: forward + grads flow, and the
    fallback (CPU/mesh) path matches the packed-kernel math."""
    main_p, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main_p, startup):
        x = layers.data("x", [PB, PS, 3 * PH], append_batch_size=False)
        x.stop_gradient = False
        bias = layers.data("bias", [PB, PS], append_batch_size=False)
        out = layers.flash_attention_qkv(x, PNH, bias=bias)
        loss = layers.reduce_mean(out)
        pt.append_backward(loss)
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.RandomState(4)
    xv = rng.randn(PB, PS, 3 * PH).astype("float32")
    bv = np.where(rng.rand(PB, PS) > 0.2, 0.0, -1e4).astype("float32")
    outs = exe.run(main_p, feed={"x": xv, "bias": bv},
                   fetch_list=[out.name, "x@GRAD"])
    ref = _packed_ref(jnp.asarray(xv), jnp.asarray(bv))
    np.testing.assert_allclose(outs[0], np.asarray(ref), atol=2e-2,
                               rtol=2e-2)
    assert np.abs(outs[1]).max() > 0


# ---------------------------------------------------------------------------
# the Pallas kernels under a mesh (ops/attention_ops.py kernel_partition)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape,manual,batch,heads,want,says", [
    # no mesh, one device, a mesh of size-one axes: the kernel as it is
    ({}, (), 64, None, ("direct", None), None),
    ({"dp": 1}, (), 64, (12, 12, 12), ("direct", None), None),
    # the dp4 cell: packed form, batch 160 over dp
    ({"dp": 4}, (), 160, None, ("shard_map", (("dp",), None)), None),
    ({"dp": 4}, (), 160, (12, 12, 12), ("shard_map", (("dp",), None)), None),
    # dp x mp: the split form splits heads too, the packed form cannot
    ({"dp": 2, "mp": 2}, (), 8, (12, 12, 12),
     ("shard_map", (("dp",), "mp")), None),
    ({"mp": 4}, (), 1, (32, 32, 32), ("shard_map", ((), "mp")), None),
    ({"dp": 2, "mp": 2}, (), 8, None, "reference", "mp=2"),
    ({"dp": 2, "mp": 1}, (), 8, None, ("shard_map", (("dp",), None)), None),
    # batch not divisible: never replicated over dp
    ({"dp": 4}, (), 6, None, "reference", "batch 6 does not divide"),
    ({"dp": 4, "mp": 2}, (), 2, (8, 8, 8), "reference", "batch 2"),
    # GQA head counts: every operand's heads must divide
    ({"mp": 4}, (), 1, (32, 8, 8), ("shard_map", ((), "mp")), None),
    ({"mp": 4}, (), 1, (28, 2, 2), "reference", "(28, 2, 2)"),
    ({"dp": 2, "mp": 8}, (), 4, (32, 4, 4), "reference", "mp=8"),
    # an axis the rule does not know
    ({"dp": 2, "ep": 2}, (), 8, None, "reference", "axis ep"),
    ({"dp": 2, "zero": 4}, (), 8, (4, 4, 4), "reference", "axis zero"),
    ({"dp": 2, "sp": 2}, (), 8, (4, 4, 4), "reference", "axis sp"),
    # a manual context (parallel/spmd.py): operands are local already
    ({"dp": 8}, ("dp",), 3, None, ("direct", None), None),
    ({"dp": 2, "mp": 2}, ("dp", "mp"), 3, (5, 5, 5), ("direct", None), None),
    ({"dp": 2, "sp": 4}, ("dp", "sp"), 3, (5, 5, 5), ("direct", None), None),
])
def test_kernel_partition_rule(mesh_shape, manual, batch, heads, want, says):
    """The partition as a pure function of the mesh's axes and the
    operands' shapes.  (With ``sp`` bound the ops take their ring / Ulysses
    branch before they ask.)"""
    from paddle_tpu.ops.attention_ops import kernel_partition

    got = kernel_partition(mesh_shape, manual, batch, heads)
    if want == "reference":
        assert got[0] == "reference" and says in got[1], got
    else:
        assert got == want


def test_kernel_route_off_a_tpu_is_the_reference_without_a_reason():
    from types import SimpleNamespace

    from paddle_tpu.ops.attention_ops import kernel_route

    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    assert kernel_route(SimpleNamespace(mesh=mesh), 8, None) == (
        "reference", None)
    assert kernel_route(SimpleNamespace(mesh=None), 8, None) == (
        "reference", None)


def _sharded(mesh_axes, batch, heads, kernel, operands, layouts, out_layout):
    """``kernel`` through the route ``kernel_partition`` gives under a mesh
    of the forced host devices, jitted as the GSPMD builders jit it."""
    from paddle_tpu.ops.attention_ops import call_kernel, kernel_partition

    n = int(np.prod(list(mesh_axes.values())))
    mesh = make_mesh(mesh_axes, devices=jax.devices()[:n])
    route, how = kernel_partition(dict(mesh.shape), (), batch, heads)
    assert route == "shard_map", (route, how)
    return jax.jit(lambda *xs: call_kernel(mesh, how, kernel, xs, layouts,
                                           out_layout))(*operands)


def _close(got, want, atol):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = max(float(jnp.abs(w).max()), 1e-6)
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=atol)


@pytest.mark.parametrize("mesh_axes", [{"dp": 4}, {"dp": 2, "mp": 2}],
                         ids=["dp4", "dp2xmp2"])
@pytest.mark.parametrize("causal,with_bias", [(False, False), (True, False),
                                              (False, True)],
                         ids=["plain", "causal", "bias"])
def test_sharded_split_kernels_match_blockwise(mesh_axes, causal, with_bias):
    """The split-form kernels (interpret mode) per shard of a ``dp`` and a
    ``dp x mp`` mesh against ``blockwise_attention`` on whole operands:
    the output and the gradients of q, k, v."""
    from paddle_tpu.ops.attention_ops import _BHSD, _BS
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bias

    rng = np.random.RandomState(5)
    b, h, s, d = 4, 4, 64, 32
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d).astype("float32"))
               for _ in range(3))
    bias = jnp.asarray(
        np.where(rng.rand(b, s) > 0.2, 0.0, -1e4).astype("float32"))
    if with_bias:
        operands, layouts = (q, k, v, bias), (_BHSD,) * 3 + (_BS,)

        def kernel(q, k, v, bb):
            return flash_attention_bias(q, k, v, bb, causal, None, 32, 32,
                                        True)
    else:
        operands, layouts = (q, k, v), (_BHSD,) * 3

        def kernel(q, k, v):
            return flash_attention(q, k, v, causal, None, 32, 32, True)

    def ref(q, k, v):
        return blockwise_attention(q, k, v, causal=causal, block_k=32,
                                   bias=bias if with_bias else None)[0]

    def run(q, k, v):
        return _sharded(mesh_axes, b, (h, h, h), kernel,
                        (q, k, v) + operands[3:], layouts, _BHSD)

    _close(run(q, k, v), ref(q, k, v), 2e-5)
    grads = jax.grad(lambda *a: (run(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (ref(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    _close(grads, want, 1e-4)


@pytest.mark.parametrize("causal,with_bias", [(False, False), (True, False),
                                              (False, True)],
                         ids=["plain", "causal", "bias"])
def test_sharded_packed_kernels_match_blockwise(causal, with_bias):
    """The packed kernels per ``dp`` shard (what the dp4 BERT cell runs on
    the chip) against the op's own blockwise lowering of the packed
    projection: the output and the gradient of ``qkv``."""
    from paddle_tpu.ops.attention_ops import _BS, _BSH
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention_packed, flash_attention_packed_bias)

    rng = np.random.RandomState(6)
    b = 8
    qkv = jnp.asarray(rng.randn(b, PS, 3 * PH).astype("float32"))
    bias = jnp.asarray(
        np.where(rng.rand(b, PS) > 0.2, 0.0, -1e4).astype("float32"))
    if with_bias:
        extra, layouts = (bias,), (_BSH, _BS)

        def kernel(x, bb):
            return flash_attention_packed_bias(x, bb, PNH, causal, None, 64,
                                               32, True)
    else:
        extra, layouts = (), (_BSH,)

        def kernel(x):
            return flash_attention_packed(x, PNH, causal, None, 64, 32, True)

    def ref(x):
        t = x.reshape(b, PS, 3, PNH, PH // PNH)
        q, k, v = (jnp.moveaxis(t[:, :, i], 1, 2) for i in range(3))
        o, _ = blockwise_attention(q, k, v, causal=causal, block_k=32,
                                   bias=bias if with_bias else None)
        return jnp.moveaxis(o, 1, 2).reshape(b, PS, PH)

    def run(x):
        return _sharded({"dp": 4}, b, None, kernel, (x,) + extra, layouts,
                        _BSH)

    _close(run(qkv), ref(qkv), 2e-5)
    _close(jax.grad(lambda x: (run(x) ** 2).sum())(qkv),
           jax.grad(lambda x: (ref(x) ** 2).sum())(qkv), 1e-4)


def test_sharded_bert_step_on_the_cpu_keeps_the_blockwise_route():
    """A small BERT step through ``build_sharded_step`` over a ``dp`` mesh
    of host devices: not a TPU backend, so every attention op (and its
    re-lowering inside the auto-grad op) books ``blockwise`` and nothing
    takes the ``shard_map`` route."""
    import bench
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.parallel import build_sharded_step, dp_mesh

    names = ("pallas", "pallas_sharded", "blockwise")
    before = {n: stat_get(f"attention_lowered_{n}") for n in names}
    layers_, batch, seq, pred = 2, 8, 64, 10
    main_p, startup, feed_names, loss, _ = bench.build_bert_train_programs(
        dict(batch_size=batch, seq_len=seq, vocab_size=211, hidden=128,
             num_layers=layers_, num_heads=2, intermediate=256,
             max_predictions=pred, use_flash=True, dropout=0.1))
    scope = pt.Scope()
    pt.Executor(pt.CPUPlace()).run(startup, scope=scope)
    mesh = dp_mesh(4, devices=jax.devices()[:4])
    fn, mut_in, const_in, _ = build_sharded_step(
        main_p, feed_names, [loss.name], mesh)
    rng = np.random.RandomState(7)
    feed = {
        "input_ids": rng.randint(0, 211, (batch, seq)).astype("int32"),
        "token_type_ids": np.zeros((batch, seq), "int32"),
        "attn_mask": np.ones((batch, seq), "float32"),
        "mlm_positions": np.sort(np.stack(
            [rng.choice(seq, pred, replace=False) for _ in range(batch)]),
            axis=1).astype("int32"),
        "mlm_labels": rng.randint(0, 211, (batch, pred)).astype("int32"),
        "mlm_weights": np.ones((batch, pred), "float32"),
    }
    fetches, _, _ = fn(tuple(feed[n] for n in feed_names),
                       tuple(scope.find_var(n) for n in mut_in),
                       tuple(scope.find_var(n) for n in const_in),
                       np.int32(1))
    assert np.isfinite(np.asarray(fetches[0])).all()
    moved = {n: stat_get(f"attention_lowered_{n}") - before[n]
             for n in names}
    assert moved == {"pallas": 0, "pallas_sharded": 0,
                     "blockwise": 2 * layers_}
