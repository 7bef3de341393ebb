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
    from paddle_tpu.models.bert import build_bert_train_programs
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.parallel import build_sharded_step, dp_mesh

    names = ("pallas", "pallas_sharded", "blockwise")
    before = {n: stat_get(f"attention_lowered_{n}") for n in names}
    layers_, batch, seq, pred = 2, 8, 64, 10
    main_p, startup, feed_names, loss, _ = build_bert_train_programs(
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


# ---------------------------------------------------------------------------
# the grad op reads the forward's saved output and softmax statistic
# (ops/attention_ops.py _attention_grad): the kernel route without a chip
# ---------------------------------------------------------------------------

@pytest.fixture
def as_tpu(monkeypatch):
    """``jax.default_backend()`` answers "tpu", so the ops take the kernel
    route.  A Mosaic kernel does not compile on the CPU: such a step is
    traced (``jax.make_jaxpr``), or run with ``interpreted`` as well."""
    import paddle_tpu.parallel.sharded as sharded
    from paddle_tpu.ops import attention_ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sharded, "ensure_compile_cache", lambda: None)
    monkeypatch.setattr(attention_ops, "_downgrades_logged", set())
    attention_ops._sharded_kernel.cache_clear()
    yield
    attention_ops._sharded_kernel.cache_clear()


@pytest.fixture
def interpreted(as_tpu, monkeypatch):
    """Every ``pallas_call`` in interpret mode, whatever its caller asks."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **kw: real(*a, **dict(kw, interpret=True)))


def _count_pallas_calls(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else [val]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_pallas_calls(sub)
    return n


def _grad_counts():
    from paddle_tpu.monitor import stat_get

    return {n: stat_get(f"attention_{n}")
            for n in ("grad_saved", "grad_relowered", "lowered_pallas",
                      "lowered_blockwise")}


def _moved(before):
    return {k: v - before[k] for k, v in _grad_counts().items()}


def _mesh(axes):
    n = int(np.prod(list(axes.values())))
    return make_mesh(axes, devices=jax.devices()[:n])


def _encoder_step(num_layers, with_bias, mesh_axes, batch=8, seq=128):
    """The training step of a small BERT encoder (packed attention, hidden
    128 in 2 heads of 64) under bf16 AMP with ``flash_attention_qkv``
    white-listed, as the benchmark's recipe has it: ``(fn, args)`` of
    ``build_sharded_step``."""
    from paddle_tpu import optimizer
    from paddle_tpu.contrib import mixed_precision
    from paddle_tpu.models.bert import bert_encoder
    from paddle_tpu.parallel import build_sharded_step

    main_p, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main_p, startup):
        ids = layers.data("input_ids", [batch, seq], dtype="int64",
                          append_batch_size=False)
        mask = layers.data("attn_mask", [batch, seq],
                           append_batch_size=False) if with_bias else None
        enc = bert_encoder(ids, None, mask, vocab_size=211, hidden=128,
                           num_layers=num_layers, num_heads=2, seq_len=seq,
                           intermediate=256, max_position=seq, dropout=0.0)
        loss = layers.reduce_mean(enc)
        mixed_precision.decorate(
            optimizer.AdamOptimizer(1e-3), dtype="bfloat16",
            amp_lists=mixed_precision.AutoMixedPrecisionLists(
                custom_white_list=["flash_attention_qkv", "layer_norm",
                                   "elementwise_add"])).minimize(loss)
    scope = pt.Scope()
    pt.Executor(pt.CPUPlace()).run(startup, scope=scope)
    feed_names = ["input_ids"] + (["attn_mask"] if with_bias else [])
    fn, mut_in, const_in, _ = build_sharded_step(
        main_p, feed_names, [loss.name], _mesh(mesh_axes))
    rng = np.random.RandomState(8)
    feed = {"input_ids": rng.randint(0, 211, (batch, seq)).astype("int32"),
            "attn_mask": (rng.rand(batch, seq) > 0.1).astype("float32")}
    return fn, (tuple(feed[n] for n in feed_names),
                tuple(scope.find_var(n) for n in mut_in),
                tuple(scope.find_var(n) for n in const_in), np.int32(1))


@pytest.mark.parametrize("mesh_axes", [{"dp": 1}, {"dp": 4}],
                         ids=["one_device", "dp4"])
@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("num_layers", [1, 2])
def test_training_step_holds_three_kernels_a_layer(as_tpu, monkeypatch,
                                                   num_layers, with_bias,
                                                   mesh_axes):
    """Forward, dkv, dq: the forward kernel is not launched a second time
    inside the grad op, on one device and per ``dp`` shard; and under
    bf16 AMP the statistic reaches the backward kernels in float32."""
    from paddle_tpu.ops import attention_ops

    seen = []
    real = attention_ops._packed_backward

    def spy(qkv, out, lse, g, *bias, **static):
        seen.append((str(qkv.dtype), str(out.dtype), str(lse.dtype),
                     str(g.dtype), lse.shape[1:]))
        return real(qkv, out, lse, g, *bias, **static)

    monkeypatch.setattr(attention_ops, "_packed_backward", spy)
    before = _grad_counts()
    fn, args = _encoder_step(num_layers, with_bias, mesh_axes)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    assert _count_pallas_calls(jaxpr) == 3 * num_layers
    assert _moved(before) == {
        "grad_saved": num_layers, "grad_relowered": 0,
        "lowered_pallas": num_layers, "lowered_blockwise": 0}
    assert set(seen) == {("bfloat16", "bfloat16", "float32", "bfloat16",
                          (2, 128))}


def test_training_step_under_mp_keeps_the_reference_route(as_tpu, caplog):
    """The packed columns do not split over ``mp``: forward and grad op
    stay on the blockwise formulation and say why, once each."""
    import logging

    before = _grad_counts()
    fn, args = _encoder_step(2, True, {"dp": 2, "mp": 2})
    with caplog.at_level(logging.WARNING, "paddle_tpu.ops.attention"):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    assert _count_pallas_calls(jaxpr) == 0
    assert _moved(before) == {
        "grad_saved": 0, "grad_relowered": 2, "lowered_pallas": 0,
        "lowered_blockwise": 4}
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 2 and all("mp=2" in s for s in said)
    assert sum("flash_attention_qkv_grad lowered as jax.vjp" in s
               for s in said) == 1


def _attention_program(packed, with_bias, causal, batch, bias_grad=True):
    """One attention op, a weighted sum of its output as the loss, and
    the backward ops: (program, feed dict, names of the gradients)."""
    rng = np.random.RandomState(9)
    main_p, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    feed = {"w": rng.randn(batch, PS, PH).astype("float32")}
    with pt.program_guard(main_p, startup):
        bias = None
        if with_bias:
            bias = layers.data("bias", [batch, PS], append_batch_size=False)
            bias.stop_gradient = not bias_grad
            feed["bias"] = np.where(rng.rand(batch, PS) > 0.2, 0.0,
                                    -1e4).astype("float32")
        if packed:
            x = layers.data("x", [batch, PS, 3 * PH],
                            append_batch_size=False)
            x.stop_gradient = False
            feed["x"] = rng.randn(batch, PS, 3 * PH).astype("float32")
            out = layers.flash_attention_qkv(x, PNH, bias=bias,
                                             causal=causal)
            wanted = ["x"]
        else:
            wanted = ["q", "k", "v"]
            qkv = []
            for n in wanted:
                t = layers.data(n, [batch, PNH, PS, PH // PNH],
                                append_batch_size=False)
                t.stop_gradient = False
                feed[n] = rng.randn(batch, PNH, PS,
                                    PH // PNH).astype("float32")
                qkv.append(t)
            out = layers.transpose(
                layers.flash_attention(*qkv, bias=bias, causal=causal),
                [0, 2, 1, 3])
            out = layers.reshape(out, [batch, PS, PH])
        w = layers.data("w", [batch, PS, PH], append_batch_size=False)
        loss = layers.reduce_sum(layers.elementwise_mul(out, w))
        pt.append_backward(loss)
    return main_p, feed, [n + "@GRAD" for n in wanted + (
        ["bias"] if with_bias and bias_grad else [])]


def _run_program(main_p, feed, fetch_names, mesh_axes):
    from paddle_tpu.parallel import build_sharded_step

    names = sorted(feed)
    fn, _, _, _ = build_sharded_step(main_p, names, fetch_names,
                                     _mesh(mesh_axes))
    fetches, _, _ = fn(tuple(feed[n] for n in names), (), (), np.int32(1))
    return [np.asarray(f) for f in fetches]


@pytest.mark.parametrize("mesh_axes", [{"dp": 1}, {"dp": 4}],
                         ids=["one_device", "dp4"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "split"])
def test_saved_backward_equals_the_custom_vjp(interpreted, packed,
                                              with_bias, causal, mesh_axes):
    """The grad op's gradients off the saved (Out, SoftmaxLse) against
    ``jax.vjp`` of the ``custom_vjp`` entry on the same operands by the
    same route: the same kernels on the same numbers, so equal; and
    against the blockwise formulation's, within its tolerance."""
    from paddle_tpu.ops.attention_ops import (_BHSD, _BS, _BSH, call_kernel,
                                              kernel_partition)
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention_bias, flash_attention_packed,
        flash_attention_packed_bias)

    batch = 4
    main_p, feed, grad_names = _attention_program(packed, with_bias,
                                                  causal, batch)
    before = _grad_counts()
    got = _run_program(main_p, feed, grad_names, mesh_axes)
    assert _moved(before) == {"grad_saved": 1, "grad_relowered": 0,
                              "lowered_pallas": 1, "lowered_blockwise": 0}

    mesh = _mesh(mesh_axes)
    heads = None if packed else (PNH,) * 3
    route, how = kernel_partition(dict(mesh.shape), (), batch, heads)
    assert route == ("direct" if mesh_axes["dp"] == 1 else "shard_map")
    if packed:
        entry = flash_attention_packed_bias if with_bias \
            else flash_attention_packed
        operands = [feed["x"]]
        layouts, out_layout, static = (_BSH,), _BSH, dict(num_heads=PNH)
    else:
        entry = flash_attention_bias if with_bias else flash_attention
        operands = [feed[n] for n in "qkv"]
        layouts, out_layout, static = (_BHSD,) * 3, _BHSD, {}
    if with_bias:
        operands.append(feed["bias"])
        layouts += (_BS,)

    def through_entry(*xs):
        out = call_kernel(mesh, how, entry, xs, layouts, out_layout,
                          causal=causal, sm_scale=None, **static)
        if not packed:
            out = jnp.moveaxis(out, 1, 2).reshape(batch, PS, PH)
        return (out * feed["w"]).sum()

    def through_blockwise(*xs):
        if packed:
            t = xs[0].reshape(batch, PS, 3, PNH, PH // PNH)
            q, k, v = (jnp.moveaxis(t[:, :, i], 1, 2) for i in range(3))
        else:
            q, k, v = xs[:3]
        o, _ = blockwise_attention(q, k, v, causal=causal,
                                   bias=xs[-1] if with_bias else None)
        return (jnp.moveaxis(o, 1, 2).reshape(batch, PS, PH)
                * feed["w"]).sum()

    argnums = tuple(range(len(operands)))
    want = jax.jit(jax.grad(through_entry, argnums))(*operands)
    for name, g, w in zip(grad_names, got, want):
        np.testing.assert_array_equal(g, np.asarray(w), err_msg=name)
    _close(got, jax.grad(through_blockwise, argnums)(*operands), 1e-4)


@pytest.mark.parametrize("bias_grad", [False, True],
                         ids=["mask", "learned_bias"])
def test_split_heads_over_mp_sum_the_bias_gradient(interpreted, bias_grad):
    """Heads split over ``mp``: the statistic splits with them and the
    saved backward runs per shard, unless a gradient of ``Bias`` is asked
    for, whose per-shard parts only the vjp through ``shard_map`` sums."""
    main_p, feed, grad_names = _attention_program(False, True, False, 4,
                                                  bias_grad=bias_grad)
    before = _grad_counts()
    got = _run_program(main_p, feed, grad_names, {"dp": 2, "mp": 2})
    moved = _moved(before)
    assert (moved["grad_saved"], moved["grad_relowered"]) == (
        (0, 1) if bias_grad else (1, 0))

    def through_blockwise(q, k, v, bias):
        o, _ = blockwise_attention(q, k, v, bias=bias)
        return (jnp.moveaxis(o, 1, 2).reshape(4, PS, PH) * feed["w"]).sum()

    want = jax.grad(through_blockwise, (0, 1, 2, 3))(
        *(feed[n] for n in ("q", "k", "v", "bias")))
    _close(got, want[:len(got)], 1e-4)


def test_forward_op_without_the_statistic_slot_trains_as_before(interpreted):
    """A program saved before the slot existed: its grad op goes through
    the auto-grad lowering (the forward kernel a second time) and gives
    the gradients the slot's program gives."""
    main_p, feed, grad_names = _attention_program(True, True, False, 4)
    want = _run_program(main_p, feed, grad_names, {"dp": 1})

    old_p, _, _ = _attention_program(True, True, False, 4)
    for op in old_p.global_block().ops:
        for slots in (op.inputs, op.outputs):
            slots.pop("SoftmaxLse", None)
            slots.pop("SoftmaxLse@GRAD", None)
        if "__fwd_outputs__" in op.attrs:
            op.attrs["__fwd_outputs__"].pop("SoftmaxLse", None)
    before = _grad_counts()
    got = _run_program(old_p, feed, grad_names, {"dp": 1})
    assert _moved(before) == {"grad_saved": 0, "grad_relowered": 1,
                              "lowered_pallas": 2, "lowered_blockwise": 0}
    for name, g, w in zip(grad_names, got, want):
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "split"])
def test_cpu_route_books_the_relowered_grad_without_a_warning(packed,
                                                              caplog):
    import logging

    main_p, feed, grad_names = _attention_program(packed, True, False, 4)
    before = _grad_counts()
    with caplog.at_level(logging.WARNING, "paddle_tpu.ops.attention"):
        got = _run_program(main_p, feed, grad_names, {"dp": 1})
    assert _moved(before) == {"grad_saved": 0, "grad_relowered": 1,
                              "lowered_pallas": 0, "lowered_blockwise": 2}
    assert not caplog.records
    assert all(np.isfinite(g).all() and np.abs(g).max() > 0 for g in got)


def test_double_backward_walks_through_the_attention_grad_op():
    """``gradients`` of a gradient through ``flash_attention_grad`` on the
    CPU route: the grad op's desc names no cotangent of the statistic, so
    every input its own grad op reads has a value."""
    rng = np.random.RandomState(10)
    shape = [2, 2, 32, 16]
    vals = {n: rng.randn(*shape).astype("float32") for n in "qkv"}
    main_p, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main_p, startup):
        q, k, v = (layers.data(n, shape, append_batch_size=False)
                   for n in "qkv")
        q.stop_gradient = False
        out = layers.flash_attention(q, k, v, causal=True)
        g1 = pt.gradients(layers.reduce_sum(layers.square(out)), q)[0]
        g2 = pt.gradients(layers.reduce_sum(layers.square(g1)), q)[0]
    got = pt.Executor().run(main_p, feed=vals, fetch_list=[g1, g2])

    def first(q):
        return jax.grad(lambda q: (blockwise_attention(
            q, vals["k"], vals["v"], causal=True)[0] ** 2).sum())(q)

    _close(got[0], first(vals["q"]), 1e-5)
    _close(got[1], jax.grad(lambda q: (first(q) ** 2).sum())(vals["q"]),
           1e-4)


# ---------------------------------------------------------------------------
# whoever differentiates the forward lowering itself (not the program's
# grad op) goes through the two-output custom_vjp entry: the dygraph
# tracer, a pipeline stage, a differentiable sub-block
# ---------------------------------------------------------------------------

def _packed_blockwise_grad(x, w, causal=False):
    def loss(x):
        t = x.reshape(x.shape[0], PS, 3, PNH, PH // PNH)
        q, k, v = (jnp.moveaxis(t[:, :, i], 1, 2) for i in range(3))
        o, _ = blockwise_attention(q, k, v, causal=causal)
        return (jnp.moveaxis(o, 1, 2).reshape(x.shape[0], PS, PH) * w).sum()
    return jax.grad(loss)(x)


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "split"])
@pytest.mark.parametrize("route", ["cpu", "kernel"])
def test_dygraph_trains_through_attention(request, route, packed):
    """The tracer takes ``jax.vjp`` of the op's forward lowering and
    collects every declared output: the statistic is bound on both routes,
    and on the kernel route the lowering differentiates as the kernels'
    own backward (a bare ``pallas_call`` has no transpose)."""
    from paddle_tpu import dygraph

    if route == "kernel":
        request.getfixturevalue("interpreted")
    rng = np.random.RandomState(11)
    x = rng.randn(2, PS, 3 * PH).astype("float32")
    w = rng.randn(2, PS, PH).astype("float32")
    before = _grad_counts()
    # the split form takes the same numbers as [B, heads, S, D] tensors
    parts = [np.ascontiguousarray(np.moveaxis(
        x.reshape(2, PS, 3, PNH, PH // PNH)[:, :, i], 1, 2))
        for i in range(3)]
    with dygraph.guard():
        leaves = [dygraph.to_variable(a) for a in ([x] if packed else parts)]
        for leaf in leaves:
            leaf.stop_gradient = False
        if packed:
            out = layers.flash_attention_qkv(leaves[0], PNH)
        else:
            out = layers.reshape(
                layers.transpose(layers.flash_attention(*leaves),
                                 [0, 2, 1, 3]), [2, PS, PH])
        layers.reduce_sum(out * dygraph.to_variable(w)).backward()
        got = [leaf.gradient() for leaf in leaves]
    moved = _moved(before)
    assert (moved["lowered_pallas"], moved["lowered_blockwise"]) == (
        (1, 0) if route == "kernel" else (0, 1))
    want = np.asarray(_packed_blockwise_grad(x, w))
    if not packed:
        want = np.moveaxis(want.reshape(2, PS, 3, PNH, PH // PNH), 2, 0)
        want = [np.moveaxis(t, 1, 2) for t in want]
    _close(got, want if not packed else [want], 1e-4)


def _staged_attention(num_stages, batch):
    """``num_stages`` uniform stages, each a projection to [B, S, 3H] and
    packed attention over it; mean-square loss."""
    from paddle_tpu import optimizer
    from paddle_tpu.framework.core import device_guard

    main_p, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main_p, startup):
        x = layers.data("x", [batch, PS, PH], append_batch_size=False)
        label = layers.data("label", [batch, PS, PH],
                            append_batch_size=False)
        h = x
        for s in range(num_stages):
            with device_guard(f"gpu:{s}"):
                h = layers.flash_attention_qkv(
                    layers.fc(h, 3 * PH, num_flatten_dims=2,
                              name=f"stage{s}"), PNH)
        diff = layers.elementwise_sub(h, label)
        loss = layers.reduce_mean(layers.elementwise_mul(diff, diff))
        optimizer.SGD(learning_rate=0.5).minimize(loss)
    return main_p, startup, loss


def test_pipeline_stage_trains_through_the_kernels(interpreted):
    """``build_pp_pipeline_step`` takes ``jax.value_and_grad`` of the
    stages' forward lowerings (no grad op is lowered): on the kernel route
    that is the custom_vjp entry, and the trajectory is the plain
    program's, whose grad ops read the saved output and statistic."""
    from paddle_tpu.parallel import build_pp_pipeline_step

    rng = np.random.RandomState(12)
    feed = {"x": rng.randn(4, PS, PH).astype("float32"),
            "label": rng.randn(4, PS, PH).astype("float32")}
    names = ["x", "label"]

    main_p, startup, loss = _staged_attention(2, 4)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    params = [p.name for p in main_p.global_block().all_parameters()]
    init = [np.asarray(scope.find_var(n)) for n in params]
    before = _grad_counts()
    plain = [float(np.asarray(exe.run(main_p, feed=feed, fetch_list=[loss],
                                      scope=scope)[0]).reshape(-1)[0])
             for _ in range(3)]
    assert _moved(before)["grad_saved"] == 2

    main_p, startup, loss = _staged_attention(2, 4)
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    for p, v in zip(main_p.global_block().all_parameters(), init):
        scope.set_var(p.name, v)
    before = _grad_counts()
    fn, mut_in, const_in, _ = build_pp_pipeline_step(
        main_p, names, [loss.name], 2, make_mesh({"pp": 2}))
    fn.prepare_scope(scope)
    mut = tuple(scope.find_var(n) for n in mut_in)
    const = tuple(scope.find_var(n) for n in const_in)
    piped = []
    for step in range(3):
        fetches, mut, _ = fn(tuple(feed[n] for n in names), mut, const,
                             np.int32(step + 1))
        piped.append(float(np.asarray(fetches[0]).reshape(-1)[0]))
    moved = _moved(before)
    assert moved["lowered_pallas"] >= 1 and not moved["lowered_blockwise"]
    assert not moved["grad_saved"] and not moved["grad_relowered"]
    np.testing.assert_allclose(piped, plain, rtol=2e-4, atol=1e-6)
    assert piped[-1] < piped[0]


def test_sub_block_trains_through_the_kernels(interpreted):
    """``run_program`` is the sub-block op with a gradient (``while`` and
    the conditionals have none): its auto-grad op takes ``jax.vjp`` of the
    block's forward lowerings in a fresh context, attention among them."""
    rng = np.random.RandomState(13)
    feed = {"x": rng.randn(2, PS, 3 * PH).astype("float32"),
            "w": rng.randn(2, PS, PH).astype("float32")}
    main_p, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main_p, startup):
        x = layers.data("x", [2, PS, 3 * PH], append_batch_size=False)
        x.stop_gradient = False
        w = layers.data("w", [2, PS, PH], append_batch_size=False)
        block = main_p.current_block()
        out = block.create_var(name="attended", shape=[2, PS, PH],
                               dtype="float32")
        sub = main_p._create_block()
        layers.assign(layers.flash_attention_qkv(x, PNH, causal=True), out)
        main_p._rollback()
        block.append_op("run_program", inputs={"X": [x]},
                        outputs={"Out": [out]},
                        attrs={"sub_block": sub.idx}, infer_shape=False)
        loss = layers.reduce_sum(layers.elementwise_mul(out, w))
        pt.append_backward(loss)
    before = _grad_counts()
    got, = _run_program(main_p, feed, ["x@GRAD"], {"dp": 1})
    moved = _moved(before)
    # the op in the block, and once more inside run_program's auto-grad op
    assert moved["lowered_pallas"] == 2 and not moved["lowered_blockwise"]
    _close(got, _packed_blockwise_grad(feed["x"], feed["w"], causal=True),
           1e-4)


def test_amp_leaves_what_the_forward_saved_uncast_for_any_grad_op():
    """``_lower_with_amp``'s rule for grad ops, on an op that is not
    attention: forward inputs and cotangents are cast to the AMP dtype,
    forward outputs reach an explicit grad lowering as the forward emitted
    them, a var that is both (in place) is cast, and the environment is
    restored afterwards."""
    from paddle_tpu.framework.core import Operator
    from paddle_tpu.ops import registry

    seen = {}

    def lower(ctx, gop):
        seen.update((n, str(ctx.env[n].dtype))
                    for n in gop.input_arg_names())

    registry.register_op("amp_probe_grad", lower=lower, grad=None)
    try:
        gop = Operator(
            None, "amp_probe_grad",
            {"X": ["x"], "State": ["s"], "Out": ["out"], "Stat": ["stat"],
             "StateOut": ["s"], "Out@GRAD": ["out@GRAD"]},
            {"X@GRAD": ["x@GRAD"]},
            {"__fwd_type__": "amp_probe",
             "__fwd_inputs__": {"X": ["x"], "State": ["s"]},
             "__fwd_outputs__": {"Out": ["out"], "Stat": ["stat"],
                                 "StateOut": ["s"]}})
        env = {"x": jnp.ones((2,), jnp.float32),
               "s": jnp.ones((2,), jnp.float32),
               "out": jnp.ones((2,), jnp.bfloat16),
               "stat": jnp.ones((2,), jnp.float32),
               "out@GRAD": jnp.ones((2,), jnp.float32)}
        ctx = registry.LowerContext(
            None, env, amp={"dtype": "bfloat16", "white": {"amp_probe"},
                            "black": set()})
        registry.lower_op(ctx, gop)
    finally:
        registry._REGISTRY.pop("amp_probe_grad")
    assert seen == {"x": "bfloat16", "s": "bfloat16", "out": "bfloat16",
                    "stat": "float32", "out@GRAD": "bfloat16"}
    assert {n: str(v.dtype) for n, v in env.items()} == {
        "x": "float32", "s": "float32", "out": "bfloat16",
        "stat": "float32", "out@GRAD": "float32"}
