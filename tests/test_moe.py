"""Expert parallelism (MoE) tests — VERDICT r4 #9, SURVEY §2.6 EP row.

Covers: Switch top-1 gating math vs a numpy reference, ep8 shard_map
all_to_all parity vs the dense path, capacity-factor dropping, balanced
routing, and a small training run with the auxiliary loss.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.parallel import build_spmd_step, make_mesh

R = np.random.RandomState

N, H, E, I = 16, 8, 4, 12


def _np_softmax(x):
    e = np.exp(x - x.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _np_moe(x, gate_w, w1, b1, w2, b2, capacity_factor=1.25):
    """Loop reference of the Switch math (top-1, capacity, gelu)."""
    n, h = x.shape
    e = gate_w.shape[1]
    probs = _np_softmax(x @ gate_w)
    expert = probs.argmax(-1)
    gate = probs[np.arange(n), expert]
    C = max(1, int(np.ceil(n / e * capacity_factor)))
    out = np.zeros_like(x)
    counts = np.zeros(e)
    slots = np.zeros(e, int)
    for t in range(n):
        ex = expert[t]
        counts[ex] += 1
        if slots[ex] >= C:
            continue  # dropped: zero contribution
        slots[ex] += 1
        hdd = x[t] @ w1[ex] + b1[ex]
        g = 0.5 * hdd * (1 + np.tanh(np.sqrt(2 / np.pi)
                                     * (hdd + 0.044715 * hdd ** 3)))
        out[t] = (g @ w2[ex] + b2[ex]) * gate[t]
    frac = np.eye(e)[expert].mean(0)
    aux = e * (frac * probs.mean(0)).sum()
    return out, aux, counts


def _weights(seed=0):
    r = R(seed)
    return dict(
        gate_w=r.randn(H, E).astype("float32") * 0.5,
        w1=r.randn(E, H, I).astype("float32") * 0.3,
        b1=r.randn(E, I).astype("float32") * 0.1,
        w2=r.randn(E, I, H).astype("float32") * 0.3,
        b2=r.randn(E, H).astype("float32") * 0.1)


def _moe_program(ws, shape=(N, H)):
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    feed = {}
    with pt.program_guard(main, startup):
        block = main.global_block()
        x = block.create_var(name="mx", shape=list(shape),
                             dtype="float32", is_data=True)
        slots = {"X": ["mx"]}
        for slot, key in [("GateW", "gate_w"), ("W1", "w1"),
                          ("B1", "b1"), ("W2", "w2"), ("B2", "b2")]:
            nm = f"m_{key}"
            block.create_var(name=nm, shape=ws[key].shape,
                             dtype="float32", is_data=True)
            feed[nm] = ws[key]
            slots[slot] = [nm]
        for nm, shp, dt in [("m_out", list(shape), "float32"),
                            ("m_aux", [], "float32"),
                            ("m_cnt", [E], "float32")]:
            block.create_var(name=nm, shape=shp, dtype=dt)
        block.append_op("moe_ffn", inputs=slots,
                        outputs={"Out": ["m_out"], "AuxLoss": ["m_aux"],
                                 "ExpertCount": ["m_cnt"]},
                        attrs={"capacity_factor": 1.25,
                               "activation": "gelu"})
    return main, startup, feed


def test_moe_matches_numpy_reference():
    ws = _weights()
    x = R(1).randn(N, H).astype("float32")
    want, aux_ref, counts_ref = _np_moe(x, **ws)
    main, startup, feed = _moe_program(ws)
    feed["mx"] = x
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    out, aux, cnt = exe.run(main, feed=feed,
                            fetch_list=["m_out", "m_aux", "m_cnt"],
                            scope=scope)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(float(np.asarray(aux)), aux_ref,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(cnt), counts_ref)


def test_moe_ep8_all_to_all_matches_dense():
    """{dp:1, ep:8} shard_map: the all_to_all dispatch/combine must
    reproduce the dense single-device output exactly."""
    ws = _weights(2)
    # E must divide ep axis: use E=8 experts here
    r = R(3)
    ws = dict(gate_w=r.randn(H, 8).astype("float32") * 0.5,
              w1=r.randn(8, H, I).astype("float32") * 0.3,
              b1=r.randn(8, I).astype("float32") * 0.1,
              w2=r.randn(8, I, H).astype("float32") * 0.3,
              b2=r.randn(8, H).astype("float32") * 0.1)
    x = R(4).randn(N, H).astype("float32")
    want, _, _ = _np_moe(x, **ws)

    main, startup, feed = _moe_program(ws)
    feed["mx"] = x
    mesh = make_mesh({"dp": 1, "ep": 8})
    fn, mut_in, const_in, _ = build_spmd_step(
        main, list(feed), ["m_out"], mesh)
    fetches, _, _ = fn(tuple(feed.values()), (), (), np.int32(1))
    got = np.asarray(fetches[0])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_overflow():
    """All tokens forced onto expert 0: rows past capacity contribute
    zero (Switch overflow semantics — the caller's residual carries
    them)."""
    ws = _weights(5)
    ws["gate_w"] = np.zeros((H, E), "float32")
    ws["gate_w"][:, 0] = 5.0  # expert 0 wins everywhere
    x = np.abs(R(6).randn(N, H)).astype("float32")
    main, startup, feed = _moe_program(ws)
    feed["mx"] = x
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    out, cnt = exe.run(main, feed=feed, fetch_list=["m_out", "m_cnt"],
                       scope=scope)
    out, cnt = np.asarray(out), np.asarray(cnt)
    C = int(np.ceil(N / E * 1.25))  # 5
    assert cnt[0] == N
    kept = (np.abs(out).sum(1) > 1e-6).sum()
    assert kept == C, (kept, C)  # only the first C tokens served


def test_moe_balanced_routing_spreads_tokens():
    ws = _weights(7)
    x = R(8).randn(64, H).astype("float32")
    main, startup, feed = _moe_program(ws, shape=(64, H))
    feed["mx"] = x
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    aux, cnt = exe.run(main, feed=feed, fetch_list=["m_aux", "m_cnt"],
                       scope=scope)
    cnt = np.asarray(cnt)
    assert cnt.sum() == 64
    assert (cnt > 0).all(), cnt  # random gate: every expert used
    # aux loss is ~1 when balanced, E when collapsed
    assert 0.9 < float(np.asarray(aux)) < 2.5


def test_moe_layer_trains_with_aux_loss():
    """layers.moe_ffn end-to-end: regression target through the expert
    path; loss (incl. 0.01*aux) must drop and routing must not
    collapse."""
    x = R(9).randn(32, H).astype("float32")
    y = np.tanh(x @ R(10).randn(H, H).astype("float32"))

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        xv = layers.data("x", [H], dtype="float32")
        yv = layers.data("y", [H], dtype="float32")
        out, aux = layers.moe_ffn(xv, num_experts=E, d_ff=I)
        res = pt.layers.elementwise_add(out, xv)  # residual
        mse = layers.mean(layers.square(res - yv))
        loss = pt.layers.elementwise_add(
            mse, pt.layers.scale(aux, scale=0.01))
        optimizer.AdamOptimizer(5e-3).minimize(loss)
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    losses = []
    for _ in range(60):
        l, = exe.run(main, feed={"x": x, "y": y}, fetch_list=[mse],
                     scope=scope)
        losses.append(float(np.asarray(l).reshape(-1)[0]))
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])


def test_moe_rules_shard_expert_weights():
    from paddle_tpu.parallel import megatron_rules, moe_rules
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh({"dp": 2, "ep": 4})
    rules = moe_rules(mesh, inner=megatron_rules(mesh))
    assert rules.spec("moe_ffn.w_1", (8, 16, 32)) == P("ep", None, None)
    assert rules.spec("fc.w_0", (16, 32)) == P()  # no mp axis here
    mesh2 = make_mesh({"dp": 2, "mp": 2, "ep": 2})
    rules2 = moe_rules(mesh2, inner=megatron_rules(mesh2))
    assert rules2.spec("moe_ffn.w_1", (8, 16, 32)) == P("ep", None,
                                                        None)
    assert rules2.spec("fc.w_0", (16, 32)) == P(None, "mp")


# ---------------------------------------------------------------------------
# dropless top-k routing: the rows behind ``valid`` (``moe_routed_tokens``)
# ---------------------------------------------------------------------------

def _valid_rows(which, n):
    return {"None": None, "all": np.ones(n, bool),
            "a_pad_tail": np.arange(n) < (5 * n) // 8,
            "every_second_row": np.arange(n) % 2 == 0,
            "none": np.zeros(n, bool)}[which]


@pytest.mark.parametrize("route", ["ragged_dot", "kernel"])
@pytest.mark.parametrize("n", [6, 48], ids=["a_decodes_rows", "a_rungs_rows"])
@pytest.mark.parametrize("which", ["None", "all", "a_pad_tail",
                                   "every_second_row", "none"])
def test_rows_behind_valid_go_through_no_expert(monkeypatch, which, n, route):
    """The pairs of a row behind ``valid`` sort past the last group: both
    ``grouped_matmul`` calls get sizes that sum to the valid rows' pairs,
    a real row's ``out`` is the all-rows formulation's, a pad row's is
    exactly 0, ``counts`` and ``logits`` are what they were, and a NaN in
    a pad row of ``x`` reaches no real row.  ``route`` "kernel": both
    products through the Pallas kernel in interpret mode."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.ops.pallas import grouped_matmul as kernel
    from paddle_tpu.parallel import moe

    highest = jax.lax.Precision.HIGHEST
    if route == "kernel":
        real = pl.pallas_call
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(pl, "pallas_call", lambda *a, **kw: real(
            *a, **dict(kw, interpret=True)))
        monkeypatch.setattr(kernel, "tiles",
                            lambda m, k, n, scoped=False: (8, n))
        kernel.grouped_matmul.clear_cache()
    seen = []
    product = moe.grouped_matmul
    monkeypatch.setattr(
        moe, "grouped_matmul", lambda rows, w, sizes, *a, **kw: (
            seen.append(np.asarray(sizes)),
            product(rows, w, sizes, *a, **kw))[1])
    rng = np.random.default_rng(n)
    hidden, experts, width, top_k = 16, 8, 12, 3
    x, rx = (rng.standard_normal((n, hidden)).astype(np.float32)
             for _ in range(2))
    wr = rng.standard_normal((hidden, experts)).astype(np.float32)
    wgu = rng.standard_normal((experts, hidden, 2 * width)).astype(np.float32)
    wd = rng.standard_normal((experts, width, hidden)).astype(np.float32)
    valid = _valid_rows(which, n)

    def layer(x, rx, valid):
        return [np.asarray(a) for a in moe.moe_routed_tokens(
            jnp.asarray(x), jnp.asarray(rx), wr, wgu, wd, top_k=top_k,
            valid=None if valid is None else jnp.asarray(valid),
            precision=highest)]

    want, every, logits = layer(x, rx, None)
    seen.clear()
    out, counts, got_logits = layer(x, rx, valid)
    live = np.ones(n, bool) if valid is None else valid
    chosen = np.argsort(-logits, axis=-1, kind="stable")[:, :top_k]
    assert [int(s.sum()) for s in seen] == [int(live.sum()) * top_k] * 2
    assert np.array_equal(seen[0], counts) and np.array_equal(seen[1], counts)
    assert np.array_equal(counts, np.bincount(chosen[live].reshape(-1),
                                              minlength=experts))
    assert np.array_equal(got_logits, logits)
    if live.all():
        assert np.array_equal(counts, every)
    if live.any():
        assert np.abs(out[live] - want[live]).max() \
            < 1e-6 * np.abs(want).max()
    assert not out[~live].any()
    if valid is None:
        return
    # NaN where no request reads: the rows behind ``valid``
    for a in (x, rx):
        a[~live] = np.nan
    out, counts_nan, _ = layer(x, rx, valid)
    assert np.array_equal(counts_nan, counts)
    assert not out[~live].any()
    if live.any():
        assert np.abs(out[live] - want[live]).max() \
            < 1e-6 * np.abs(want).max()
    if route == "kernel":
        kernel.grouped_matmul.clear_cache()


@pytest.mark.parametrize("sizes", [[10, 0, 21, 9, 0, 17, 3, 8], [0] * 8,
                                   [0, 0, 0, 0, 0, 0, 5, 0]],
                         ids=["short_of_m", "zero", "one_short_group"])
def test_kernel_visits_nothing_past_the_last_group(sizes):
    """The Pallas grouped matmul (interpret mode) over sizes that sum
    short of M, and to 0: the visit list ends at the block of the last
    row in a group, NaN rows past it reach no row of a group."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import grouped_matmul as kernel

    m, k, n, tm = 96, 16, 128, 16
    sizes = np.asarray(sizes, np.int32)
    filled = int(sizes.sum())
    rng = np.random.default_rng(filled)
    rows = rng.standard_normal((m, k)).astype(np.float32)
    rows[filled:] = np.nan
    weights = rng.standard_normal((8, k, n)).astype(np.float32)
    weights[sizes == 0] = np.nan
    _, _, block, visits = kernel.visits(jnp.asarray(sizes), m, tm)
    ends = np.cumsum(sizes)
    assert int(visits) == len({
        (r // tm, int(np.searchsorted(ends, r, side="right")))
        for r in range(filled)})
    assert not filled or int(np.asarray(block)[:int(visits)].max()) \
        == (filled - 1) // tm
    got = np.asarray(kernel.grouped_matmul(
        jnp.asarray(rows), jnp.asarray(weights), jnp.asarray(sizes),
        tm=tm, tn=n, interpret=True))
    assert got.shape == (m, n)
    which = np.searchsorted(ends, np.arange(filled), side="right")
    for r, g in enumerate(which):
        np.testing.assert_allclose(
            got[r], rows[r].astype(np.float64) @ weights[g], rtol=1e-5,
            atol=1e-5)


# ---------------------------------------------------------------------------
# the layer between and after its two products (PR 57)
# ---------------------------------------------------------------------------

def _parent_routed_tokens(x, router_x, router_w, w_gate_up, w_down, *, top_k,
                          activation, valid, precision, limit=None):
    """``moe_routed_tokens`` without ``held_first`` as PR 54 to PR 56 had
    it, kept here as what the new formulation is held to: ``h`` [N k, 2I]
    gated by a pass of its own, ``y`` scaled by another, scattered into
    [N k, H] of zeros and summed."""
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    N, E, inter = x.shape[0], router_w.shape[1], w_down.shape[1]
    logits, experts, weights = moe.route_top_k(router_x, router_w, top_k)
    flat = experts.reshape(-1)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, top_k), flat, E)
    order = jnp.argsort(flat, stable=True)
    group_sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    rows = jnp.take(x, order // top_k, axis=0)
    h = moe.grouped_matmul(rows, w_gate_up, group_sizes, precision)
    y = moe.grouped_matmul(moe._gated(h, inter, activation, limit), w_down,
                           group_sizes, precision)
    y = y * jnp.take(weights.reshape(-1), order)[:, None]
    if valid is not None:
        order = jnp.where(jnp.arange(N * top_k) < group_sizes.sum(), order,
                          N * top_k)
    y = jnp.zeros_like(y).at[order].set(y, mode="drop")
    return y.reshape(N, top_k, -1).sum(axis=1), group_sizes, logits


@pytest.mark.parametrize("route", ["ragged_dot", "kernel"])
@pytest.mark.parametrize("gate", [("relu", None), ("silu", None),
                                  ("silu", 0.5)],
                         ids=["relu", "silu", "silu_with_limit"])
@pytest.mark.parametrize("which", ["None", "all", "a_pad_tail", "none"])
def test_routed_layer_is_the_parents_bit_for_bit(monkeypatch, which, gate,
                                                 route):
    """The gate and the routing weight as the products' epilogues and the
    one gather-sum, against the parent's formulation: the same bits in
    ``out``, ``counts`` and ``logits``.  Every product's rows past
    ``group_sizes.sum()`` are set to NaN on both sides (they "hold what lay
    in memory"): a pad row's ``out`` is exactly 0 and no NaN reaches a real
    row.  The three counters move on the kernel's route alone (the
    gather-sum is the one combine, so its counter moves on both)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.ops.pallas import grouped_matmul as kernel
    from paddle_tpu.parallel import moe

    highest = jax.lax.Precision.HIGHEST
    if route == "kernel":
        real = pl.pallas_call
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(pl, "pallas_call", lambda *a, **kw: real(
            *a, **dict(kw, interpret=True)))
        monkeypatch.setattr(kernel, "tiles",
                            lambda m, k, n, scoped=False: (8, n))
        kernel.grouped_matmul.clear_cache()
    product = moe.grouped_matmul

    def poisoned(rows, w, sizes, *a, **kw):
        out = product(rows, w, sizes, *a, **kw)
        return jnp.where((jnp.arange(out.shape[0]) < sizes.sum())[:, None],
                         out, jnp.nan)

    monkeypatch.setattr(moe, "grouped_matmul", poisoned)
    n, hidden, experts, width, top_k = 40, 32, 8, 128, 3
    rng = np.random.default_rng(n + len(which))
    x, rx = (rng.standard_normal((n, hidden)).astype(np.float32)
             for _ in range(2))
    wr = rng.standard_normal((hidden, experts)).astype(np.float32)
    wgu = rng.standard_normal((experts, hidden, 2 * width)).astype(
        np.float32) * 0.3
    wd = rng.standard_normal((experts, width, hidden)).astype(
        np.float32) * 0.3
    valid = _valid_rows(which, n)
    operands = [jnp.asarray(a) for a in (x, rx, wr, wgu, wd)]
    kw = dict(top_k=top_k, activation=gate[0], limit=gate[1],
              valid=None if valid is None else jnp.asarray(valid),
              precision=highest)
    want = [np.asarray(a) for a in _parent_routed_tokens(*operands, **kw)]
    names = ("grouped_matmul_epilogue_gate", "grouped_matmul_epilogue_scale",
             "moe_combine_gather")
    before = [stat_get(c) for c in names]
    got = [np.asarray(a) for a in moe.moe_routed_tokens(*operands, **kw)]
    fused = int(route == "kernel")
    assert [stat_get(c) - b for c, b in zip(names, before)] \
        == [fused, fused, 1]
    live = np.ones(n, bool) if valid is None else valid
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    out, counts, _ = got
    assert np.isfinite(out).all() and not out[~live].any()
    assert not live.any() or np.abs(out[live]).min(axis=-1).max() > 0
    assert int(counts.sum()) == int(live.sum()) * top_k
    if route == "kernel":
        kernel.grouped_matmul.clear_cache()


def test_the_held_share_builds_no_epilogue_and_no_gather_sum():
    """``held_first`` is the parent's path, line for line: none of the
    three counters moves."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.parallel import moe

    names = ("grouped_matmul_epilogue_gate", "grouped_matmul_epilogue_scale",
             "moe_combine_gather")
    before = [stat_get(c) for c in names]
    S = jax.ShapeDtypeStruct
    jax.make_jaxpr(lambda x, r, gu, dn: moe.moe_routed_tokens(
        x, x, r, gu, dn, top_k=2, activation="silu", held_first=4,
        precision=jax.lax.Precision.HIGHEST))(
        S((32, 16), jnp.float32), S((16, 8), jnp.float32),
        S((2, 16, 256), jnp.float32), S((2, 128, 16), jnp.float32))
    assert [stat_get(c) for c in names] == before
