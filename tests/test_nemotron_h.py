"""Layers of ONE sublayer, state-space layers with more than one group of
B and C, and LatentMoE with experts of two matrices (PR 63,
``nemotron3-super-120b-a12b``).

* **Ops** (``ops/ssd_ops.py``, ``ops/pallas/ssd.py`` in interpret mode):
  the chunked scan and the step with 2 and 4 groups against the
  recurrence taken token by token and head by head in float64; one group
  given as ``[.., 1, N]`` is bit for bit the ungrouped call; the grouped
  gated norm (``rms_norm(group_size=)``).
* **Experts** (``parallel/moe.py``): experts of two matrices (the squared
  ReLU, and no other activation), through the held share and through the sorted route
  and its interpreted epilogue kernel; the shares of a layer add up.
* **Model** (``models/llama.py``): a mixer-only and an FFN-only layer
  (one norm, no cache of the kind it lacks), the LatentMoE layer whole,
  the whole toy model uncached and through the paged ``GenerationEngine``
  (a reused slot between live neighbours) against the benchmark's plain
  reference, the counters, and the refusals that remain.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.monitor import stat_get

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
# of the logits' range: nothing here rounds below float32 (the program
# and the reference read 4e-7 to 2e-6 at these sizes)
TOL = 2.0 ** -12


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "nemotron_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


NAME = "nemotron3-super-120b-a12b"
REF = _load("reference", NAME)
BUILDER = _load("builders", "nemotron_h_engine")


def _cfg(**over):
    """The configuration's file at its rehearsal's toy widths: five layers
    ``ME*EM``, 8 state-space heads of 16 over 16 state rows in 2 groups, 4
    query over 2 KV heads of 16, a router of 16 with 3 a token of which
    experts 4..7 are held, experts of 48 in a latent of 32, a shared
    expert of 96 at the full width of 64."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg.pop("rehearse"))
    cfg.update(over)
    return cfg


def _seed(scope, cfg, seed):
    BUILDER.seed_delta_gates(scope, cfg, seed)
    BUILDER.seed_expert_bias(scope, cfg, seed)
    # (the convolution's bias starts at zero: give it values)
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    channels = heads * p + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        if kind == "M":
            scope.set_var(f"llama.blk{i}.ssd_conv.b",
                          np.random.default_rng(seed + i).normal(
                              0, 0.3, channels).astype("float32"))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 97, n).tolist()


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


# ---------------------------------------------------------------------------
# ops: groups of B and C
# ---------------------------------------------------------------------------

H, P, N = 8, 64, 128         # a group of 4 (or 2) heads: whole lane tiles


def _operands(seed, B, T, G):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(B, T, H, P))
    dt = 5.0 * np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (B, T, H)))
    a = -rng.uniform(1.0, 16.0, H)
    bm, cm = rng.normal(size=(B, T, G, N)), rng.normal(size=(B, T, G, N))
    d = rng.normal(size=H)
    return [t.astype("float32") for t in (x, dt, a, bm, cm, d)]


def _recurrence(x, dt, a, bm, cm, d, s0=None, valid=None):
    """Token by token and head by head, in float64: head h reads group h
    // (H / G)'s B and C; the state ``[B, N, H P]``."""
    x, dt, a, bm, cm, d = (np.asarray(t, "float64")
                           for t in (x, dt, a, bm, cm, d))
    B, T = x.shape[:2]
    per = H // bm.shape[2]
    s = np.zeros((B, N, H, P)) if s0 is None \
        else np.asarray(s0, "float64").reshape(B, N, H, P).copy()
    out = np.zeros(x.shape)
    for b in range(B):
        for t in range(T if valid is None else int(valid[b])):
            for h in range(H):
                g = h // per
                s[b, :, h] = np.exp(dt[b, t, h] * a[h]) * s[b, :, h] \
                    + np.outer(bm[b, t, g], dt[b, t, h] * x[b, t, h])
                out[b, t, h] = cm[b, t, g] @ s[b, :, h] + d[h] * x[b, t, h]
    return out, s.reshape(B, N, H * P)


def _forms(chunk):
    from paddle_tpu.ops import ssd_ops
    from paddle_tpu.ops.pallas import ssd

    return {"xla": lambda *t, **kw: ssd_ops.chunked(*t, chunk=chunk, **kw),
            "kernel": lambda *t, **kw: ssd.chunk(
                *t, chunk=chunk, lanes_block=128, interpret=True, **kw)}


@pytest.mark.parametrize("valid", [[21, 40], [7, 32], None])
@pytest.mark.parametrize("groups", [2, 4])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_grouped_scan_is_the_recurrence(form, groups, valid):
    import jax

    ops = _operands(3, 2, 40, groups)
    s0 = np.random.default_rng(4).normal(
        size=(2, N, H * P)).astype("float32")
    if valid is not None:
        # whatever lies behind ``valid`` reaches nothing
        for b, n in enumerate(valid):
            for t in (ops[0], ops[1], ops[3], ops[4]):
                t[b, n:] = np.nan
    want_y, want_s = _recurrence(*[np.nan_to_num(t) for t in ops], s0=s0,
                                 valid=valid)
    v = None if valid is None else np.asarray(valid, "int32")
    with jax.default_matmul_precision("highest"):
        y, s = _forms(16)[form](*ops, s0=s0, valid=v)
    assert np.isfinite(np.asarray(y)).all()
    _close(s, want_s)
    for b in range(2):
        n = 40 if valid is None else valid[b]
        _close(np.asarray(y)[b, :n], want_y[b, :n])
        assert not np.asarray(y)[b, n:].any()      # pad rows read zero


@pytest.mark.parametrize("groups", [2, 4])
def test_grouped_recurrence_of_the_ops_module_is_the_float64_one(groups):
    from paddle_tpu.ops import ssd_ops

    ops = _operands(6, 2, 19, groups)
    valid = np.asarray([19, 11], "int32")
    y, s = ssd_ops.recurrence(*ops, valid=valid)
    want_y, want_s = _recurrence(*ops, valid=valid)
    _close(s, want_s)
    _close(np.asarray(y)[1, :11], want_y[1, :11])
    _close(np.asarray(y)[0], want_y[0])


@pytest.mark.parametrize("groups", [2, 4])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_grouped_step_is_one_token_and_moves_live_rows_only(form, groups):
    import jax.numpy as jnp

    from paddle_tpu.ops import ssd_ops
    from paddle_tpu.ops.pallas import ssd

    n = 5
    x, dt, a, bm, cm, d = _operands(7, n, 1, groups)
    state = np.random.default_rng(8).normal(
        size=(n + 1, N, H * P)).astype("float32")
    live = np.asarray([1, 0, 1, 1, 0], "int32")
    row = (x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d)
    if form == "xla":
        y, new = ssd_ops.step(*row, jnp.asarray(state), live.astype(bool))
    else:
        y, new = ssd.step(*row, jnp.asarray(state), jnp.asarray(live),
                          interpret=True, lanes_block=128)
    want_y, want_s = _recurrence(x, dt, a, bm, cm, d, s0=state[:n])
    new = np.asarray(new)
    for i in range(n):
        if live[i]:
            _close(new[i], want_s[i])
            _close(np.asarray(y)[i], want_y[i, 0])
        else:
            assert np.array_equal(new[i], state[i])
    assert np.array_equal(new[n], state[n])


@pytest.mark.parametrize("what", ["chunk", "step"])
def test_one_group_is_the_kernel_of_before_bit_for_bit(what):
    """B and C of ``[.., 1, N]`` run the kernels' one-group bodies: the
    same numbers, to the bit, as B and C of ``[.., N]``."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import ssd

    x, dt, a, bm, cm, d = _operands(9, 2, 32, 1)
    if what == "chunk":
        one = ssd.chunk(x, dt, a, bm[:, :, 0], cm[:, :, 0], d, chunk=16,
                        interpret=True)
        grouped = ssd.chunk(x, dt, a, bm, cm, d, chunk=16, interpret=True)
    else:
        state = jnp.asarray(np.random.default_rng(1).normal(
            size=(3, N, H * P)).astype("float32"))
        live = jnp.asarray([1, 1], jnp.int32)
        one = ssd.step(x[:, 0], dt[:, 0], a, bm[:, 0, 0], cm[:, 0, 0], d,
                       state, live, interpret=True)
        grouped = ssd.step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d,
                           state, live, interpret=True)
    for got, want in zip(grouped, one):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_kernels_say_what_they_take_of_groups():
    from paddle_tpu.ops.pallas import ssd

    # the cell's: 128 heads of 64 in 8 groups, a group 1024 lanes
    assert ssd.chunk_supported((1, 512, 128, 64), 128, 128, 8)
    assert ssd.step_supported((129, 128, 8192), 8)
    # a group that is no whole lane tile, state rows that are no tile of
    # lanes (a group's B is a block of lanes): the XLA form runs
    assert not ssd.chunk_supported((1, 64, 8, 16), 128, 16, 2)
    assert not ssd.step_supported((3, 128, 8 * 16), 2)
    assert not ssd.chunk_supported((1, 64, 8, 64), 16, 16, 2)
    assert not ssd.step_supported((3, 16, 8 * 64), 2)
    assert ssd.step_supported((3, 16, 8 * 64))
    # a lane block lies inside one group
    assert ssd._lane_block(1024, 8192 // 8) == 1024
    assert ssd._lane_block(1024, 2048 // 8) == 256


def test_groups_that_do_not_divide_the_heads_are_refused():
    from paddle_tpu.models.llama import _ssd_dims, cache_spec

    mixer = {"kind": "ssd", "heads": 8, "head_dim": 16, "state": 16,
             "groups": 2, "conv": 4}
    assert _ssd_dims(mixer) == (8, 16, 16, 8 * 16 + 2 * 2 * 16, 2)
    with pytest.raises(ValueError, match="groups of B and C"):
        cache_spec("llama", 1, [{"mixer": dict(mixer, groups=3)}],
                   num_slots=2, num_pages=4, page_tokens=PAGE,
                   num_kv_heads=2, head_dim=16, hidden=64)


@pytest.mark.parametrize("group", [None, 4, 12])
def test_gated_norm_over_each_groups_channels_apart(group):
    from paddle_tpu import layers

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        x = layers.data("x", [2, 3, 12], dtype="float32",
                        append_batch_size=False)
        y = layers.rms_norm(x, epsilon=1e-5, param_attr="w",
                            group_size=group)
    op, = [o for o in main.global_block().ops if o.type == "rms_norm"]
    # a group that is the whole row is the norm of before: no attribute
    assert (op.attr("group_size", None) is not None) == (group == 4)
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    w = np.random.default_rng(0).normal(size=12).astype("float32")
    scope.set_var("w", w)
    xs = np.random.default_rng(1).normal(size=(2, 3, 12)).astype("float32")
    got, = exe.run(main, feed={"x": xs}, fetch_list=[y], scope=scope)
    g = xs.reshape(2, 3, -1, group or 12).astype("float64")
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)) \
        .reshape(2, 3, 12) * w
    _close(got, want, 1e-6)
    with pytest.raises(ValueError, match="do not divide"):
        layers.rms_norm(x, group_size=5)


# ---------------------------------------------------------------------------
# experts of two matrices, in a latent row
# ---------------------------------------------------------------------------

def _experts(seed, E=8, hidden=32, latent=16, inter=24, n=40):
    rng = np.random.default_rng(seed)
    return dict(
        h=rng.normal(size=(n, hidden)).astype("float32"),
        u=rng.normal(size=(n, latent)).astype("float32"),
        router=rng.normal(size=(hidden, E)).astype("float32"),
        bias=(0.3 * rng.normal(size=E)).astype("float32"),
        up=(0.3 * rng.normal(size=(E, latent, inter))).astype("float32"),
        down=(0.3 * rng.normal(size=(E, inter, latent))).astype("float32"))


def _dense_experts(t, top_k, first=0, count=None, scale=2.5):
    """Every token through its chosen experts among ``first .. first +
    count - 1``, one at a time, ``W2 relu(W1 u)^2`` in float64."""
    E = t["router"].shape[1]
    count = E if count is None else count
    s = 1 / (1 + np.exp(-(t["h"].astype("float64") @ t["router"])))
    chosen = np.argsort(-(s + t["bias"]), axis=-1, kind="stable")[:, :top_k]
    out = np.zeros(t["u"].shape)
    for i, row in enumerate(chosen):
        total = s[i, row].sum() + 1e-6
        for e in row:
            if first <= e < first + count:
                out[i] += scale * s[i, e] / total * (
                    np.maximum(t["u"][i].astype("float64") @ t["up"][e],
                               0) ** 2 @ t["down"][e])
    return out


def _routed(t, held, activation):
    import jax

    from paddle_tpu.parallel.moe import moe_routed_tokens

    first, count = held or (0, 8)
    return moe_routed_tokens(
        t["u"], t["h"], t["router"], t["up"][first:first + count],
        t["down"][first:first + count], top_k=3, activation=activation,
        score="sigmoid", expert_bias=t["bias"], route_scale=2.5,
        precision=jax.lax.Precision.HIGHEST,
        held_first=None if held is None else first)


@pytest.mark.parametrize("held", [None, (2, 3)])
def test_experts_of_two_matrices_are_the_dense_loop(held):
    """``moe_routed_tokens`` tells experts without a gate by their first
    stack's width, [E, H, I]: through the sorted route (``held`` None) and
    through a held share, the router reading a row WIDER than the
    experts'."""
    t = _experts(1)
    out, counts, logits = _routed(t, held, "relu2")
    assert out.shape == t["u"].shape and logits.shape == (40, 8)
    assert int(counts.sum()) == 40 * 3
    _close(out, _dense_experts(t, 3, *(held or (0, 8))), 1e-5)


@pytest.mark.parametrize("activation", ["relu", "silu"])
@pytest.mark.parametrize("held", [None, (2, 3)])
def test_experts_without_a_gate_take_the_squared_relu_alone(activation,
                                                            held):
    """No model has two-matrix experts under another activation, so none
    is built: both routes say so."""
    with pytest.raises(ValueError, match="take the activation 'relu2'"):
        _routed(_experts(1), held, activation)


def test_a_clamp_is_the_gated_experts_alone():
    import jax

    from paddle_tpu.parallel.moe import _acted, moe_routed_tokens

    t = _experts(2)
    with pytest.raises(ValueError, match="clamp"):
        moe_routed_tokens(t["u"], t["h"], t["router"], t["up"], t["down"],
                          top_k=3, activation="relu2", limit=7.0,
                          precision=jax.lax.Precision.HIGHEST)
    with pytest.raises(ValueError, match="take the activation 'relu2'"):
        _acted(t["u"], 16, "gelu")
    # the gated form is the one of before, by the first product's width
    h = np.random.default_rng(0).normal(size=(4, 32)).astype("float32")
    assert np.allclose(_acted(h, 16, "relu"),
                       np.maximum(h[:, :16], 0) * h[:, 16:])


@pytest.mark.parametrize("scaled", [False, True])
def test_the_activation_rides_the_interpreted_kernel(scaled):
    """The epilogue kernel with an activation alone keeps the product's
    width under any column block, and carries the routing weight beside
    it as it does beside a gate."""
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import grouped_matmul as kernel
    from paddle_tpu.parallel import moe

    rng = np.random.default_rng(3)
    rows = rng.normal(size=(192, 128)).astype("float32")
    w = (0.2 * rng.normal(size=(4, 128, 256))).astype("float32")
    sizes = np.asarray([70, 0, 100, 22], "int32")
    scale = rng.uniform(0.1, 1, 192).astype("float32") if scaled else None
    got = kernel.grouped_matmul_epilogue(
        jnp.asarray(rows), jnp.asarray(w), jnp.asarray(sizes),
        None if scale is None else jnp.asarray(scale), tm=64, tn=128,
        act=functools.partial(moe._activation, activation="relu2"),
        interpret=True)
    group = np.repeat(np.arange(4), sizes)
    want = np.maximum(np.einsum("mk,mkn->mn", rows.astype("float64"),
                                w[group]), 0) ** 2
    if scaled:
        want = want * scale[:, None]
    assert got.shape == (192, 256)
    _close(got, want, 1e-5)
    # ... and ``grouped_matmul`` asks for it where its products are the
    # kernel: the counter of the epilogues built
    n0 = stat_get("grouped_matmul_epilogue_act")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        jax.make_jaxpr(lambda r, ww, s: moe.grouped_matmul(
            r, ww, s, jax.lax.Precision.HIGHEST, act="relu2"))(
                rows, w, sizes)
    assert stat_get("grouped_matmul_epilogue_act") == n0 + 1


def test_the_shares_add_up():
    """Sixteen chips, two experts each of a router of 32: the shares'
    ``r`` summed and taken up through ``W_up`` once, plus the shared
    expert once, is the uncut layer of the plain reference; and each share
    through ``W_up`` alone adds up to the same (``W_up`` is linear)."""
    import jax

    from paddle_tpu.parallel.moe import moe_routed_tokens

    cfg = _cfg(n_routed_experts=32, num_experts_per_tok=6,
               expert_share={"router_experts": 32, "first": 0})
    rng = np.random.default_rng(5)
    hidden, latent, inter, shared = 64, 32, 48, 96
    p = {"router": rng.normal(size=(hidden, 32)),
         "bias": 0.2 * rng.normal(size=32),
         "lat_down": 0.2 * rng.normal(size=(hidden, latent)),
         "up": 0.3 * rng.normal(size=(32, latent, inter)),
         "down": 0.3 * rng.normal(size=(32, inter, latent)),
         "lat_up": 0.2 * rng.normal(size=(latent, hidden)),
         "shared_up": 0.2 * rng.normal(size=(hidden, shared)),
         "shared_down": 0.2 * rng.normal(size=(shared, hidden))}
    p = {k: v.astype("float32") for k, v in p.items()}
    h = rng.normal(size=(24, hidden)).astype("float32")
    with jax.default_matmul_precision("highest"):
        whole, _, _ = REF.latent_moe(h, p, cfg, (0, 32))
        u = h @ p["lat_down"]
        shares = [moe_routed_tokens(
            u, h, p["router"], p["up"][f:f + 2], p["down"][f:f + 2],
            top_k=6, activation="relu2", score="sigmoid",
            expert_bias=p["bias"], route_scale=5.0, held_first=f,
            precision=jax.lax.Precision.HIGHEST)[0] for f in range(0, 32, 2)]
        shared_once = REF._relu2_mlp(h, p["shared_up"], p["shared_down"])
        summed = sum(shares) @ p["lat_up"] + shared_once
        apart = sum(r @ p["lat_up"] for r in shares) + shared_once
        # the reference's own shares add up too
        ref_shares = sum(REF.latent_moe(
            h, dict(p, up=p["up"][f:f + 2], down=p["down"][f:f + 2]), cfg,
            (f, 2), shared=f == 0)[0] for f in range(0, 32, 2))
    _close(summed, whole, 1e-5)
    _close(apart, whole, 1e-5)
    _close(ref_shares, whole, 1e-5)
    # no share is the whole: every one adds something
    assert all(float(np.abs(np.asarray(r)).max()) > 0 for r in shares)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def test_the_builder_reads_the_published_keys():
    cfg = _cfg()
    BUILDER.require_program()
    model = BUILDER.model_args(cfg)
    m, e, a = (model["layer_pattern"][i] for i in (0, 1, 2))
    assert [bool(x["mixer"]) for x in model["layer_pattern"]] \
        == [True, False, True, False, True]
    assert m["ffn"] is None and a["ffn"] is None and e["mixer"] is None
    assert m["mixer"] == {"kind": "ssd", "heads": 8, "head_dim": 16,
                          "state": 16, "groups": 2, "conv": 4,
                          "conv_bias": True}
    assert a["mixer"] == "attention" and a["rope"] is False
    assert e["ffn"] == {
        "experts": 16, "held": (4, 4), "top_k": 3, "width": 48,
        "latent": 32, "activation": "relu2", "gated": False,
        "route_from": "normed", "score": "sigmoid", "expert_bias": True,
        "norm_topk": True, "route_scale": 5.0, "shared_width": 96}
    assert (model["head_dim"], model["num_kv_heads"], model["tie_head"],
            model["rms_norm_eps"]) == (16, 2, False, 1e-5)
    with pytest.raises(ValueError, match="letters M"):
        BUILDER.layer_pattern(dict(cfg, hybrid_override_pattern="ME-EM"))


@pytest.mark.parametrize("lacking", ["a layer of one sublayer",
                                     "the latent pair",
                                     "experts of two matrices"])
def test_the_builder_refuses_a_program_without_a_mechanism(monkeypatch,
                                                           lacking):
    """``require_program`` asks what the program BUILDS for a toy of the
    mechanisms, not what its source says: a program that gives a layer two
    norms, builds no latent pair or keeps the gate matrix is refused, with
    what it built in the message."""
    import importlib

    llama = importlib.import_module("paddle_tpu.models.llama")
    build = llama.build_llama_forward

    def without(*args, layer_pattern, **kw):
        e, m = (dict(x) for x in layer_pattern)
        if lacking == "a layer of one sublayer":
            m["ffn"] = "dense"                 # (a second half, and a norm)
            kw["intermediate"] = 64
        else:
            e["ffn"] = {k: v for k, v in e["ffn"].items()
                        if k != ("latent" if lacking == "the latent pair"
                                 else "gated")}
            if lacking != "the latent pair":
                e["ffn"]["activation"] = "relu"
        return build(*args, layer_pattern=[e, m], **kw)

    monkeypatch.setattr(llama, "build_llama_forward", without)
    with pytest.raises(SystemExit, match="cannot run .* build "):
        BUILDER.require_program()


def test_a_layer_keeps_no_cache_of_the_kind_it_lacks():
    from paddle_tpu.models.llama import (cache_spec, expert_layers,
                                         state_layers, window_layers)

    cfg = _cfg()
    pattern = BUILDER.layer_pattern(cfg)
    assert state_layers(pattern, 5) == [0, 4]
    assert expert_layers(pattern, 5) == [1, 3]
    assert window_layers(pattern, 5) == []
    spec = cache_spec("llama", 5, pattern, num_slots=3, num_pages=9,
                      page_tokens=PAGE, num_kv_heads=2, head_dim=16,
                      hidden=64)
    assert [(e["layer"], e["kind"]) for e in spec] == [
        (0, "slot_state"), (0, "slot_state"), (2, "pages"), (2, "pages"),
        (4, "slot_state"), (4, "slot_state")]
    # x | B | C of two groups; the state's lanes are every head's
    assert spec[0]["shape"] == [4, 3, 8 * 16 + 2 * 2 * 16]
    assert spec[1]["shape"] == [4, 16, 8 * 16]
    both = dict(pattern[0], ffn=None, mixer=None)
    with pytest.raises(ValueError, match="one sublayer"):
        _uncached(cfg, dict(BUILDER.model_args(cfg), layer_pattern=[both],
                            num_layers=1))
    with pytest.raises(ValueError, match="one sublayer"):
        _uncached(cfg, dict(BUILDER.model_args(cfg), norm="post"))


def _uncached(cfg, model=None, seed=3, S=70):
    """``build_llama_forward``'s logits [2, S, V] on seeded weights, the
    scope, and the reference's parameters of it."""
    from paddle_tpu.models.llama import build_llama_forward

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        _, fetches = build_llama_forward(
            2, S, name="llama", attn_impl="xla",
            **(model or BUILDER.model_args(cfg)))
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    _seed(scope, cfg, seed)
    ids = np.random.default_rng(0).integers(1, 97, (2, S))
    logits, = exe.run(main, feed={"input_ids": ids.astype("int64")},
                      fetch_list=[fetches["logits"]], scope=scope)
    return ids, np.asarray(logits), scope


def _off(logits, want):
    return float(np.abs(logits - want).max() / np.abs(want).max())


def test_uncached_forward_is_the_reference():
    cfg = _cfg()
    ids, logits, scope = _uncached(cfg)
    params = REF.params_from_scope(scope, cfg, "llama")
    for b in range(2):
        want = np.asarray(REF.forward(params, ids[b].astype("int32"), cfg))
        assert _off(logits[b], want) < TOL
    # one norm a layer, and only the sublayer's own parameters
    names = set(scope.local_var_names())
    assert not [n for n in names if ".ln2" in n]
    assert all(f"llama.blk{i}.ln1" in names for i in range(5))
    assert not [n for n in names if n.startswith("llama.blk1.")
                and ".moe." not in n and not n.endswith(".ln1")]
    assert scope.find_var("llama.blk1.moe.router.w").shape == (64, 16)
    assert scope.find_var("llama.blk1.moe.up.w").shape == (4, 32, 48)
    assert scope.find_var("llama.blk1.moe.down.w").shape == (4, 48, 32)
    assert scope.find_var("llama.blk1.moe.shared_up.w").shape == (64, 96)
    assert scope.find_var("llama.blk0.ssd_in.w").shape \
        == (64, 128 + 128 + 64 + 8)


@pytest.mark.parametrize("pattern", ["M", "*", "E", "EE", "MM"])
def test_a_layer_of_one_sublayer_is_the_reference(pattern):
    """A mixer-only layer (state-space, attention) and an FFN-only layer,
    each alone in a model, and two of a kind behind each other."""
    cfg = _cfg(hybrid_override_pattern=pattern,
               num_hidden_layers=len(pattern))
    ids, logits, scope = _uncached(cfg, S=40)
    params = REF.params_from_scope(scope, cfg, "llama")
    want = np.asarray(REF.forward(params, ids[0].astype("int32"), cfg))
    assert _off(logits[0], want) < TOL


def test_the_latent_layer_whole_is_the_reference():
    """The uncut layer: every one of the router's 16 experts held."""
    cfg = _cfg(hybrid_override_pattern="E", num_hidden_layers=1,
               n_routed_experts=16,
               expert_share={"router_experts": 16, "first": 0})
    ids, logits, scope = _uncached(cfg, S=40)
    params = REF.params_from_scope(scope, cfg, "llama")
    assert params["layers"][0]["up"].shape == (16, 32, 48)
    want = np.asarray(REF.forward(params, ids[1].astype("int32"), cfg))
    assert _off(logits[1], want) < TOL
    # what is left out is seen: the held share alone is not the layer
    part = np.asarray(REF.forward(
        dict(params, blocks=[dict(params["blocks"][0],
                                  up=params["blocks"][0]["up"][:4],
                                  down=params["blocks"][0]["down"][:4])]),
        ids[1].astype("int32"), cfg, held=(0, 4)))
    assert _off(logits[1], part) > 16 * TOL


@pytest.mark.parametrize("left_out", ["groups", "group_norm", "latent",
                                      "shared"])
def test_a_mechanism_left_out_is_not_the_reference(left_out):
    """Each of what this model adds reaches the program: built without
    one, it is off the reference by far more than the tolerance."""
    cfg = _cfg()
    model = BUILDER.model_args(cfg)
    pattern = [dict(e) for e in model["layer_pattern"]]
    if left_out == "groups":
        # both groups read group 0's B and C: the same parameter shapes
        import jax.numpy as jnp

        from paddle_tpu.ops import ssd_ops

        real = ssd_ops.chunked

        def first_group(x, dt, a, bm, cm, d, *rest, **kw):
            if bm.ndim > dt.ndim:
                bm = jnp.broadcast_to(bm[:, :, :1], bm.shape)
                cm = jnp.broadcast_to(cm[:, :, :1], cm.shape)
            return real(x, dt, a, bm, cm, d, *rest, **kw)

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(ssd_ops, "chunked", first_group)
            ids, logits, scope = _uncached(cfg, model)
    elif left_out == "group_norm":
        from paddle_tpu import layers

        real = layers.rms_norm
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(layers, "rms_norm", lambda *a, group_size=None, **kw:
                       real(*a, **kw))
            ids, logits, scope = _uncached(cfg, model)
    else:
        for e in pattern:
            if e["ffn"] and left_out == "shared":
                e["ffn"] = {k: v for k, v in e["ffn"].items()
                            if k != "shared_width"}
        if left_out == "latent":
            # the experts at full width: other shapes, so the reference
            # cannot read them; compare with the program as published
            for e in pattern:
                if e["ffn"]:
                    e["ffn"] = {k: v for k, v in e["ffn"].items()
                                if k != "latent"}
            _, want, _ = _uncached(cfg, model)
            ids, logits, scope = _uncached(
                cfg, dict(model, layer_pattern=pattern))
            assert _off(logits, want) > 16 * TOL
            return
        ids, logits, scope = _uncached(cfg, dict(model,
                                                 layer_pattern=pattern))
        if left_out == "shared":
            _, want, _ = _uncached(cfg, model)
            assert _off(logits, want) > 16 * TOL
            return
    params = REF.params_from_scope(scope, cfg, "llama")
    want = np.asarray(REF.forward(params, ids[0].astype("int32"), cfg))
    assert _off(logits[0], want) > 16 * TOL


def _engine(cfg, seed=11, **kw):
    from paddle_tpu.serving import GenerationEngine

    args = dict(num_slots=3, max_seq_len=256,
                prefill_buckets=[8, 32, 192], page_tokens=PAGE,
                attn_impl="xla", keep_logits=True, prefill_chunk=0,
                prefix_reuse=False, speculate=False, eos_id=-1,
                deadline_ms=600000)
    args.update(kw)
    eng = GenerationEngine(BUILDER.model_args(cfg), **args)
    _seed(eng.scope, cfg, seed)
    return eng


@pytest.fixture(scope="module")
def served():
    """Slots 0 and 1 decode all the while; slot 2 serves a request, is
    left, and takes the compared ones."""
    cfg = _cfg()
    eng = _engine(cfg)
    before = {k: stat_get(k) for k in (
        "serving_slot_state_writes", "serving_ssm_state_steps",
        "moe_shared_expert_rows", "ssd_lowered_reference")}
    spans = []
    try:
        sides = [eng.submit(_prompt(50 + i, 9 + i), 60) for i in range(2)]
        first = eng.submit(_prompt(52, 30), 6).result(300)
        res = {}
        for n in (5, 150):
            prompt = _prompt(60 + n, n)
            res[n] = (prompt, eng.generate(prompt, 9, timeout=300))
        rest = [f.result(300) for f in sides]
        counters = eng.stats()["counters"]
    finally:
        eng.close()
    return dict(cfg=cfg, eng=eng, first=first, res=res, rest=rest,
                counters=counters, before=before, spans=spans)


def _off_reference(served, prompt, res):
    """How far a result's logits lie off the reference's full forward
    over prompt plus generated tokens (its near-tie rule handed the
    program's router logits), as a share of its range."""
    cfg, eng = served["cfg"], served["eng"]
    n, new = len(prompt), len(res["tokens"])
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    seq = np.asarray(prompt + res["tokens"], "int32")
    prog = np.stack(res["router_logits"]).astype("float32")
    assert prog.shape == (new, 2, 16)           # the two expert layers
    want, report = REF.forward(params, seq, cfg,
                               np.arange(n - 1, n - 1 + new),
                               program_router=prog)
    got = np.stack(res["logits"])
    assert got.shape == np.asarray(want).shape and np.isfinite(got).all()
    # the program's router scores are the reference's
    assert float(np.asarray(report)[:, 0].max()) < 1e-5
    return _off(got, np.asarray(want))


def test_prefill_then_cached_decode_in_a_reused_slot_between_neighbours(
        served):
    assert served["first"]["slot"] == 2
    assert [r["slot"] for r in served["rest"]] == [0, 1]
    assert all(len(r["tokens"]) == 60 for r in served["rest"])
    for prompt, r in served["res"].values():
        assert r["slot"] == 2 and len(r["tokens"]) == 9
        assert _off_reference(served, prompt, r) < TOL


def test_the_counters_count_the_layers_that_have_state_or_experts(served):
    c, b = served["counters"], served["before"]
    assert c["slot_state_writes"] == 5
    assert stat_get("serving_slot_state_writes") \
        == b["serving_slot_state_writes"] + 5
    # every rider of every step moved TWO layers' states on (of five)
    assert c["ssm_state_steps"] % 2 == 0
    assert c["ssm_state_steps"] >= 2 * (2 * 59 + 5 + 8 + 8)
    assert c["delta_state_steps"] == 0
    assert stat_get("serving_ssm_state_steps") \
        == b["serving_ssm_state_steps"] + c["ssm_state_steps"]
    # two expert layers: 3 pairs a real row a layer, none dropped, a
    # quarter of the router's experts held here; the shared expert ran on
    # every routed row
    assert c["moe_tokens_dropped"] == 0
    assert c["moe_pairs_routed"] == c["moe_tokens_routed"]
    assert c["moe_pairs_routed"] % (2 * 3) == 0
    assert 0 < c["moe_pairs_held"] < c["moe_pairs_routed"]
    assert c["moe_shared_expert_rows"] * 3 == c["moe_pairs_routed"]
    assert stat_get("ssd_lowered_reference") > b["ssd_lowered_reference"]


@pytest.mark.parametrize("kw,reason", [
    ({"prefill_chunk": 32}, "prefill_chunk"),
    ({"prefix_reuse": True}, "prefix_reuse"),
    ({"speculate": True}, "speculate")])
def test_what_walks_pages_only_is_still_refused(kw, reason):
    with pytest.raises(ValueError, match=reason):
        _engine(_cfg(), **kw)


def test_the_chunk_program_is_still_refused_over_slot_state():
    from paddle_tpu.models.llama import build_llama_prefill_chunk

    cfg = _cfg()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pytest.raises(ValueError, match="slot state"):
            build_llama_prefill_chunk(32, 256, 97, PAGE, name="llama",
                                      **BUILDER.model_args(cfg))


def test_the_chunk_program_takes_a_layer_that_is_an_ffn_alone():
    """Without slot state the continuation programs are built: an FFN-only
    layer has no pool and takes none."""
    from paddle_tpu.models.llama import build_llama_prefill_chunk

    cfg = _cfg(hybrid_override_pattern="*E*", num_hidden_layers=3)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        feeds, fetches, caches = build_llama_prefill_chunk(
            32, 256, 97, PAGE, name="llama", **BUILDER.model_args(cfg))
    assert caches == ["llama.pool_k_0", "llama.pool_v_0",
                      "llama.pool_k_2", "llama.pool_v_2"]
    assert "expert_counts" in fetches
