"""The gated delta rule (PR 41): a matrix of state a head a slot.

* **Ops** (``ops/gated_delta_ops.py``): the chunked op against the
  recurrence taken token by token (whole and broken chunks, ``beta`` up to
  1 and up to 2, an initial state); two calls that carry the state against
  one; rows behind ``valid`` (a NaN planted there) reach neither outputs,
  state nor convolution tail; the step moves ``live`` rows only, in place,
  and the trash row takes a warm-up's write.
* **Kernels** (``ops/pallas/gated_delta.py``, interpret mode) against the
  XLA formulations.
* **Model** (``models/llama.py``): ``mixer: gated_delta``, ``norm: "post"``
  and ``qk_norm: "proj"`` against the benchmark's plain reference, uncached
  and through the paged ``GenerationEngine`` (a reused slot between live
  neighbours), ``cache_spec``'s two states a layer, the refusals, spans and
  counters, and planted faults that must NOT pass.
"""
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.monitor import stat_get

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
TOL = 2.0 ** -10          # of the logits' range; float32 reads 1e-5 here


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "olmo_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "olmo-hybrid-7b")
BUILDER = _load("builders", "olmo_hybrid_engine")


def _cfg(**over):
    """The published keys at a toy size: hidden 64, three delta layers of 4
    heads (keys of 8, values of 16, 4 taps) and one of full attention of 4
    heads of 16 without rotary embedding."""
    cfg = {"vocab_size": 97, "hidden_size": 64, "intermediate_size": 96,
           "num_hidden_layers": 4,
           "layer_types": ["linear_attention"] * 3 + ["full_attention"],
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
           "linear_num_key_heads": 4, "linear_num_value_heads": 4,
           "linear_key_head_dim": 8, "linear_value_head_dim": 16,
           "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
           "rope_parameters": {"rope_theta": None},
           "as_run": {"attention_precision": "highest"},
           "assumed": {"qk_norm": "proj", "norm": "post", "eos_id": -1}}
    cfg.update(over)
    return cfg


def _engine(cfg=None, seed=11, **kw):
    from paddle_tpu.serving import GenerationEngine

    cfg = cfg or _cfg()
    args = dict(num_slots=3, max_seq_len=256,
                prefill_buckets=[8, 32, 192], page_tokens=PAGE,
                attn_impl="xla", keep_logits=True, prefill_chunk=0,
                prefix_reuse=False, speculate=False, eos_id=-1,
                deadline_ms=600000)
    args.update(kw)
    eng = GenerationEngine(BUILDER.model_args(cfg), **args)
    if "scope" not in kw:
        BUILDER.seed_delta_gates(eng.scope, cfg, seed)
    return eng


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 97, n).tolist()


def _off_reference(eng, cfg, prompt, res):
    """How far a result's logits lie off the reference's full forward
    over prompt plus generated tokens, as a share of its range."""
    n, new = len(prompt), len(res["tokens"])
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    seq = np.asarray(prompt + res["tokens"], "int32")
    want = np.asarray(REF.forward(params, seq, cfg,
                                  np.arange(n - 1, n - 1 + new)))
    got = np.stack(res["logits"])
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.abs(got - want).max() / np.abs(want).max())


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def _operands(seed, B, T, H=3, Dk=8, Dv=12, beta_max=2.0, decay=2.0):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(B, T, H, Dk))) * Dk ** -0.5
    k = unit(rng.normal(size=(B, T, H, Dk)))
    v = rng.normal(size=(B, T, H, Dv))
    g = -decay * np.abs(rng.normal(size=(B, T, H)))
    beta = beta_max / (1.0 + np.exp(-rng.normal(size=(B, T, H))))
    return [x.astype("float32") for x in (q, k, v, g, beta)]


def _recurrence(q, k, v, g, beta, s0=None, valid=None):
    """Token by token, in float64."""
    q, k, v, g, beta = (np.asarray(x, "float64") for x in (q, k, v, g, beta))
    B, T, H, Dk = q.shape
    s = np.zeros((B, H, Dk, v.shape[-1])) if s0 is None \
        else np.asarray(s0, "float64").copy()
    out = np.zeros(v.shape)
    for b in range(B):
        for t in range(T if valid is None else int(valid[b])):
            for h in range(H):
                sd = np.exp(g[b, t, h]) * s[b, h]
                r = v[b, t, h] - sd.T @ k[b, t, h]
                s[b, h] = sd + np.outer(k[b, t, h], beta[b, t, h] * r)
                out[b, t, h] = s[b, h].T @ q[b, t, h]
    return out, s


def _chunk_program(B, T, H=3, Dk=8, Dv=12, state0=False, valid=False):
    """``(feeds, [out, state])`` of one ``gated_delta_chunk``."""
    def data(name, shape, dtype="float32"):
        return layers.data(name, shape, dtype=dtype, append_batch_size=False)

    q, k = data("q", [B, T, H, Dk]), data("k", [B, T, H, Dk])
    v = data("v", [B, T, H, Dv])
    g, beta = data("g", [B, T, H]), data("beta", [B, T, H])
    kw = {}
    if state0:
        kw["state0"] = data("s0", [B, H, Dk, Dv])
    if valid:
        kw["valid"] = data("valid", [B], "int32")
    return list(layers.gated_delta_chunk(q, k, v, g, beta, **kw))


def _run(build, feed, scope=None):
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        fetches = build()
    exe = pt.Executor()
    scope = scope or pt.Scope()
    exe.run(startup, scope=scope)
    return exe.run(main, feed=feed, fetch_list=fetches, scope=scope)


@pytest.mark.parametrize("T,beta_max,state0", [
    (64, 2.0, False), (128, 1.0, False), (150, 2.0, True), (5, 2.0, True),
    (65, 1.0, False)])
def test_chunked_op_is_the_recurrence(T, beta_max, state0):
    """Whole chunks and broken ones, one token more than a chunk and fewer
    than one; ``beta`` up to 1 (``neg_eigval`` off) and up to 2; from zero
    and from a state."""
    B = 2
    q, k, v, g, beta = _operands(T, B, T, beta_max=beta_max)
    feed = dict(q=q, k=k, v=v, g=g, beta=beta)
    s0 = None
    if state0:
        s0 = np.random.default_rng(1).normal(size=(B, 3, 8, 12)) \
            .astype("float32")
        feed["s0"] = s0
    out, state = _run(lambda: _chunk_program(B, T, state0=state0), feed)
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    assert out.shape == (B, T, 3, 12) and state.shape == (B, 3, 8, 12)
    np.testing.assert_allclose(out, want_o, rtol=0, atol=2e-5)
    np.testing.assert_allclose(state, want_s, rtol=0, atol=2e-5)


def test_strong_decay_inside_a_chunk_neither_overflows_nor_drifts():
    """Log decay of -12 a token: 64 tokens reach exp(-768), and the
    decay between two tokens is formed from the difference of the sums."""
    q, k, v, g, beta = _operands(3, 1, 128, decay=12.0)
    out, state = _run(lambda: _chunk_program(1, 128),
                      dict(q=q, k=k, v=v, g=g, beta=beta))
    want_o, want_s = _recurrence(q, k, v, g, beta)
    assert np.isfinite(out).all() and np.isfinite(state).all()
    np.testing.assert_allclose(out, want_o, rtol=0, atol=2e-5)
    np.testing.assert_allclose(state, want_s, rtol=0, atol=2e-5)


def test_two_calls_that_carry_the_state_are_one_call():
    B, T, cut = 1, 200, 77
    ops = _operands(8, B, T)
    names = ("q", "k", "v", "g", "beta")
    whole_o, whole_s = _run(lambda: _chunk_program(B, T),
                            dict(zip(names, ops)))
    o1, s1 = _run(lambda: _chunk_program(B, cut),
                  {n: x[:, :cut] for n, x in zip(names, ops)})
    o2, s2 = _run(lambda: _chunk_program(B, T - cut, state0=True),
                  dict({n: x[:, cut:] for n, x in zip(names, ops)}, s0=s1))
    np.testing.assert_allclose(np.concatenate([o1, o2], 1), whole_o,
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(s2, whole_s, rtol=0, atol=2e-5)


@pytest.mark.parametrize("n", [1, 63, 64, 70])
def test_rows_behind_valid_reach_nothing(n):
    """A prompt of ``n`` tokens in a rung of 128 whose pad rows hold NaN
    in every operand: the real rows' outputs and the state are those of
    the ``n`` tokens alone, and nothing is NaN."""
    T = 128
    ops = _operands(n, 1, T)
    padded = [x.copy() for x in ops]
    for x in padded:
        x[:, n:] = np.nan
    out, state = _run(lambda: _chunk_program(1, T, valid=True),
                      dict(zip(("q", "k", "v", "g", "beta"), padded),
                           valid=np.asarray([n], "int32")))
    want_o, want_s = _recurrence(*[x[:, :n] for x in ops])
    assert np.isfinite(out).all() and np.isfinite(state).all()
    np.testing.assert_allclose(out[:, :n], want_o, rtol=0, atol=2e-5)
    np.testing.assert_allclose(state, want_s, rtol=0, atol=2e-5)
    assert not out[:, n:].any()


def test_step_moves_live_rows_only_in_place_and_prefill_takes_the_trash_row():
    """A prefill writes slot 1's state and (a warm-up) the trash row; the
    step then moves slots 0 and 1 on and leaves dead slot 2 and the trash
    row as they were, bit for bit.  The state variable is updated where
    it lies (the ops' output aliases it)."""
    rng = np.random.default_rng(4)
    H, Dk, Dv, T, slots = 3, 8, 12, 20, 3
    ops = _operands(5, 1, T)
    sq, sk, sv, sg, sb = _operands(6, slots, 1)

    def build():
        fetches = _chunk_program(1, T, valid=True)
        block = pt.default_main_program().global_block()
        state = block.create_var(name="delta", persistable=True,
                                 shape=[slots + 1, H, Dk, Dv],
                                 dtype="float32")
        slot = layers.data("slot", [1], dtype="int32",
                           append_batch_size=False)
        layers.slot_state_write(state, fetches[1], slot)

        def data(name, shape, dtype="float32"):
            return layers.data(name, shape, dtype=dtype,
                               append_batch_size=False)

        step = layers.gated_delta_step(
            data("sq", [slots, 1, H, Dk]), data("sk", [slots, 1, H, Dk]),
            data("sv", [slots, 1, H, Dv]), data("sg", [slots, 1, H]),
            data("sb", [slots, 1, H]), state, data("live", [slots], "int32"))
        assert [op.type for op in block.ops
                if "delta" in op.output_arg_names()] \
            == ["slot_state_write", "gated_delta_step"]
        return fetches + [step]

    before = rng.normal(size=(slots + 1, H, Dk, Dv)).astype("float32")
    feed = dict(zip(("q", "k", "v", "g", "beta"), ops),
                valid=np.asarray([T - 3], "int32"), sq=sq, sk=sk, sv=sv,
                sg=sg, sb=sb, live=np.asarray([1, 1, 0], "int32"))
    after = {}
    for slot in (slots, 1):                    # the trash row, then a slot
        scope = pt.Scope()
        scope.set_var("delta", before.copy())
        _, wrote, step = _run(build, dict(feed, slot=np.asarray([slot],
                                                                "int32")),
                              scope)
        after[slot] = np.asarray(scope.find_var("delta"))
    _, prompt_state = _recurrence(*[x[:, :T - 3] for x in ops])
    np.testing.assert_allclose(wrote, prompt_state, rtol=0, atol=2e-5)
    # slot 1: the prefill's state, then one token more
    cont = [np.concatenate([x[:, :T - 3], s[1:2]], axis=1)
            for x, s in zip(ops, (sq, sk, sv, sg, sb))]
    want_o, want_s = _recurrence(*cont)
    np.testing.assert_allclose(after[1][1], want_s[0], rtol=0, atol=2e-5)
    np.testing.assert_allclose(step[1, 0], want_o[0, -1], rtol=0, atol=2e-5)
    # slot 0: live, moved on from what it held
    _, s0 = _recurrence(sq[:1], sk[:1], sv[:1], sg[:1], sb[:1], before[:1])
    np.testing.assert_allclose(after[1][0], s0[0], rtol=0, atol=2e-5)
    # dead slot 2 and the trash row: untouched by the step
    assert np.array_equal(after[1][2], before[2])
    assert np.array_equal(after[1][3], before[3])
    # the warm-up's prefill wrote the trash row and no slot's
    np.testing.assert_allclose(after[slots][slots], prompt_state[0], rtol=0,
                               atol=2e-5)
    assert np.array_equal(after[slots][2], before[2])


# ---------------------------------------------------------------------------
# kernels (interpret mode) against the XLA formulations
# ---------------------------------------------------------------------------

def _fused(*ops, **kw):
    """The fused scan kernel in interpret mode, on numpy operands."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import gated_delta as kern

    out, state = kern.chunk(
        *(jnp.asarray(x) for x in ops), interpret=True,
        **{n: jnp.asarray(x) for n, x in kw.items()})
    return np.asarray(out), np.asarray(state)


@pytest.mark.parametrize("T,Dk,Dv", [(192, 8, 16), (128, 96, 192),
                                     (256, 128, 128)])
def test_chunk_kernel_is_the_scan(T, Dk, Dv):
    """The whole scan as one kernel, from a state and with a prompt that
    ends inside a chunk: what ``chunked`` makes of it in XLA, and what the
    recurrence makes of it token by token."""
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_ops as gd
    from paddle_tpu.ops.pallas import gated_delta as kern

    ops = _operands(T, 2, T, H=2, Dk=Dk, Dv=Dv)
    s0 = np.random.default_rng(2).normal(size=(2, 2, Dk, Dv)) \
        .astype("float32")
    valid = np.asarray([T, T - 70], "int32")
    assert kern.chunk_supported(ops[0].shape, gd.CHUNK)
    want_o, want_s = gd.chunked(*(jnp.asarray(x) for x in ops),
                                s0=jnp.asarray(s0), valid=jnp.asarray(valid))
    got_o, got_s = _fused(*ops, s0=s0, valid=valid)
    np.testing.assert_allclose(got_o, want_o, rtol=0, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=0, atol=1e-5)
    true_o, true_s = _recurrence(*ops, s0=s0, valid=valid)
    np.testing.assert_allclose(got_o, true_o, rtol=0, atol=2e-5)
    np.testing.assert_allclose(got_s, true_s, rtol=0, atol=2e-5)


@pytest.mark.parametrize("heads_block", [1, 2, 3])
def test_chunk_kernel_takes_as_many_heads_a_block_as_divide_them(
        heads_block):
    """Six heads 1, 2 and 3 a grid step: the same numbers."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import gated_delta as kern

    ops = [jnp.asarray(x) for x in _operands(5, 1, 130, H=6)]
    want_o, want_s = kern.chunk(*ops, interpret=True, heads_block=6)
    got_o, got_s = kern.chunk(*ops, interpret=True, heads_block=heads_block)
    np.testing.assert_allclose(got_o, want_o, rtol=0, atol=1e-6)
    np.testing.assert_allclose(got_s, want_s, rtol=0, atol=1e-6)


@pytest.mark.parametrize("n", [1, 63, 64, 70, 129])
def test_chunk_kernel_reads_nothing_behind_valid(n):
    """``n`` real rows of 192, NaN behind them in every operand: the real
    rows and the state are the ``n`` tokens' alone; a chunk wholly behind
    ``valid`` leaves zeros and hands the state through."""
    T = 192
    ops = _operands(n, 1, T)
    padded = [x.copy() for x in ops]
    for x in padded:
        x[:, n:] = np.nan
    s0 = np.random.default_rng(n).normal(size=(1, 3, 8, 12)) \
        .astype("float32")
    out, state = _fused(*padded, s0=s0, valid=np.asarray([n], "int32"))
    want_o, want_s = _recurrence(*[x[:, :n] for x in ops], s0=s0)
    assert np.isfinite(out).all() and np.isfinite(state).all()
    np.testing.assert_allclose(out[:, :n], want_o, rtol=0, atol=2e-5)
    np.testing.assert_allclose(state, want_s, rtol=0, atol=2e-5)
    assert not out[:, n:].any()


def test_chunk_kernel_two_calls_that_carry_the_state_are_one_call():
    T, cut = 200, 77
    ops = _operands(8, 1, T)
    whole_o, whole_s = _fused(*ops)
    o1, s1 = _fused(*[x[:, :cut] for x in ops])
    o2, s2 = _fused(*[x[:, cut:] for x in ops], s0=s1)
    np.testing.assert_allclose(np.concatenate([o1, o2], 1), whole_o,
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(s2, whole_s, rtol=0, atol=2e-5)


def test_chunk_kernel_under_strong_decay_neither_overflows_nor_drifts():
    """``test_strong_decay_inside_a_chunk_neither_overflows_nor_drifts``
    through the kernel."""
    ops = _operands(3, 1, 128, decay=12.0)
    out, state = _fused(*ops)
    want_o, want_s = _recurrence(*ops)
    assert np.isfinite(out).all() and np.isfinite(state).all()
    np.testing.assert_allclose(out, want_o, rtol=0, atol=2e-5)
    np.testing.assert_allclose(state, want_s, rtol=0, atol=2e-5)


@pytest.mark.parametrize("Dk,Dv", [(8, 16), (96, 192)])
def test_step_kernel_is_the_three_contractions(Dk, Dv):
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_ops as gd
    from paddle_tpu.ops.pallas import gated_delta as kern

    n, H = 5, 3
    q, k, v, g, beta = (jnp.asarray(x[:, 0]) for x in
                        _operands(9, n, 1, H=H, Dk=Dk, Dv=Dv))
    state = np.random.default_rng(3).normal(size=(n + 1, H, Dk, Dv)) \
        .astype("float32")
    live = np.asarray([1, 0, 1, 1, 0])
    assert kern.step_supported(state.shape)
    want_o, want_s = gd.step(q, k, v, g, beta, jnp.asarray(state),
                             jnp.asarray(live, bool))
    got_o, got_s = kern.step(q, k, v, g, beta, jnp.asarray(state),
                             jnp.asarray(live, jnp.int32), interpret=True)
    on = live.astype(bool)
    np.testing.assert_allclose(np.asarray(got_o)[on], np.asarray(want_o)[on],
                               rtol=0, atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, rtol=0, atol=2e-6)
    # dead rows and the trash row: bit for bit what they were
    assert np.array_equal(np.asarray(got_s)[:n][~on], state[:n][~on])
    assert np.array_equal(np.asarray(got_s)[n], state[n])


def test_ops_book_their_lowering_and_log_a_downgrade_once(monkeypatch,
                                                          caplog):
    """Off a TPU the ops are the XLA formulations and say nothing; on a
    TPU backend under a mesh they are too, and say why, once."""
    import jax

    from paddle_tpu.ops import gated_delta_ops as gd

    ref0 = stat_get("gated_delta_lowered_reference")
    pal0 = stat_get("gated_delta_lowered_pallas")
    q, k, v, g, beta = _operands(1, 1, 64)
    _run(lambda: _chunk_program(1, 64), dict(q=q, k=k, v=v, g=g, beta=beta))
    assert stat_get("gated_delta_lowered_reference") == ref0 + 1
    assert stat_get("gated_delta_lowered_pallas") == pal0

    class Mesh:
        class devices:
            size = 4

    class Ctx:
        mesh = Mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    gd._downgrades_logged.clear()
    with caplog.at_level("WARNING"):
        for _ in range(2):
            use, why = gd._kernel_route(Ctx, "gated_delta_step")
            gd._lowered("reference", why)
    assert not use and "4-device mesh" in why
    assert sum("not the Pallas kernel" in r.message
               for r in caplog.records) == 1
    Ctx.mesh = None
    assert gd._kernel_route(Ctx, "gated_delta_step") == (True, None)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def test_every_delta_layer_has_two_states(engine):
    from paddle_tpu.models.llama import cache_spec

    spec = cache_spec("llama", 4, engine.model["layer_pattern"],
                      num_slots=3, num_pages=engine.num_pages,
                      page_tokens=PAGE, num_kv_heads=4, head_dim=16,
                      hidden=64)
    assert [(e["layer"], e["kind"]) for e in spec] == [
        (0, "slot_state"), (0, "slot_state"), (1, "slot_state"),
        (1, "slot_state"), (2, "slot_state"), (2, "slot_state"),
        (3, "pages"), (3, "pages")]
    # slots + the trash row; 3 rows of q | k | v (4 x (8 + 8 + 16))
    assert spec[0]["name"] == "llama.conv_state_0"
    assert spec[0]["shape"] == [4, 3, 128]
    assert spec[1]["name"] == "llama.delta_state_0"
    assert spec[1]["shape"] == [4, 4, 8, 16]
    assert engine.cache_names == ["llama.pool_k_3", "llama.pool_v_3"]
    assert engine.state_names == [
        f"llama.{kind}_state_{i}" for i in range(3)
        for kind in ("conv", "delta")]
    assert engine.slot_state_bytes == 3 * 4 * (3 * 128 + 4 * 8 * 16) * 4
    assert engine.stats()["slot_state_bytes"] == engine.slot_state_bytes
    for n in engine.state_names:
        assert n not in engine._weight_names()


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    yield eng
    eng.close()


def test_decay_constants_differ_by_head_layer_and_seed(engine):
    """The program draws ``A_log`` / ``dt_bias`` as the family's code does
    (from the layer's name); the builder redraws them from a seed."""
    from paddle_tpu.serving import GenerationEngine

    fresh = GenerationEngine(BUILDER.model_args(_cfg()), num_slots=2,
                             max_seq_len=32, prefill_buckets=[8],
                             page_tokens=PAGE, autostart=False,
                             prefill_chunk=0, prefix_reuse=False,
                             speculate=False)
    a0 = np.asarray(fresh.scope.find_var("llama.blk0.gdn_A_log"))
    a1 = np.asarray(fresh.scope.find_var("llama.blk1.gdn_A_log"))
    dt = np.asarray(fresh.scope.find_var("llama.blk0.gdn_dt_bias"))
    assert a0.shape == (4,) and len(set(a0.tolist())) == 4
    assert not np.array_equal(a0, a1)
    assert (np.exp(a0) > 0).all() and (np.exp(a0) < 16).all()
    softplus = np.log1p(np.exp(dt))
    assert (softplus > 9e-4).all() and (softplus < 0.11).all()
    seeded = np.asarray(engine.scope.find_var("llama.blk0.gdn_A_log"))
    assert not np.array_equal(seeded, a0)
    BUILDER.seed_delta_gates(fresh.scope, _cfg(), 11)
    assert np.array_equal(
        np.asarray(fresh.scope.find_var("llama.blk0.gdn_A_log")), seeded)


@pytest.mark.parametrize("over", [
    {}, {"linear_allow_neg_eigval": False},
    {"assumed": {"qk_norm": "proj", "norm": "pre", "eos_id": -1}}])
def test_uncached_forward_is_the_reference(over):
    """``build_llama_forward`` with the delta mixer, the norms on the
    outputs and QK-norm over the whole projection, against the plain
    reference, every row of a batch of two."""
    from paddle_tpu.models.llama import build_llama_forward

    cfg = _cfg(**over)
    S = 70
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        _, fetches = build_llama_forward(2, S, name="llama",
                                         attn_impl="xla",
                                         **BUILDER.model_args(cfg))
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    BUILDER.seed_delta_gates(scope, cfg, 3)
    ids = np.random.default_rng(0).integers(1, 97, (2, S))
    logits, = exe.run(main, feed={"input_ids": ids.astype("int64")},
                      fetch_list=[fetches["logits"]], scope=scope)
    params = REF.params_from_scope(scope, cfg, "llama")
    if cfg["assumed"]["norm"] == "pre":
        # the reference knows the published layout only: the program's
        # two layouts must differ, and by more than rounding
        post = np.asarray(REF.forward(params, ids[0].astype("int32"), cfg))
        assert np.abs(logits[0] - post).max() > 0.1
        return
    for b in range(2):
        want = np.asarray(REF.forward(params, ids[b].astype("int32"), cfg))
        assert np.abs(logits[b] - want).max() / np.abs(want).max() < TOL


def test_prefill_then_cached_decode_in_a_reused_slot_between_neighbours():
    """Slots 0 and 2 decode all the while; slot 1 serves a long request,
    is left, and takes the compared one: its paged prefill and eight
    cached decode steps are the reference's full forward, and so is a
    prompt of more than two chunks."""
    cfg = _cfg()
    eng = _engine(cfg)
    w0 = stat_get("serving_slot_state_writes")
    d0 = stat_get("serving_delta_state_steps")
    try:
        started = []
        sides = [eng.submit(_prompt(50 + i, 9 + i), 60,
                            on_token=lambda t, ts: started.append(t))
                 for i in range(2)]
        first = eng.submit(_prompt(52, 30), 6)
        assert first.result(300)["slot"] == 2
        # slot 2 is free again, 0 and 1 still decode
        res = {}
        for n in (5, 150):
            prompt = _prompt(60 + n, n)
            r = eng.generate(prompt, 9, timeout=300)
            assert r["slot"] == 2
            res[n] = (prompt, r)
        rest = [f.result(300) for f in sides]
        counters = eng.stats()["counters"]
    finally:
        eng.close()
    assert [r["slot"] for r in rest] == [0, 1]
    assert all(len(r["tokens"]) == 60 for r in rest)
    for prompt, r in res.values():
        assert _off_reference(eng, cfg, prompt, r) < TOL
    for f, r in zip((50, 51), rest):
        assert _off_reference(eng, cfg, _prompt(f, 9 + f - 50), r) < TOL
    assert counters["slot_state_writes"] == 5
    assert stat_get("serving_slot_state_writes") == w0 + 5
    # all but the first request joined a grid with a step in flight, and
    # rode the step ahead on the states their prefills left on the device
    assert counters["decode_joiners_ahead"] == 4
    assert counters["decode_steps_ahead"] == counters["decode_steps"] - 1
    # every rider of every step moved three layers' states on
    assert counters["delta_state_steps"] % 3 == 0
    assert counters["delta_state_steps"] >= 3 * (2 * 59 + 5 + 8 + 8)
    assert stat_get("serving_delta_state_steps") \
        == d0 + counters["delta_state_steps"]


def test_spans_say_what_the_scan_covered():
    from paddle_tpu import telemetry

    eng = _engine()
    try:
        eng.generate(_prompt(41, 70), 4, timeout=300)
        spans = [s for s in telemetry.get_spans() if s.end is not None]
    finally:
        eng.close()
    assert telemetry.metrics.gauge("serving_slot_state_bytes").get() \
        == eng.slot_state_bytes
    prefill = [s for s in spans if s.name == "generation/prefill"][-1]
    # 70 tokens in the rung of 192: three chunks of 64, the last all pad
    assert prefill.attrs["state_written"] == 1
    assert prefill.attrs["scan_tokens"] == 70
    assert prefill.attrs["scan_chunks"] == 3
    assert prefill.attrs["scan_pad_chunks"] == 1
    steps = [s for s in spans if s.name == "generation/decode_step"
             and "state_slots" in s.attrs]
    assert steps and all(s.attrs["state_slots"] == 1 for s in steps[-3:])
    assert all(s.attrs["live_positions"] >= 71 for s in steps[-3:])


@pytest.mark.parametrize("fault", ["state_not_written", "tail_not_written"])
def test_a_planted_fault_is_not_within_tolerance(fault, monkeypatch):
    """A prefill that leaves a slot's delta state (or the rows of its
    convolution) unwritten reads far outside the tolerance the sound
    program sits well inside: the slot decodes on from what its last
    tenant left."""
    from paddle_tpu.ops.registry import get_op_def

    cfg = _cfg()
    write = get_op_def("slot_state_write")
    real = write.lower
    skipped_rank = 4 if fault == "state_not_written" else 3

    def lower(ctx, op):
        if len(ctx.get_input(op, "State").shape) == skipped_rank:
            return ctx.set_output(op, "StateOut", ctx.get_input(op, "State"))
        return real(ctx, op)

    monkeypatch.setattr(write, "lower", lower)
    eng = _engine(cfg)
    try:
        eng.generate(_prompt(70, 25), 12, timeout=300)   # used and left
        prompt = _prompt(71, 19)
        res = eng.generate(prompt, 9, timeout=300)
    finally:
        eng.close()
    assert res["slot"] == 0
    assert _off_reference(eng, cfg, prompt, res) > 8 * TOL


@pytest.mark.parametrize("kw,reason", [
    ({"prefix_reuse": True}, "prefix_reuse"),
    ({"prefill_chunk": 8}, "prefill_chunk > 0"),
    ({"speculate": True}, "speculate"),
    ({"role": "prefill"}, "KV-segment handoff"),
    ({"role": "decode"}, "KV-segment handoff"),
])
def test_what_walks_pages_only_is_refused_for_delta_state(kw, reason):
    with pytest.raises(ValueError, match="slot state") as e:
        _engine(**kw)
    assert reason in str(e.value)
    assert "convolution layers" not in str(e.value)


def test_block_diffusion_over_delta_state_is_refused():
    from paddle_tpu.serving import GenerationEngine

    model = BUILDER.model_args(_cfg())
    model["block_diffusion"] = {"block": 4, "passes": 2, "mask_id": 96}
    with pytest.raises(ValueError, match="block_diffusion"):
        GenerationEngine(model, num_slots=2, max_seq_len=64,
                         prefill_buckets=[16], page_tokens=PAGE,
                         prefill_chunk=0, prefix_reuse=False,
                         speculate=False, autostart=False)


def test_continuation_programs_refuse_delta_layers():
    from paddle_tpu.models.llama import build_llama_prefill_chunk

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pytest.raises(ValueError, match="keep slot state"):
            build_llama_prefill_chunk(8, 64, 9, PAGE, name="llama",
                                      **BUILDER.model_args(_cfg()))


def test_the_uncached_prefill_returns_both_states():
    from paddle_tpu.models.llama import build_llama_prefill

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches = build_llama_prefill(
            2, 8, name="llama", attn_impl="xla",
            **BUILDER.model_args(_cfg()))
    assert feeds == ["input_ids", "last_pos"]
    kept = sorted(k for k in fetches if k[:2] in ("k_", "v_", "st", "de"))
    assert kept == ["delta_state_0", "delta_state_1", "delta_state_2",
                    "k_3", "state_0", "state_1", "state_2", "v_3"]
    assert tuple(fetches["state_0"].shape) == (2, 3, 128)
    assert tuple(fetches["delta_state_0"].shape) == (2, 4, 8, 16)


def test_value_heads_that_are_no_multiple_of_key_heads_are_refused():
    """Since PR 47 value heads may be a multiple of the key heads (4
    here): 8 give a state a value head, 6 are refused."""
    from paddle_tpu.models.llama import cache_spec

    mixer = dict(BUILDER.layer_pattern(_cfg())[0]["mixer"], value_heads=6)
    with pytest.raises(ValueError, match="multiple of key heads"):
        cache_spec("llama", 1, [{"mixer": mixer}], num_slots=2, num_pages=4,
                   page_tokens=PAGE, num_kv_heads=4, head_dim=16, hidden=64)
    spec = cache_spec("llama", 1, [{"mixer": dict(mixer, value_heads=8)}],
                      num_slots=2, num_pages=4, page_tokens=PAGE,
                      num_kv_heads=4, head_dim=16, hidden=64)
    assert [e["shape"][1] for e in spec if "delta_state" in e["name"]] == [8]


def test_norm_is_pre_post_or_both_by_name():
    from paddle_tpu.models.llama import build_llama_forward

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pytest.raises(ValueError,
                           match="'pre', 'post', 'pre_post' or 'parallel'"):
            build_llama_forward(1, 8, vocab_size=97, hidden=64,
                                num_layers=1, num_heads=4, intermediate=96,
                                name="llama", norm="both")
