"""Per-slot state that is not pages, and what came with it (PR 34).

* **Ops** (``ops/decode_ops.py``): ``short_conv`` / ``short_conv_tail`` /
  ``slot_state_write`` / ``short_conv_step`` against a plain float64 sum
  over three shifted copies: prompts of length 1, 2, 3 and a padded rung
  (the state is taken at the TRUE last positions), the step after them, a
  dead row.
* **Router** (``parallel/moe.py`` ``route_top_k``): sigmoid scoring with
  a non-zero bias against the benchmark's plain reference: selection by
  ``s + b``, weights from ``s``, the 1e-6, the route scale, ties.
* **Model** (``models/llama.py``): a pattern holding both mixers and a
  leading dense layer through the paged ``GenerationEngine``: paged
  prefill + cached decode against the reference's full forward; the tied
  head is the embedding's variable; a reused slot, a joiner's prefill
  while a step is in flight and rows going dead beside a live one, each
  against the same request on a fresh engine; every refusal at
  construction, with its reason.
* **Kernel** (``ops/pallas/paged_attention.py``): heads of 64 over a pool
  packed two heads a row, against ``_attend_cache`` (interpret mode).
"""
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.monitor import stat_get

from conftest import assert_logits_match

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "lfm_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "lfm2-24b-a2b")
BUILDER = _load("builders", "lfm2_engine")


def _cfg(**over):
    """The published keys at a toy size: hidden 64, 4 query over 2 KV
    heads of 16, a conv layer over a dense SwiGLU, then attention, conv,
    conv over 8 SiLU experts top-3, sigmoid-scored with a bias."""
    cfg = {"vocab_size": 97, "hidden_size": 64, "num_hidden_layers": 4,
           "num_dense_layers": 1, "intermediate_size": 96,
           "layer_types": ["conv", "full_attention", "conv", "conv"],
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
           "rope_parameters": {"rope_theta": 1000000},
           "num_experts": 8, "num_experts_per_tok": 3,
           "moe_intermediate_size": 32, "norm_topk_prob": True,
           "routed_scaling_factor": 1, "use_expert_bias": True,
           "as_run": {"attention_precision": "highest"},
           "assumed": {"qk_norm": True, "tie_word_embeddings": True,
                       "expert_bias_scale": 0.05},
           "check_tolerance": {
               "near_tie_margin_share_of_router_range": 0.0004}}
    cfg.update(over)
    return cfg


def _engine(cfg=None, seed=11, **kw):
    from paddle_tpu.serving import GenerationEngine

    cfg = cfg or _cfg()
    args = dict(num_slots=3, max_seq_len=64, prefill_buckets=[8, 16, 32],
                page_tokens=PAGE, attn_impl="xla", keep_logits=True,
                prefill_chunk=0, prefix_reuse=False, speculate=False,
                eos_id=-1, deadline_ms=600000)
    args.update(kw)
    eng = GenerationEngine(BUILDER.model_args(cfg), **args)
    if "scope" not in kw:
        BUILDER.seed_expert_bias(eng.scope, cfg, seed)
    return eng


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 97, n).tolist()


def _reference_rows(eng, cfg, seq, rows):
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    return np.asarray(REF.forward(params, np.asarray(seq, "int32"), cfg,
                                  np.asarray(rows)))


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def _run(build, feed):
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        fetches = build()
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    return exe.run(main, feed=feed, fetch_list=fetches, scope=scope), scope


def _conv64(z, w):
    """c_t = sum_j w[:, j] z_{t-2+j}, z_{<0} = 0, in float64."""
    z, w = np.asarray(z, "float64"), np.asarray(w, "float64")
    zp = np.concatenate([np.zeros((w.shape[1] - 1, z.shape[1])), z])
    return sum(zp[j:j + len(z)] * w[:, j] for j in range(w.shape[1]))


@pytest.mark.parametrize("n", [1, 2, 3, 7])
def test_prefill_conv_and_state_at_the_true_last_positions(n):
    """A prompt of ``n`` tokens right-padded to a rung of 8: the
    convolution's real rows are the plain sum's, the state is rows ``n -
    2, n - 1`` (zero where the prompt is shorter), whatever the pad
    holds, and the step after it continues the sequence."""
    rng = np.random.default_rng(n)
    H, L, S = 16, 3, 8
    z = rng.normal(size=(1, S, H)).astype("float32")   # pad rows: garbage
    nxt = rng.normal(size=(2, 1, H)).astype("float32")

    def build():
        zv = layers.data("z", [1, S, H], append_batch_size=False)
        nv = layers.data("n", [1], dtype="int32", append_batch_size=False)
        xv = layers.data("x", [2, 1, H], append_batch_size=False)
        live = layers.data("live", [2], dtype="int32",
                           append_batch_size=False)
        slot = layers.data("slot", [1], dtype="int32",
                           append_batch_size=False)
        block = pt.default_main_program().global_block()
        state = block.create_var(name="state", persistable=True,
                                 shape=[3, L - 1, H], dtype="float32")
        c = layers.short_conv(zv, L, param_attr="conv.w")
        tail = layers.short_conv_tail(zv, nv, L - 1)
        layers.slot_state_write(state, tail, slot)
        step = layers.short_conv_step(xv, state, live, L,
                                      param_attr="conv.w")
        return [c, tail, step]

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        fetches = build()
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    before = rng.normal(size=(3, L - 1, H)).astype("float32")
    scope.set_var("state", before.copy())
    c, tail, step = exe.run(
        main, feed={"z": z, "n": np.asarray([n], "int32"), "x": nxt,
                    "live": np.asarray([0, 1], "int32"),
                    "slot": np.asarray([1], "int32")},
        fetch_list=fetches, scope=scope)
    w = np.asarray(scope.find_var("conv.w"))
    assert w.shape == (H, L)
    want = _conv64(z[0, :n], w)
    np.testing.assert_allclose(c[0, :n], want, rtol=0, atol=2e-6)
    rows = np.concatenate([np.zeros((2, H), "float32"), z[0, :n]])[-2:]
    assert np.array_equal(tail[0], rows)
    # slot 1's step: the sequence's next row over the state just written
    # (the whole of it: what the slot held before is gone)
    cont = _conv64(np.concatenate([z[0, :n], nxt[1]]), w)[-1]
    np.testing.assert_allclose(step[1, 0], cont, rtol=0, atol=2e-6)
    after = np.asarray(scope.find_var("state"))
    assert np.array_equal(after[1], np.stack([rows[1], nxt[1, 0]]))
    # slot 0 was dead in the step: its state is what it was, bit for bit;
    # so is the trash row
    assert np.array_equal(after[0], before[0])
    assert np.array_equal(after[2], before[2])


def test_conv_bias_is_added_once():
    rng = np.random.default_rng(5)
    z = rng.normal(size=(2, 6, 8)).astype("float32")

    def build():
        zv = layers.data("z", [2, 6, 8], append_batch_size=False)
        return [layers.short_conv(zv, 3, param_attr="w", bias_attr="b")]

    (c,), scope = _run(build, {"z": z})
    scope_b = np.asarray(scope.find_var("b"))
    assert scope_b.shape == (8,)
    w = np.asarray(scope.find_var("w"))
    for b in range(2):
        np.testing.assert_allclose(c[b], _conv64(z[b], w) + scope_b,
                                   rtol=0, atol=2e-6)


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("norm_topk,scale", [(True, 1.0), (True, 2.5),
                                             (False, 1.0)])
def test_sigmoid_routing_with_a_bias_is_the_references(norm_topk, scale):
    from paddle_tpu.parallel.moe import route_top_k

    rng = np.random.default_rng(3)
    x = rng.normal(size=(50, 32)).astype("float32")
    w = rng.normal(size=(32, 8)).astype("float32") * 0.3
    bias = rng.normal(size=(8,)).astype("float32") * 0.2
    cfg = _cfg(norm_topk_prob=norm_topk, routed_scaling_factor=scale)
    logits, experts, weights = route_top_k(
        x, w, 3, "sigmoid", bias, norm_topk, scale)
    logits, experts, weights = map(np.asarray, (logits, experts, weights))
    want, _ = REF.route(x @ w, bias, cfg)
    want = np.asarray(want)
    got = np.zeros_like(want)
    np.put_along_axis(got, experts, weights, axis=1)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    s = 1.0 / (1.0 + np.exp(-(x.astype("float64") @ w)))
    # selection by s + b ...
    assert np.array_equal(np.sort(experts, 1),
                          np.sort(np.argsort(-(s + bias), 1)[:, :3], 1))
    # ... which the bias moved for some rows ...
    assert (np.sort(experts, 1)
            != np.sort(np.argsort(-s, 1)[:, :3], 1)).any()
    # ... and weights from the UNBIASED s, over their sum plus 1e-6
    picked = np.take_along_axis(s, experts, 1)
    if norm_topk:
        picked = picked / (picked.sum(1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(weights, picked * scale, rtol=0, atol=2e-6)
    if norm_topk and scale == 1.0:
        assert (weights.sum(1) < 1.0).all()          # the 1e-6


def test_sigmoid_routing_ties_go_to_the_lower_index():
    from paddle_tpu.parallel.moe import route_top_k

    x = np.ones((1, 4), "float32")
    w = np.zeros((4, 6), "float32")                  # every s is 0.5
    bias = np.asarray([0, 0.1, 0, 0.1, 0, 0], "float32")
    _, experts, weights = route_top_k(x, w, 3, "sigmoid", bias)
    assert sorted(np.asarray(experts)[0].tolist()) == [0, 1, 3]
    np.testing.assert_allclose(np.asarray(weights)[0],
                               0.5 / (1.5 + 1e-6), rtol=1e-6)


def test_softmax_routing_is_what_it_was():
    """Defaults reproduce the softmax router: the k largest logits under
    a softmax over the selected."""
    import jax

    from paddle_tpu.parallel.moe import route_top_k

    rng = np.random.default_rng(4)
    x = rng.normal(size=(20, 16)).astype("float32")
    w = rng.normal(size=(16, 8)).astype("float32")
    logits, experts, weights = route_top_k(x, w, 3)
    top, idx = jax.lax.top_k(logits, 3)
    assert np.array_equal(np.asarray(experts), np.asarray(idx))
    assert np.array_equal(np.asarray(weights),
                          np.asarray(jax.nn.softmax(top, axis=-1)))
    with pytest.raises(ValueError, match="unknown router score"):
        route_top_k(x, w, 3, "tanh")


# ---------------------------------------------------------------------------
# model and engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    yield eng
    eng.close()


@pytest.mark.parametrize("n", [1, 2, 3, 10, 17])
def test_paged_prefill_and_cached_decode_are_the_references(engine, n):
    """Prompts of 1, 2, 3 tokens (the state's missing entries are zero),
    a padded rung and one past it: the paged prefill and eleven cached
    decode steps give the plain reference's full forward over prompt plus
    generated tokens, rows ``n - 1`` on."""
    cfg = _cfg()
    prompt = _prompt(n, n)
    res = engine.generate(prompt, 12, timeout=300)
    seq = prompt + res["tokens"]
    want = _reference_rows(engine, cfg, seq, np.arange(n - 1, n + 11))
    assert_logits_match(np.stack(res["logits"]), want,
                        f"prompt {n}: prefill + cached decode")
    assert len(res["router_logits"]) == 12
    assert res["router_logits"][0].shape == (3, 8)   # three expert layers


def test_every_layer_kind_has_its_cache(engine):
    from paddle_tpu.models.llama import cache_spec

    spec = cache_spec("llama", 4, engine.model["layer_pattern"],
                      num_slots=3, num_pages=engine.num_pages,
                      page_tokens=PAGE, num_kv_heads=2, head_dim=16,
                      hidden=64)
    assert [(e["layer"], e["kind"]) for e in spec] == [
        (0, "slot_state"), (1, "pages"), (1, "pages"), (2, "slot_state"),
        (3, "slot_state")]
    assert spec[0]["shape"] == [4, 2, 64]             # slots + trash row
    assert spec[1]["shape"] == [engine.num_pages, 2, PAGE, 16]
    assert engine.cache_names == ["llama.pool_k_1", "llama.pool_v_1"]
    assert engine.state_names == ["llama.conv_state_0",
                                  "llama.conv_state_2",
                                  "llama.conv_state_3"]
    assert engine.slot_state_bytes == 3 * 4 * 2 * 64 * 4
    assert engine.stats()["slot_state_bytes"] == engine.slot_state_bytes
    for n in engine.state_names:
        assert n not in engine._weight_names()


def test_the_tied_head_is_the_embeddings_variable(engine):
    names = engine.scope.local_var_names()
    assert "llama.embed" in names
    assert not [n for n in names if "head" in n]
    for prog in (engine._decode_prog,
                 engine._prefill_prog_for(8)[0]):
        block = prog.global_block()
        assert "llama.embed" in block.vars and "llama.head.w" not in block.vars
        heads = [op for op in block.ops if op.type == "matmul"
                 and "llama.embed" in op.input_arg_names()]
        assert len(heads) == 1 and heads[0].attr("transpose_Y")


def _alone(cfg, prompt, n_new, scope):
    """The request on a fresh engine (zero state, empty pool) built on
    the weights in ``scope``, whose engine is closed."""
    eng = _engine(cfg, scope=scope)
    try:
        return eng.generate(prompt, n_new, timeout=300)
    finally:
        eng.close()


def test_a_reused_slot_starts_from_its_prompt_alone():
    """Slot 0 serves a long request, then a short one: the second
    request's logits are, bit for bit, those of the same request on a
    fresh engine (whose state was zero): the prefill overwrites the whole
    of the slot's state and nothing is reset between."""
    cfg = _cfg()
    first, second = _prompt(21, 19), _prompt(22, 2)
    eng = _engine(cfg)
    try:
        eng.generate(first, 9, timeout=300)
        state = np.asarray(eng.scope.find_var("llama.conv_state_0"))
        assert np.abs(state[0]).max() > 0            # the slot was used
        res = eng.generate(second, 8, timeout=300)
        assert res["slot"] == 0
        writes = eng.stats()["counters"]["slot_state_writes"]
    finally:
        eng.close()
    fresh = _alone(cfg, second, 8, eng.scope)
    assert res["tokens"] == fresh["tokens"]
    assert np.array_equal(np.stack(res["logits"]),
                          np.stack(fresh["logits"]))
    assert writes == 2


def test_a_joiners_prefill_and_dead_rows_leave_a_live_slot_alone():
    """A request decodes in slot 0 while a second joins (its prefill is
    dispatched while a step of the first is in flight), decodes beside it
    and ends (its row goes dead), and a third takes the slot it left: the
    first request's logits are those of the same request alone on a fresh
    engine, and so are the joiners', each against its own fresh engine."""
    cfg = _cfg()
    long_, j1, j2 = _prompt(31, 6), _prompt(32, 11), _prompt(33, 3)
    eng = _engine(cfg)
    ahead0 = stat_get("serving_decode_steps_ahead")
    try:
        started = []
        f0 = eng.submit(long_, 40, on_token=lambda t, ts: started.append(t))
        while len(started) < 5:                      # decoding, in flight
            pass
        f1 = eng.submit(j1, 6)
        r1 = f1.result(300)
        f2 = eng.submit(j2, 6)
        r2, r0 = f2.result(300), f0.result(300)
        counters = eng.stats()["counters"]
        compiled = eng._decode_exe.cache_info()["compiled"]
    finally:
        eng.close()
    assert (r0["slot"], r1["slot"], r2["slot"]) == (0, 1, 1)
    assert stat_get("serving_decode_steps_ahead") > ahead0
    # both joiners rode the step dispatched ahead of the settle, on their
    # prefill's token as the device holds it: the prefill writes the
    # slot's state on the device and the step behind it in the queue
    # reads it, so only the long request's first step was not ahead
    assert counters["decode_joiners_ahead"] == 2
    assert counters["decode_steps_ahead"] == counters["decode_steps"] - 1
    assert counters["decode_rows_discarded"] == 0 and compiled == 1
    for prompt, res in ((long_, r0), (j1, r1), (j2, r2)):
        fresh = _alone(cfg, prompt, len(res["tokens"]), eng.scope)
        assert res["tokens"] == fresh["tokens"]
        assert_logits_match(np.stack(res["logits"]),
                            np.stack(fresh["logits"]),
                            f"prompt of {len(prompt)} beside others")
    # the long request sat in the same row of the same grid: exact
    fresh = _alone(cfg, long_, 40, eng.scope)
    assert np.array_equal(np.stack(r0["logits"]), np.stack(fresh["logits"]))


def test_spans_and_counters_say_what_the_state_did():
    from paddle_tpu import telemetry

    cfg = _cfg()
    eng = _engine(cfg)
    w0 = stat_get("serving_slot_state_writes")
    try:
        eng.generate(_prompt(41, 5), 6, timeout=300)
        spans = [s for s in telemetry.get_spans() if s.end is not None]
    finally:
        eng.close()
    assert stat_get("serving_slot_state_writes") == w0 + 1
    assert telemetry.metrics.gauge("serving_slot_state_bytes").get() \
        == eng.slot_state_bytes
    prefill = [s for s in spans if s.name == "generation/prefill"][-1]
    assert prefill.attrs["state_written"] == 1
    steps = [s for s in spans if s.name == "generation/decode_step"
             and "state_slots" in s.attrs]
    assert steps and all(s.attrs["state_slots"] == 1 for s in steps[-3:])
    assert all(s.attrs["live_positions"] >= 6 for s in steps[-3:])
    assert all("experts_touched" in s.attrs for s in steps[-3:])


@pytest.mark.parametrize("kw,reason", [
    ({"prefix_reuse": True}, "prefix_reuse"),
    ({"prefill_chunk": 8}, "prefill_chunk > 0"),
    ({"speculate": True}, "speculate"),
    ({"role": "prefill"}, "KV-segment handoff"),
    ({"role": "decode"}, "KV-segment handoff"),
])
def test_what_walks_pages_only_is_refused_with_its_reason(kw, reason):
    with pytest.raises(ValueError, match="state that is not pages") as e:
        _engine(**kw)
    assert reason in str(e.value)


def test_block_diffusion_over_slot_state_is_refused():
    from paddle_tpu.serving import GenerationEngine

    model = BUILDER.model_args(_cfg())
    model["block_diffusion"] = {"block": 4, "passes": 2, "mask_id": 96}
    with pytest.raises(ValueError, match="block_diffusion"):
        GenerationEngine(model, num_slots=2, max_seq_len=64,
                         prefill_buckets=[16], page_tokens=PAGE,
                         prefill_chunk=0, prefix_reuse=False,
                         speculate=False, autostart=False)


def test_continuation_programs_refuse_convolution_layers():
    from paddle_tpu.models.llama import build_llama_prefill_chunk

    model = BUILDER.model_args(_cfg())
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pytest.raises(ValueError, match="state that is not pages"):
            build_llama_prefill_chunk(8, 64, 9, PAGE, name="llama", **model)


def test_the_uncached_prefill_returns_the_state_rows():
    """``build_llama_prefill`` without a cache: a conv layer's rows come
    back as ``state_<i>`` beside the attention layer's ``k_<i>`` /
    ``v_<i>``."""
    from paddle_tpu.models.llama import build_llama_prefill

    model = BUILDER.model_args(_cfg())
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches = build_llama_prefill(2, 8, name="llama",
                                             attn_impl="xla", **model)
    assert feeds == ["input_ids", "last_pos"]
    assert sorted(k for k in fetches if k[:2] in ("k_", "v_", "st")) \
        == ["k_1", "state_0", "state_2", "state_3", "v_1"]
    assert tuple(fetches["state_0"].shape) == (2, 2, 64)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [1, 4])
def test_paged_kernel_at_head_64_is_the_einsum_formulation(rows):
    """32 query over 8 KV heads of 64 (and a block of rows): the pool is
    kept two heads a 128-lane row, the kernel (interpret mode) reads it
    as it lies and agrees with the gather + einsum formulation."""
    import jax.numpy as jnp

    from paddle_tpu.ops.decode_ops import (_attend_cache, _gather_pages,
                                           pool_shape)
    from paddle_tpu.ops.pallas import paged_attention as pa

    rng = np.random.default_rng(rows)
    B, H, Hkv, D, pt_, NP = 3, 32, 8, 64, 16, 6
    P = B * NP + 1
    shape = pool_shape(P, Hkv, pt_, D)
    assert shape == [P, 4, pt_, 128]
    assert pool_shape(P, Hkv, pt_, 128) == [P, Hkv, pt_, 128]
    assert pool_shape(P, 3, pt_, 64) == [P, 3, pt_, 64]
    pool_k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(B * NP).reshape(B, NP), jnp.int32)
    pos = jnp.asarray([0, 37, 91], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, rows, D)), jnp.float32)
    assert pa.supported(q.shape, pool_k.shape)
    assert not pa.supported((B, H, rows, 64), (P, Hkv, pt_, 64))
    assert not pa.supported((B, H, rows, 32), (P, 2, pt_, 128))
    got = pa.paged_decode_attention(q, pool_k, pool_v, bt,
                                    pos + (rows - 1), interpret=True)
    want = _attend_cache(q, _gather_pages(pool_k, bt, D),
                         _gather_pages(pool_v, bt, D), pos,
                         block=rows > 1)
    assert got.shape == (B, H, rows, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6)


def test_a_packed_pool_round_trips_through_write_and_gather():
    """``kv_pool_write`` packs by the pool's shape, ``kv_pool_gather``
    unpacks by the head's width: what was written is what is read, head
    for head."""
    rng = np.random.default_rng(9)
    Hkv, D, T = 4, 64, 5
    new = rng.normal(size=(1, Hkv, T, D)).astype("float32")

    def build():
        from paddle_tpu.ops.decode_ops import pool_shape

        nv = layers.data("new", [1, Hkv, T, D], append_batch_size=False)
        bt = layers.data("bt", [1, 2], dtype="int32",
                         append_batch_size=False)
        n = layers.data("n", [1], dtype="int32", append_batch_size=False)
        zero = layers.fill_constant([1], "int32", 0)
        block = pt.default_main_program().global_block()
        pool = block.create_var(name="pool", persistable=True,
                                shape=pool_shape(3, Hkv, PAGE, D),
                                dtype="float32")
        layers.kv_pool_write(pool, nv, zero, bt, n)
        return [layers.kv_pool_gather(pool, bt, head_dim=D)]

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        fetches = build()
    exe, scope = pt.Executor(), pt.Scope()
    scope.set_var("pool", np.zeros((3, 2, PAGE, 128), "float32"))
    view, = exe.run(main, feed={"new": new,
                                "bt": np.asarray([[2, 1]], "int32"),
                                "n": np.asarray([T], "int32")},
                    fetch_list=fetches, scope=scope)
    assert view.shape == (1, Hkv, 2 * PAGE, D)
    assert np.array_equal(view[:, :, :T], new)
    assert not view[:, :, T:].any()
