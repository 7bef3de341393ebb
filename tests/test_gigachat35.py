"""``gigachat35-432b-a28b`` at a small size (PR 47): latent (MLA) pages with
an expanded prefill path and an absorbed decode path, delta layers whose
value heads outnumber their key heads, sandwich norms, clamped SwiGLUs,
YaRN over interleaved rotary pairs, one chip's share of a 256-wide router
behind a leading dense layer.

* **Ops**: ``rope`` with ``interleave`` and ``yarn`` against a written-out
  table, decode position ``p`` bit-equal to prefill position ``p``;
  ``latent_decode_attention`` (absorbed) against ``latent_prefill_
  attention`` (expanded) on the same latent rows; both Pallas kernels
  (interpret mode) against einsums, a recycled page's garbage reaching
  nothing; the delta mixer's ops at two value heads a key head against the
  recurrence (chunked and step); the clamp at inputs past 10.
* **The share**: 32 shares of a 256-wide router at toy widths, the shared
  expert counted once, add up to the uncut reference layer.
* **Model** (``models/llama.py``) against the benchmark's plain reference:
  prefill then eight cached decode steps through the paged
  ``GenerationEngine`` for a 5-layer toy of the published pattern, logits
  not tokens; the latent pool written by a prefill and by steps, read back
  row for row; slots reused and left; ``pre_post`` against the reference;
  spans, counters, the refusals.
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
        "giga_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "gigachat35-432b-a28b")
BUILDER = _load("builders", "gigachat35_engine")
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "yarn"}


def _cfg(**over):
    """The published keys at a toy size: hidden 64; layer 0 a delta layer
    (2 key, 4 value heads of 16) over the dense SwiGLU; layer 1 latent
    attention (8 heads of nope 16 + rope 8 over a latent of 32, values of
    16, query rank 24); layers 2-4 delta layers; layers 1-4 a router of 16
    experts, 3 a token, of which experts 4..7 are held, beside a shared
    expert."""
    cfg = {"model_type": "gigachat3_5", "hidden_size": 64,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "num_hidden_layers": 5, "num_attention_heads": 8,
           "num_key_value_heads": 8, "vocab_size": 97,
           "n_shared_experts": 1, "n_routed_experts": 4,
           "routed_scaling_factor": 2.5, "kv_lora_rank": 32,
           "q_lora_rank": 24, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "qk_nope_head_dim": 16, "qk_head_dim": 24, "n_group": 1,
           "topk_group": 1, "num_experts_per_tok": 3,
           "first_k_dense_replace": 1, "norm_topk_prob": True,
           "rope_interleave": True, "hidden_act": "silu",
           "rms_norm_eps": 1e-6, "rope_theta": 100000,
           "rope_scaling": dict(YARN), "layernorm_type": "pre_post",
           "gated_attention": True, "use_shared_expert_sigmoid": False,
           "use_mla_scaling_factor": True, "full_attention_layers": [1],
           "linear_key_head_dim": 16, "linear_value_head_dim": 16,
           "linear_conv_kernel_dim": 4, "linear_num_key_heads": 2,
           "linear_num_value_heads": 4, "linear_sigmoid_gate_scale": 2,
           "linear_attn_o_norm_eps": 1e-6, "swiglu_limit": 10,
           "tie_word_embeddings": False,
           "expert_share": {"router_experts": 16, "first": 4},
           "assumed": {"expert_bias_scale": 0.02, "eos_id": -1},
           "check_tolerance": {
               "near_tie_margin_share_of_router_range": 4e-4}}
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
        BUILDER.seed_expert_bias(eng.scope, cfg, seed)
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


def _run(build, feed, scope=None):
    """Build a small program under a guard, run its startup and fetch."""
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        fetches = build()
    exe = pt.Executor()
    scope = scope or pt.Scope()
    exe.run(startup, scope=scope)
    return [np.asarray(o) for o in exe.run(
        main, feed=feed, fetch_list=list(fetches), scope=scope)], scope


# ---------------------------------------------------------------------------
# rope: interleaved pairs, YaRN's table
# ---------------------------------------------------------------------------

def _written_out_table(d, base, factor, original_max, beta_fast, beta_slow):
    """YaRN's frequencies, one at a time, by hand."""
    f = []
    lo = max(np.floor(d * np.log(original_max / (beta_fast * 2 * np.pi))
                      / (2 * np.log(base))), 0)
    hi = min(np.ceil(d * np.log(original_max / (beta_slow * 2 * np.pi))
                     / (2 * np.log(base))), d - 1)
    for i in range(d // 2):
        plain = base ** (-2 * i / d)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        keep = 1.0 - ramp
        f.append(plain / factor * (1 - keep) + plain * keep)
    return np.asarray(f)


@pytest.mark.parametrize("d,base,original_max", [(8, 1e5, 64),
                                                 (64, 1e5, 32768)])
def test_yarn_table_is_the_written_out_one(d, base, original_max):
    from paddle_tpu.ops.rope_ops import yarn_inv_freq

    plain = 1.0 / (base ** (np.arange(0, d // 2) / (d // 2)))
    got = yarn_inv_freq(plain, base, d, 8.0, original_max, 32.0, 1.0)
    want = _written_out_table(d, base, 8.0, original_max, 32.0, 1.0)
    assert np.allclose(got, want, rtol=1e-12)
    # the fastest pair is kept, the slowest divided by the factor
    assert got[0] == plain[0] and np.isclose(got[-1], plain[-1] / 8.0)
    if d == 64:
        cfg = dict(qk_rope_head_dim=64, rope_theta=1e5, rope_scaling=dict(
            YARN, original_max_position_embeddings=32768))
        assert np.allclose(REF.yarn_frequencies(cfg), want, rtol=1e-12)
        assert 0 < (got != plain).sum() < 32      # a ramp, not a step


def test_interleaved_rope_rotates_pairs_where_they_lie():
    yarn = {"factor": 8.0, "original_max": 64, "beta_fast": 32,
            "beta_slow": 1}
    x = np.random.default_rng(0).normal(size=(2, 3, 12, 8)).astype("float32")
    (got,), _ = _run(lambda: [layers.rope(
        layers.data("x", [2, 3, 12, 8], append_batch_size=False),
        base=1e5, interleave=True, yarn=yarn)], {"x": x})
    f = _written_out_table(8, 1e5, 8.0, 64, 32, 1)
    ang = np.arange(12)[:, None] * f[None, :]                 # [S, 4]
    want = np.empty_like(x)
    want[..., 0::2] = x[..., 0::2] * np.cos(ang) - x[..., 1::2] * np.sin(ang)
    want[..., 1::2] = x[..., 1::2] * np.cos(ang) + x[..., 0::2] * np.sin(ang)
    assert np.abs(got - want).max() < 1e-5
    # norms of pairs are kept, position 0 is the identity
    assert np.allclose(got[:, :, 0], x[:, :, 0], atol=1e-7)


def test_decode_position_p_is_bit_equal_to_prefill_position_p():
    yarn = {"factor": 8.0, "original_max": 64, "beta_fast": 32,
            "beta_slow": 1}
    x = np.random.default_rng(1).normal(size=(3, 2, 40, 8)).astype("float32")
    (whole,), _ = _run(lambda: [layers.rope(
        layers.data("x", [3, 2, 40, 8], append_batch_size=False),
        base=1e5, interleave=True, yarn=yarn)], {"x": x})
    pos = np.asarray([0, 17, 39], "int32")
    one = np.stack([x[b, :, p:p + 1] for b, p in enumerate(pos)])

    def step():
        return [layers.rope(
            layers.data("x", [3, 2, 1, 8], append_batch_size=False),
            base=1e5, interleave=True, yarn=yarn,
            offset=layers.data("pos", [3], dtype="int32",
                               append_batch_size=False))]

    (got,), _ = _run(step, {"x": one, "pos": pos})
    for b, p in enumerate(pos):
        assert np.array_equal(got[b, :, 0], whole[b, :, p])


# ---------------------------------------------------------------------------
# latent attention: absorbed against expanded, the kernels, garbage pages
# ---------------------------------------------------------------------------

H, C, DN, DR, DV = 8, 128, 16, 8, 16


def _latent_case(seed, lengths, np_slot=4, pt_=8, garbage=np.nan):
    """Rows [c_kv | k_r] of ``len(lengths)`` slots in a pool whose other
    bytes are ``garbage``, behind permuted tables."""
    from paddle_tpu.ops.latent_attention_ops import latent_pool_shape

    rng = np.random.default_rng(seed)
    B = len(lengths)
    P = B * np_slot + 1
    row = latent_pool_shape(P, pt_, C, DR)[-1]
    rows = rng.normal(size=(B, np_slot * pt_, C + DR)).astype("float32")
    table = (rng.permutation(P - 1)[:B * np_slot] + 1).reshape(B, np_slot)
    pool = np.full((P, 1, pt_, row), garbage, "float32")
    for b, n in enumerate(lengths):
        for j in range(n):
            pool[table[b, j // pt_], 0, j % pt_] = 0.0
            pool[table[b, j // pt_], 0, j % pt_, :C + DR] = rows[b, j]
    w = (rng.normal(size=(C, H * (DN + DV))) * C ** -0.5).astype("float32")
    q_nope = rng.normal(size=(B, H, 1, DN)).astype("float32")
    q_rope = rng.normal(size=(B, H, 1, DR)).astype("float32")
    return rows, pool, table.astype("int32"), w, q_nope, q_rope


def _expanded(rows, n, w, q_nope, q_rope, scale):
    """The last row of the EXPANDED attention over a slot's first ``n``
    rows: keys and values of every head made from the latent."""
    c_kv, k_r = rows[:n, :C], rows[:n, C:]
    kv = (c_kv.astype("float64") @ w.astype("float64")).reshape(
        n, H, DN + DV)
    s = (np.einsum("hd,nhd->hn", q_nope[:, 0].astype("float64"),
                   kv[..., :DN])
         + np.einsum("hr,nr->hn", q_rope[:, 0].astype("float64"), k_r)) \
        * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hn,nhd->hd", p, kv[..., DN:])


@pytest.mark.parametrize("lengths", [[1, 8, 9, 32], [17, 1, 24]])
def test_absorbed_decode_is_the_expanded_attention_on_the_same_rows(
        lengths):
    """The op the decode step runs (``q_lat = q_nope W_UK^T`` over the
    cached rows, ``o = o_lat W_UV``) against keys and values expanded from
    the same rows, slot by slot; the pool's other bytes are NaN (a
    recycled page's garbage, the tails of live pages) and reach nothing."""
    rows, pool, table, w, q_nope, q_rope = _latent_case(5, lengths)
    B = len(lengths)
    pos = np.asarray(lengths, "int32") - 1
    scale = 0.37

    def build():
        d = lambda n, s, t="float32": layers.data(  # noqa: E731
            n, list(s), dtype=t, append_batch_size=False)
        return [layers.latent_decode_attention(
            d("qn", q_nope.shape), d("qr", q_rope.shape), d("w", w.shape),
            d("pool", pool.shape), d("bt", table.shape, "int32"),
            d("pos", pos.shape, "int32"), scale, DV)]

    ref0 = stat_get("attention_lowered_latent_decode_reference")
    (got,), _ = _run(build, {"qn": q_nope, "qr": q_rope, "w": w,
                             "pool": pool, "bt": table, "pos": pos})
    assert stat_get("attention_lowered_latent_decode_reference") == ref0 + 1
    assert got.shape == (B, H, 1, DV) and np.isfinite(got).all()
    for b, n in enumerate(lengths):
        want = _expanded(rows[b], n, w, q_nope[b], q_rope[b], scale)
        assert np.abs(got[b, :, 0] - want).max() < 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("lengths,pt_,np_slot", [
    ([1, 8, 9, 32], 8, 4), ([130, 1, 256, 77], 16, 16)])
def test_decode_kernel_reads_live_rows_only(lengths, pt_, np_slot):
    """``mla_decode_attention`` under interpret mode against the einsums:
    lengths of 1, a page, a page and a row, a whole slot, more than one
    granule; NaN in the trash page, in pages no slot owns and behind every
    live length."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import latent_attention as la

    rows, pool, table, _, _, _ = _latent_case(7, lengths, np_slot, pt_)
    B = len(lengths)
    rng = np.random.default_rng(8)
    q = rng.normal(size=(B, H, C + DR)).astype("float32")
    row = pool.shape[-1]
    q_row = np.pad(q, ((0, 0), (0, 0), (0, row - C - DR)))
    pos = np.asarray(lengths, "int32") - 1
    got = np.asarray(la.mla_decode_attention(
        jnp.asarray(q_row), jnp.asarray(pool), jnp.asarray(table),
        jnp.asarray(pos), scale=0.2, value_dim=C, interpret=True))
    assert np.isfinite(got).all()
    for b, n in enumerate(lengths):
        s = q[b].astype("float64") @ rows[b, :n].T.astype("float64") * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[b, :n, :C]
        assert np.abs(got[b] - want).max() < 1e-5 * np.abs(want).max()
    assert la.decode_supported(H, pool.shape, C)
    assert not la.decode_supported(H, (9, 2, pt_, row), C)
    del jax


@pytest.mark.parametrize("S", [32, 256, 384])
def test_prefill_kernel_takes_keys_wider_than_values(S):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import latent_attention as la

    rng = np.random.default_rng(S)
    q, k = (rng.normal(size=(1, 3, S, DN + DR)).astype("float32")
            for _ in range(2))
    v = rng.normal(size=(1, 3, S, 128)).astype("float32")
    got = np.asarray(la.mla_prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), scale=0.3,
        interpret=True))
    s = np.einsum("bhqd,bhkd->bhqk", q.astype("float64"), k) * 0.3
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)
    assert got.shape == (1, 3, S, 128)
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_prefill_op_is_the_plain_causal_attention():
    rng = np.random.default_rng(3)
    q, k = (rng.normal(size=(2, 4, 20, 24)).astype("float32")
            for _ in range(2))
    v = rng.normal(size=(2, 4, 20, 16)).astype("float32")

    def build():
        d = lambda n, a: layers.data(  # noqa: E731
            n, list(a.shape), append_batch_size=False)
        return [layers.latent_prefill_attention(d("q", q), d("k", k),
                                                d("v", v), 0.25)]

    (got,), _ = _run(build, {"q": q, "k": k, "v": v})
    s = np.einsum("bhqd,bhkd->bhqk", q.astype("float64"), k) * 0.25
    s = np.where(np.tril(np.ones((20, 20), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)
    assert got.shape == (2, 4, 20, 16)
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


# ---------------------------------------------------------------------------
# the delta mixer at two value heads a key head, the clamp, the norms
# ---------------------------------------------------------------------------

def _delta_inputs(seed, B, T, hk, hv, d):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.normal(size=(B, T, hk, d))).astype("float32") * d ** -0.5
    k = unit(rng.normal(size=(B, T, hk, d))).astype("float32")
    v = rng.normal(size=(B, T, hv, d)).astype("float32")
    g = -np.abs(rng.normal(size=(B, T, hv))).astype("float32") * 0.3
    beta = (1 / (1 + np.exp(-rng.normal(size=(B, T, hv))))).astype("float32")
    return q, k, v, g, beta


@pytest.mark.parametrize("T", [1, 70, 128])
def test_two_value_heads_a_key_head_is_the_recurrence(T):
    """What the mixer hands the ops at ``value_heads = 2 x key_heads`` (key
    head j repeated for value heads 2j and 2j + 1) is the reference's
    recurrence: the chunked op over a sequence, then the step op from the
    state it left."""
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_ops as gd

    hk, hv, d = 2, 4, 16
    q, k, v, g, beta = _delta_inputs(T, 1, T + 1, hk, hv, d)
    rq, rk = np.repeat(q, 2, axis=2), np.repeat(k, 2, axis=2)
    want = np.asarray(REF.delta_rule(*(jnp.asarray(t[0]) for t in (
        rq, rk, v, g, beta))))                               # [T + 1, hv, d]
    o, state = gd.chunked(*(jnp.asarray(t[:, :T]) for t in (
        rq, rk, v, g, beta)))
    assert np.abs(np.asarray(o)[0] - want[:T]).max() \
        < 2e-5 * np.abs(want).max()
    full = jnp.concatenate([state, jnp.zeros_like(state)], axis=0)
    o1, _ = gd.step(*(jnp.asarray(t[:, T]) for t in (rq, rk, v, g, beta)),
                    full, jnp.asarray([True]))
    assert np.abs(np.asarray(o1)[0] - want[T]).max() \
        < 2e-5 * np.abs(want).max()
    # value heads 2j and 2j + 1 share a key head and differ all the same
    assert np.abs(want[:, 0] - want[:, 1]).max() > 1e-3


def test_delta_dims_and_cache_spec_at_value_heads_over_key_heads():
    from paddle_tpu.models.llama import _delta_dims, cache_spec

    model = BUILDER.model_args(_cfg())
    delta = model["layer_pattern"][0]["mixer"]
    assert _delta_dims(delta) == (4, 16, 16, 2 * 2 * 16 + 4 * 16, 2)
    with pytest.raises(ValueError, match="multiple of key heads"):
        _delta_dims(dict(delta, value_heads=5))
    spec = cache_spec("llama", 5, model["layer_pattern"], num_slots=5,
                      num_pages=9, page_tokens=PAGE, num_kv_heads=8,
                      head_dim=24, hidden=64)
    by_layer = {i: [e for e in spec if e["layer"] == i] for i in range(5)}
    assert [(e["name"], e["kind"], e["shape"]) for e in by_layer[1]] == [
        ("llama.pool_c_1", "latent_pages", [9, 1, PAGE, 128])]
    for i in (0, 2, 3, 4):
        assert [(e["name"], e["shape"]) for e in by_layer[i]] == [
            (f"llama.conv_state_{i}", [6, 3, 128]),
            (f"llama.delta_state_{i}", [6, 4, 16, 16])]
    with pytest.raises(ValueError, match="sliding window"):
        cache_spec("llama", 1, [dict(model["layer_pattern"][1], window=16)],
                   num_slots=1, num_pages=3, page_tokens=PAGE,
                   num_kv_heads=8, head_dim=24, hidden=64,
                   num_window_pages=3)


@pytest.mark.parametrize("limit", [None, 10.0])
def test_the_clamp_holds_inputs_past_ten(limit):
    """Inputs scaled until ``W_1 h`` and ``W_3 h`` pass 10: the dense
    SwiGLU and the experts' are the reference's clamped form, and differ
    from the unclamped one."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.llama import _swiglu
    from paddle_tpu.parallel.moe import _gated

    rng = np.random.default_rng(2)
    h = (rng.normal(size=(1, 6, 16)) * 30).astype("float32")

    def build():
        x = layers.data("h", [1, 6, 16], append_batch_size=False)
        kw = {} if limit is None else {"limit": limit}
        return [_swiglu(x, 16, 24, "t.gate_up.w", "t.ffn_out.w", **kw)]

    (got,), scope = _run(build, {"h": h})
    gu, down = (np.asarray(scope.find_var(n))
                for n in ("t.gate_up.w", "t.ffn_out.w"))
    assert np.abs(h[0] @ gu).max() > 10
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF._swiglu(jnp.asarray(h[0]), gu, down, limit))
        free = np.asarray(REF._swiglu(jnp.asarray(h[0]), gu, down, None))
    assert np.abs(got[0] - want).max() < 1e-5 * np.abs(want).max()
    assert (np.abs(want - free).max() > 0.05 * np.abs(free).max()) \
        == (limit is not None)
    x = jnp.asarray(h[0] @ gu)
    act = np.asarray(_gated(x, 24, "silu", limit))
    gate = x[:, :24] if limit is None else jnp.minimum(x[:, :24], limit)
    up = x[:, 24:] if limit is None else jnp.clip(x[:, 24:], -limit, limit)
    assert np.allclose(act, np.asarray(jax.nn.silu(gate) * up), rtol=1e-6)


def test_norm_layouts_are_refused_by_name():
    from paddle_tpu.models.llama import _norm_modes

    assert [_norm_modes(n) for n in ("pre", "post", "pre_post")] \
        == [(True, False), (False, True), (True, True)]
    with pytest.raises(ValueError, match="pre_post"):
        _norm_modes("sandwich")


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------

E_ROUTER, SHARES, TOP_K, HID, WIDTH = 256, 32, 8, 32, 16


def _layer(seed, n=48):
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return (scale * rng.normal(size=shape)).astype("float32")

    p = {"router": draw(HID, E_ROUTER, scale=HID ** -0.5),
         "bias": draw(E_ROUTER, scale=0.02),
         "gate_up": draw(E_ROUTER, HID, 2 * WIDTH, scale=3 * HID ** -0.5),
         "down": draw(E_ROUTER, WIDTH, HID, scale=WIDTH ** -0.5),
         "shared_gate_up": draw(HID, 2 * WIDTH, scale=3 * HID ** -0.5),
         "shared_down": draw(WIDTH, HID, scale=WIDTH ** -0.5)}
    cfg = {"num_experts_per_tok": TOP_K, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5, "n_shared_experts": 1,
           "swiglu_limit": 10}
    return p, cfg, draw(n, HID, scale=3.0)


def test_thirty_two_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Each of 32 chips routes over all 256, multiplies the pairs of its
    own 8 experts (weights over all 8 chosen, times 2.5, the clamp on),
    and the thirty-two parts, with the shared expert counted once, are the
    uncut reference layer."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_routed_tokens

    p, cfg, h = _layer(3)
    with jax.default_matmul_precision("highest"):
        whole, logits, _ = REF.ffn(jnp.asarray(h), p, cfg, (0, E_ROUTER))
        shared = REF._swiglu(jnp.asarray(h), p["shared_gate_up"],
                             p["shared_down"], 10)
    whole = np.asarray(whole)
    s = 1 / (1 + np.exp(-np.asarray(logits, "float64")))
    chosen = np.argsort(-(s + p["bias"]), axis=-1, kind="stable")[:, :TOP_K]
    held = E_ROUTER // SHARES
    parts, pairs = [], 0
    for rank in range(SHARES):
        first = rank * held
        mine = dict(p, gate_up=p["gate_up"][first:first + held],
                    down=p["down"][first:first + held])
        out, counts, _ = moe_routed_tokens(
            jnp.asarray(h), jnp.asarray(h), mine["router"], mine["gate_up"],
            mine["down"], top_k=TOP_K, activation="silu",
            precision=jax.lax.Precision.HIGHEST, score="sigmoid",
            expert_bias=mine["bias"], route_scale=2.5, held_first=first,
            limit=10.0)
        counts = np.asarray(counts)
        assert counts.shape == (E_ROUTER,) and counts.sum() == len(h) * TOP_K
        here = int(((chosen >= first) & (chosen < first + held)).sum())
        assert counts[first:first + held].sum() == here
        pairs += here
        with jax.default_matmul_precision("highest"):
            want, _, _ = REF.ffn(jnp.asarray(h), mine, cfg, (first, held),
                                 shared=False)
        assert np.abs(np.asarray(out) - np.asarray(want)).max() \
            < 1e-5 * np.abs(whole).max()
        parts.append(np.asarray(out))
    assert pairs == len(h) * TOP_K           # every pair lives on one chip
    total = np.sum(parts, axis=0) + np.asarray(shared)
    assert np.abs(total - whole).max() < 1e-5 * np.abs(whole).max()
    assert np.abs(parts[0] + np.asarray(shared) - whole).max() \
        > 0.1 * np.abs(whole).max()


# ---------------------------------------------------------------------------
# the model through the engine
# ---------------------------------------------------------------------------

def test_prefill_then_cached_decode_in_a_reused_slot_between_neighbours():
    """Slots 0 and 1 decode all the while; slot 2 serves a request, is
    left, and takes the compared ones: the paged prefill (expanded path)
    and eight cached decode steps (absorbed path) are the reference's full
    forward, logits not tokens, and so is a prompt of more than two
    chunks.  The engine books what the share and the latent pool did."""
    cfg = _cfg()
    eng = _engine(cfg)
    try:
        sides = [eng.submit(_prompt(50 + i, 9 + i), 60) for i in range(2)]
        first = eng.submit(_prompt(52, 30), 6)
        assert first.result(300)["slot"] == 2
        res = {}
        for n in (5, 150):
            prompt = _prompt(60 + n, n)
            r = eng.generate(prompt, 9, timeout=300)
            assert r["slot"] == 2
            res[n] = (prompt, r)
        rest = [f.result(300) for f in sides]
        stats = eng.stats()
    finally:
        eng.close()
    counters = stats["counters"]
    assert [r["slot"] for r in rest] == [0, 1]
    for prompt, r in res.values():
        assert len(r["logits"]) == 9
        # one router row an EXPERT layer: the dense layer has none
        assert np.stack(r["router_logits"]).shape == (9, 4, 16)
        assert _off_reference(eng, cfg, prompt, r) < TOL
    for f, r in zip((50, 51), rest):
        assert _off_reference(eng, cfg, _prompt(f, 9 + f - 50), r) < TOL
    assert eng.cache_names == ["llama.pool_c_1"]
    assert stats["paged"]["latent_layers"] == 1
    assert stats["paged"]["page_bytes"] == PAGE * 128 * 4
    assert stats["paged"]["pages_live"] == 0          # slots left
    assert counters["slot_state_writes"] == 5
    assert counters["delta_state_steps"] % 4 == 0
    assert counters["moe_tokens_dropped"] == 0
    assert 0.1 < counters["moe_pairs_held"] / counters["moe_pairs_routed"] \
        < 0.45
    assert counters["moe_shared_expert_rows"] * 3 \
        == counters["moe_pairs_routed"]


def test_latent_pool_holds_the_rows_a_prefill_and_steps_wrote():
    """The pool read back row for row: a prefill's whole pages and four
    steps' single rows are ``[c_kv | k_r | 0]`` of the reference (post-norm,
    post-RoPE) at the pages the block table named."""
    import jax
    import jax.numpy as jnp

    cfg = _cfg()
    eng = _engine(cfg, num_slots=2)
    try:
        prompt = _prompt(5, 19)
        res = eng.generate(prompt, 5, timeout=300)
        assert res["slot"] == 0
        pool = np.asarray(eng.scope.find_var("llama.pool_c_1"))
        # (slot 0 claimed the lowest free pages, in order)
        table = np.arange(1, 1 + eng.pages_per_slot)
    finally:
        eng.close()
    seq = prompt + res["tokens"][:-1]                 # rows 0 .. 22
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(params["embed"]))[jnp.asarray(seq)]
        p0, p1 = params["dense"][0], params["layers"][0]
        x = x + REF._norm(REF._delta(REF._norm(x, p0["ln1"], 1e-6), p0, cfg,
                                     1e-6), p0["ln1_post"], 1e-6)
        x = x + REF._norm(REF._swiglu(REF._norm(x, p0["ln2"], 1e-6),
                                      p0["gate_up"], p0["down"], 10),
                          p0["ln2_post"], 1e-6)
        h = REF._norm(x, p1["ln1"], 1e-6)
        kv_a = h @ p1["kv_a"]
        c_kv = REF._norm(kv_a[:, :32], p1["kv_a_norm"], 1e-6)
        ang = jnp.arange(len(seq), dtype=jnp.float32)[:, None] \
            * jnp.asarray(REF.yarn_frequencies(cfg), jnp.float32)[None]
        k_r = REF._rotate_pairs(kv_a[:, 32:], jnp.cos(ang), jnp.sin(ang))
    want = np.concatenate([np.asarray(c_kv), np.asarray(k_r)], axis=1)
    got = pool[table, 0].reshape(-1, 128)[:len(seq)]
    assert np.abs(got[:, :40] - want).max() < 1e-5 * np.abs(want).max()
    assert not got[:, 40:].any()                      # the lanes' padding
    assert len(seq) == 23 and np.abs(got[19:, :40]).max() > 0   # the steps'


def test_a_recycled_pages_garbage_does_not_reach_the_output():
    """A slot is served and left, its pages and the trash page are filled
    with NaN, and the next request, which takes the same pages, is still
    the reference's."""
    cfg = _cfg()
    eng = _engine(cfg, num_slots=2)
    try:
        eng.generate(_prompt(1, 40), 4, timeout=300)
        pool = np.asarray(eng.scope.find_var("llama.pool_c_1")).copy()
        pool[:] = np.nan
        import jax.numpy as jnp

        eng.scope.set_var("llama.pool_c_1", jnp.asarray(pool))
        prompt = _prompt(2, 11)
        res = eng.generate(prompt, 9, timeout=300)
    finally:
        eng.close()
    assert _off_reference(eng, cfg, prompt, res) < TOL


def test_spans_say_what_the_latent_pool_the_share_and_the_scan_did():
    from paddle_tpu import telemetry

    eng = _engine()
    try:
        eng.generate(_prompt(41, 70), 4, timeout=300)
        spans = [s for s in telemetry.get_spans() if s.end is not None]
        gauge = telemetry.metrics.gauge("serving_latent_pages_live").get()
    finally:
        eng.close()
    prefill = [s for s in spans if s.name == "generation/prefill"][-1]
    assert (prefill.attrs["scan_tokens"], prefill.attrs["scan_chunks"],
            prefill.attrs["scan_pad_chunks"], prefill.attrs["state_written"],
            prefill.attrs["latent_rows_written"]) == (70, 3, 1, 1, 70)
    fetch = [s for s in spans if s.name == "generation/prefill_fetch"][-1]
    assert fetch.attrs["pairs_routed"] == 4 * 70 * 3
    assert 0 < fetch.attrs["pairs_held"] < fetch.attrs["pairs_routed"]
    steps = [s for s in spans if s.name == "generation/decode_step"
             and "latent_positions" in s.attrs]
    assert steps
    for s in steps[-3:]:
        assert s.attrs["state_slots"] == 1 and s.attrs["pairs_routed"] == 12
        assert 71 <= s.attrs["latent_positions"] \
            == s.attrs["live_positions"] <= 74
        assert 0 <= s.attrs["experts_held_touched"] <= 3
    assert gauge >= 0


def test_the_programs_book_their_lowerings():
    """Per program build: the decode step books the absorbed attention's
    reference formulation off the chip, a prefill writes the ONE latent
    pool page by page."""
    names = ("attention_lowered_latent_decode",
             "attention_lowered_latent_decode_reference",
             "attention_lowered_latent_prefill", "kv_pool_write_pages",
             "kv_pool_write_rows", "gated_delta_lowered_reference")
    before = {n: stat_get(n) for n in names}
    eng = _engine(prefill_buckets=[8, 32])
    try:
        eng.warmup()
    finally:
        eng.close()
    grew = {n: stat_get(n) - before[n] for n in names}
    assert grew == {"attention_lowered_latent_decode": 0,
                    "attention_lowered_latent_decode_reference": 1,
                    "attention_lowered_latent_prefill": 0,
                    "kv_pool_write_pages": 2, "kv_pool_write_rows": 0,
                    "gated_delta_lowered_reference": 4 * 3}


@pytest.mark.parametrize("kw,reason", [
    ({"prefix_reuse": True}, "prefix_reuse"),
    ({"prefill_chunk": 8}, "prefill_chunk > 0"),
    ({"speculate": True}, "speculate"),
    ({"role": "prefill"}, "KV-segment handoff"),
    ({"role": "decode"}, "KV-segment handoff"),
])
def test_what_walks_pages_only_is_refused_beside_latent_pages_too(kw,
                                                                  reason):
    with pytest.raises(ValueError, match="slot state") as e:
        _engine(**kw)
    assert reason in str(e.value)


def test_a_chunk_program_over_latent_pages_is_built_since_pr_56():
    """The latent layer alone has a chunk program (``latent_chunk_attention``
    over its one pool; ``tests/test_deepseek_v2.py`` runs it); beside the
    delta layers' slot state it stays refused."""
    from paddle_tpu.models.llama import build_llama_prefill_chunk

    model = BUILDER.model_args(_cfg())
    only_latent = dict(model, num_layers=1,
                       layer_pattern=[model["layer_pattern"][1]])
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        _, _, caches = build_llama_prefill_chunk(8, 64, 9, PAGE,
                                                 name="llama", **only_latent)
        with pytest.raises(ValueError, match="slot state"):
            build_llama_prefill_chunk(8, 64, 9, PAGE, name="llama", **model)
    assert caches == ["llama.pool_c_0"]
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("latent_chunk_attention") == 1
    assert "latent_decode_attention" not in ops


def test_the_balanced_bias_evens_the_loads_and_leaves_the_weights():
    """``gigachat35_engine.balance_bias``: from the seeded draw, the
    family's sign rule over a sample's router logits; on a FRESH sample of
    the same weights the fullest expert's load falls, the engine is
    still the reference's, and nothing of the small engine is left in the
    scope."""
    cal = {"requests": 12, "rung": 32, "new_tokens": 17, "slots": 3,
           "positions": 64, "page_tokens": PAGE, "step": 0.02,
           "decay": 0.98, "iterations": 200}
    cfg = _cfg(assumed={"expert_bias_scale": 0.02, "eos_id": -1,
                        "bias_balance": cal})
    plain = _cfg()
    first = _engine(plain)            # the draw at 0.02 alone
    first.close()                     # (one engine a scope at a time)
    scope = first.scope
    scope.erase(list(first.cache_names) + list(first.state_names))
    names = set(scope.local_var_names())
    drawn = np.asarray(scope.find_var("llama.blk1.moe.expert_bias"))

    def fullest(seed):
        s = 1 / (1 + np.exp(-BUILDER.router_sample(scope, cfg, seed)))
        bias = np.stack([np.asarray(scope.find_var(
            f"llama.blk{i}.moe.expert_bias")) for i in range(1, 5)])
        chosen = np.argsort(-(s + bias[None]), axis=-1)[..., :3]
        return max(np.bincount(chosen[:, j].ravel(), minlength=16).max()
                   / (chosen.shape[0] * 3 / 16) for j in range(4))

    before = fullest(991)
    BUILDER.balance_bias(scope, cfg, 11)
    after = fullest(991)              # a sample the rule never saw
    moved = np.asarray(scope.find_var("llama.blk1.moe.expert_bias"))
    assert set(scope.local_var_names()) == names
    eng = _engine(plain, scope=scope)
    try:
        prompt = _prompt(78, 21)
        res = eng.generate(prompt, 9, timeout=300)
    finally:
        eng.close()
    assert after < 0.9 * before, (before, after)
    assert np.abs(moved - drawn).max() > 0.01
    assert _off_reference(eng, plain, prompt, res) < TOL


def test_the_uncut_layer_runs_through_the_same_program():
    """``held`` covering every expert of the router is the uncut model:
    the reference given all 16 agrees."""
    cfg = _cfg(n_routed_experts=16,
               expert_share={"router_experts": 16, "first": 0})
    eng = _engine(cfg)
    try:
        prompt = _prompt(77, 40)
        res = eng.generate(prompt, 5, timeout=300)
        counters = eng.stats()["counters"]
    finally:
        eng.close()
    assert _off_reference(eng, cfg, prompt, res) < TOL
    assert counters["moe_pairs_held"] == counters["moe_pairs_routed"]
