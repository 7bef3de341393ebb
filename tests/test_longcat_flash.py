"""``longcat-flash-chat`` at a small size (PR 66): shortcut-connected
layers (two latent-attention sublayers, two dense SwiGLUs and ONE expert
branch that leaves behind the first sublayer and joins behind the second
dense SwiGLU) under a router whose last outputs are identity experts.

* **The router** (``parallel/moe.py`` ``route_top_k``: "softmax" with a
  selection bias) against a written-out loop: the k largest of ``softmax +
  bias``, weighed by the UNBIASED softmax over all outputs, not
  renormalised, times 6; the bias moves the choice and never the weights.
* **Identity experts** (``moe_routed_tokens(zero_experts=)``): a router
  forced to pick only identity experts gives ``(sum w) u`` and runs no
  expert product (the expert matrices are NaN); one forced to pick only
  real experts is today's ``moe_routed_tokens``; rows behind ``valid`` add
  nothing; the shares of a router of E + Z add up, the identity term and
  what every chip computes alike counted once, to the uncut reference
  layer; the eight head shares of a latent sublayer add up to the uncut
  sublayer.
* **The block** (``models/llama.py`` layer-pattern ``branch`` / ``join``,
  the two MLA scales): the whole-prompt forward and a chunked prefill over
  latent pages then absorbed decode steps through the engine against the
  plain reference, logits not tokens; the reference with the join moved
  before the second sublayer, or dropped, is far off; what the pattern
  refuses.
* **The engine** (``serving/generation.py``): the spans' routed pairs split
  into held, absent and identity; the builder refuses a program without
  the mechanisms.
"""
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
TOL = 2.0 ** -10          # of the logits' range; float32 reads 1e-6 here


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "lcf_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "longcat-flash-chat")
BUILDER = _load("builders", "longcat_flash_engine")


def _cfg(**over):
    """The published keys at a toy size: hidden 64; two published layers,
    each two latent sublayers (4 heads of nope 16 + rope 8 over a latent of
    32, values of 16, query rank 16: the inner norms' scales are 2 and
    1.414) and two dense SwiGLUs of 96; a router of 24 outputs of which the
    last 8 are identity experts, 5 picks a token times 6, not renormalised;
    real experts 4..7 of 16 are held, of width 32."""
    cfg = {"attention_bias": False, "vocab_size": 97, "hidden_size": 64,
           "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
           "num_layers": 2, "num_attention_heads": 4, "kv_lora_rank": 32,
           "q_lora_rank": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "qk_nope_head_dim": 16, "mla_scale_q_lora": True,
           "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
           "n_routed_experts": 4, "rms_norm_eps": 1e-5, "rope_theta": 1e7,
           "attention_method": "MLA", "zero_expert_num": 8,
           "zero_expert_type": "identity", "moe_topk": 5,
           "num_experts_per_tok": 5,
           "expert_share": {"router_experts": 24, "zero_experts": 8,
                            "first": 4},
           "assumed": {"eos_id": -1, "expert_bias_seed": 7,
                       "expert_bias_scale": 0.02},
           "check_tolerance": {
               "near_tie_margin_share_of_router_range": 4e-4}}
    cfg.update(over)
    return cfg


def _engine(cfg=None, **kw):
    from paddle_tpu.serving import GenerationEngine

    cfg = cfg or _cfg()
    args = dict(num_slots=3, max_seq_len=256, prefill_buckets=[8, 16, 32],
                page_tokens=PAGE, attn_impl="xla", keep_logits=True,
                prefill_chunk=32, prefix_reuse=False, speculate=False,
                eos_id=-1, deadline_ms=600000)
    args.update(kw)
    eng = GenerationEngine(BUILDER.model_args(cfg), **args)
    if "scope" not in kw:
        BUILDER.seed_expert_bias(eng.scope, cfg)
    return eng


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 97, n).tolist()


def _off_reference(eng, cfg, prompt, res, **how):
    """How far a result's logits lie off the reference's single forward
    over prompt plus generated tokens, as a share of its range."""
    n, new = len(prompt), len(res["tokens"])
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    seq = np.asarray(prompt + res["tokens"], "int32")
    want = np.asarray(REF.forward(params, seq, cfg,
                                  np.arange(n - 1, n - 1 + new), **how))
    got = np.stack(res["logits"])
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.abs(got - want).max() / np.abs(want).max())


def _rel(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.abs(np.asarray(want)).max())


def _layer(seed=5, n=70, hid=32, inter=8, real=16, zero=8):
    """Rows and one branch's parameters: a router of ``real + zero``
    outputs with a bias, ``real`` experts."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(n, hid)), jnp.float32)
    p = {"router": jnp.asarray(rng.normal(size=(hid, real + zero)) * 0.5,
                               jnp.float32),
         "bias": jnp.asarray(rng.normal(size=(real + zero,)) * 0.02,
                             jnp.float32),
         "gate_up": jnp.asarray(rng.normal(size=(real, hid, 2 * inter)) * 0.2,
                                jnp.float32),
         "down": jnp.asarray(rng.normal(size=(real, inter, hid)) * 0.2,
                             jnp.float32)}
    return u, p


def _routed(u, p, zero, held=None, bias=None, valid=None, top_k=5, **kw):
    """``moe_routed_tokens`` as the configuration runs it."""
    import jax

    from paddle_tpu.parallel.moe import moe_routed_tokens

    first, count = held or (0, p["gate_up"].shape[0])
    args = dict(top_k=top_k, activation="silu",
                precision=jax.lax.Precision.HIGHEST, norm_topk=False,
                route_scale=6.0, zero_experts=zero, valid=valid,
                expert_bias=p["bias"] if bias is None else bias,
                held_first=None if held is None else first)
    args.update(kw)
    return moe_routed_tokens(
        u, u, p["router"], p["gate_up"][first:first + count],
        p["down"][first:first + count], **args)


# ---------------------------------------------------------------------------
# the router: softmax + bias chooses, the unbiased softmax weighs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_biased_softmax_routing_is_the_written_out_rule(seed):
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import route_top_k

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(40, 16)).astype("float32")
    w = (rng.normal(size=(16, 24)) * 0.7).astype("float32")
    bias = (rng.normal(size=(24,)) * 0.03).astype("float32")
    logits, experts, weights = route_top_k(
        jnp.asarray(x), jnp.asarray(w), 5, "softmax", jnp.asarray(bias),
        norm_topk=False, route_scale=6.0)
    want_logits = x.astype("float64") @ w.astype("float64")
    assert np.abs(np.asarray(logits) - want_logits).max() < 1e-5
    moved = 0
    for r in range(40):
        e = np.exp(want_logits[r] - want_logits[r].max())
        prob = e / e.sum()
        chosen = np.argsort(-(prob + bias), kind="stable")[:5]
        assert np.asarray(experts)[r].tolist() == chosen.tolist()
        assert np.allclose(np.asarray(weights)[r], 6.0 * prob[chosen],
                           rtol=1e-5)
        moved += set(chosen) != set(np.argsort(-prob, kind="stable")[:5])
    assert 0 < moved < 40          # the bias moves some choices, not all


def test_the_bias_moves_the_choice_and_never_the_weights():
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import route_top_k

    logits = np.array([[3.0, 2.0, 1.0, 0.9, 0.0, -1.0]], "float32")
    eye = jnp.eye(6, dtype=jnp.float32)
    prob = np.exp(logits[0]) / np.exp(logits[0]).sum()
    bias = np.zeros(6, "float32")
    bias[3] = 0.05                     # lifts expert 3 over expert 2
    _, plain, w_plain = route_top_k(jnp.asarray(logits), eye, 3, "softmax",
                                    jnp.zeros(6), norm_topk=False)
    _, moved, w_moved = route_top_k(jnp.asarray(logits), eye, 3, "softmax",
                                    jnp.asarray(bias), norm_topk=False)
    assert np.asarray(plain)[0].tolist() == [0, 1, 2]
    assert np.asarray(moved)[0].tolist() == [0, 1, 3]
    assert np.allclose(np.asarray(w_plain)[0], prob[[0, 1, 2]], rtol=1e-6)
    assert np.allclose(np.asarray(w_moved)[0], prob[[0, 1, 3]], rtol=1e-6)
    # renormalised over the chosen where the configuration says so
    _, _, w_norm = route_top_k(jnp.asarray(logits), eye, 3, "softmax",
                               jnp.asarray(bias), norm_topk=True)
    assert np.allclose(np.asarray(w_norm)[0],
                       prob[[0, 1, 3]] / prob[[0, 1, 3]].sum(), rtol=1e-6)


# ---------------------------------------------------------------------------
# identity experts
# ---------------------------------------------------------------------------

def test_a_branch_is_the_references_written_out_sum():
    import jax

    u, p = _layer()
    cfg = _cfg(n_routed_experts=16,
               expert_share={"router_experts": 24, "zero_experts": 8,
                             "first": 0})
    with jax.default_matmul_precision("highest"):
        want, logits, _ = REF.moe(u, p, cfg, (0, 16))
    out, counts, got_logits = _routed(u, p, 8)
    counts = np.asarray(counts)
    assert counts.shape == (24,) and counts.sum() == 70 * 5
    assert 0 < counts[16:].sum() < 70 * 5      # some identity picks
    assert _rel(got_logits, logits) < 1e-5
    assert _rel(out, want) < 1e-5


def test_an_identity_only_router_adds_w_u_and_runs_no_expert_product():
    """Every pick an identity expert: ``s = (sum w) u`` and the expert
    matrices, all NaN, are never multiplied."""
    import jax.numpy as jnp

    u, p = _layer()
    bias = jnp.where(jnp.arange(24) >= 16, 1.0, 0.0)     # p <= 1 < 1 + p
    nan = {k: jnp.full(p[k].shape, jnp.nan, jnp.float32)
           for k in ("gate_up", "down")}
    for held in (None, (4, 8)):
        out, counts, logits = _routed(u, dict(p, **nan), 8, held=held,
                                      bias=bias)
        counts = np.asarray(counts)
        assert counts[:16].sum() == 0 and counts[16:].sum() == 70 * 5
        prob = np.asarray(jnp.exp(logits - jnp.max(logits, -1, keepdims=True)),
                          "float64")
        prob /= prob.sum(-1, keepdims=True)
        w = 6.0 * np.sort(prob[:, 16:], axis=-1)[:, -5:].sum(-1)
        assert np.isfinite(np.asarray(out)).all()
        assert np.abs(np.asarray(out) - w[:, None] * np.asarray(u)).max() \
            < 1e-5


def test_a_real_only_router_is_todays_moe_routed_tokens():
    """Every pick a real expert (the identity outputs pushed under every
    real one): the layer is ``moe_routed_tokens`` over the real experts'
    columns of the router, which renormalises over the chosen."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_routed_tokens

    u, p = _layer()
    bias = jnp.where(jnp.arange(24) >= 16, -2.0, 0.0)
    out, counts, _ = _routed(u, p, 8, bias=bias, norm_topk=True,
                             route_scale=1.0)
    want, want_counts, _ = moe_routed_tokens(
        u, u, p["router"][:, :16], p["gate_up"], p["down"], top_k=5,
        activation="silu", precision=jax.lax.Precision.HIGHEST)
    assert np.asarray(counts)[16:].sum() == 0
    assert np.array_equal(np.asarray(counts)[:16], np.asarray(want_counts))
    assert _rel(out, want) < 1e-5


@pytest.mark.parametrize("held", [None, (4, 8)])
def test_rows_behind_valid_add_nothing(held):
    """Rows behind ``valid`` (NaN here) take no place in the counts, reach
    no product and get neither an expert's output nor the identity term;
    the valid rows' results are those of the layer without them."""
    import jax.numpy as jnp

    u, p = _layer()
    valid = jnp.arange(70) < 41
    dirty = jnp.where(valid[:, None], u, jnp.nan)
    out, counts, _ = _routed(dirty, p, 8, held=held, valid=valid)
    want, want_counts, _ = _routed(u[:41], p, 8, held=held)
    out = np.asarray(out)
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    assert (out[41:] == 0).all() and np.isfinite(out).all()
    assert _rel(out[:41], want) < 1e-5


def test_the_shares_and_the_identity_term_once_are_the_uncut_layer():
    """A router of 16 + 8 at toy widths over four chips of four real
    experts: each computes its held pairs and, alike, the identity term,
    both latent sublayers and both dense SwiGLUs; the four expert parts,
    the identity term ONCE and what every chip computes alike ONCE are the
    reference's uncut layer."""
    import jax
    import jax.numpy as jnp

    cfg = _cfg(hidden_size=32, ffn_hidden_size=48, expert_ffn_hidden_size=8,
               num_attention_heads=2, kv_lora_rank=16, q_lora_rank=8,
               n_routed_experts=16,
               expert_share={"router_experts": 24, "zero_experts": 8,
                             "first": 0})
    rng = np.random.default_rng(11)
    n = 40

    def mat(*shape, scale=0.2):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    def sublayer():
        return {"ln_in": jnp.ones(32), "ln_post": jnp.ones(32),
                "q_a": mat(32, 8), "q_a_norm": jnp.ones(8),
                "q_b": mat(8, 2 * 24), "kv_a": mat(32, 24),
                "kv_a_norm": jnp.ones(16), "kv_b": mat(16, 2 * 32),
                "wo": mat(32, 32), "gate_up": mat(32, 96),
                "down": mat(48, 32)}

    u, branch = _layer(seed=12, n=n)
    p = dict(branch, sub=[sublayer(), sublayer()])
    x = mat(n, 32, scale=1.0)
    cos, sin = REF._tables(cfg, n, jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _, _ = REF.layer(x, p, cfg, cos, sin, (0, 16))
        alike, _, _ = REF.layer(x, p, cfg, cos, sin, (0, 16), join="dropped")
        first = p["sub"][0]
        a = x + REF.mla(REF._norm(x, first["ln_in"], 1e-5), first, cfg,
                        cos, sin)
        rows = REF._norm(a, first["ln_post"], 1e-5)
        whole, _, _ = REF.moe(rows, p, cfg, (0, 16))
        no_identity, _, _ = REF.moe(rows, p, cfg, (0, 16), identity=False)
    identity = np.asarray(whole) - np.asarray(no_identity)
    assert np.abs(identity).max() > 1e-3
    total, held_pairs = np.asarray(alike) + identity, 0
    for chip in range(4):
        out, counts, _ = _routed(rows, p, 8, held=(4 * chip, 4))
        total = total + (np.asarray(out) - identity)
        held_pairs += int(np.asarray(counts)[4 * chip:4 * chip + 4].sum())
    assert held_pairs == int(np.asarray(counts)[:16].sum())
    assert _rel(total, uncut) < 1e-5


def test_eight_head_shares_add_up_to_the_uncut_sublayer():
    """The PROGRAM's latent sublayer at one head, on each head's columns
    of ``W_qb`` and ``W_kvb`` and rows of ``W_o`` in turn: the eight
    outputs add up to the reference's uncut sublayer of eight heads."""
    import importlib

    import jax
    import jax.numpy as jnp

    # (``paddle_tpu.models`` exports a function of the module's name)
    llama = importlib.import_module("paddle_tpu.models.llama")
    cfg = _cfg(num_attention_heads=8)
    mla = BUILDER.layer_pattern(cfg)[0]["mla"]
    rng = np.random.default_rng(3)
    n, hid = 24, 64

    def mat(*shape):
        return (rng.normal(size=shape) * 0.2).astype("float32")

    full = {"q_a": mat(hid, 16), "q_a_norm": 1 + mat(16),
            "q_b": mat(16, 8 * 24), "kv_a": mat(hid, 40),
            "kv_a_norm": 1 + mat(32), "kv_b": mat(32, 8 * 32),
            "wo": mat(8 * 16, hid)}
    h = rng.normal(size=(1, n, hid)).astype("float32")
    cos, sin = REF._tables(cfg, n, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF.mla(jnp.asarray(h[0]),
                                  {k: jnp.asarray(v) for k, v in full.items()},
                                  cfg, cos, sin))
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        rows = pt.layers.data("h", [1, n, hid], append_batch_size=False)
        y, _ = llama._mla_mixer(
            rows, n, hid, 1, dict(llama.DEFAULT_LAYER, mla=mla),
            lambda s: f"one.{s}", cfg["rms_norm_eps"],
            float(cfg["rope_theta"]), "xla")
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    names = {"q_a": "q_a.w", "q_a_norm": "q_a_norm", "q_b": "q_b.w",
             "kv_a": "kv_a.w", "kv_a_norm": "kv_a_norm", "kv_b": "kv_b.w",
             "wo": "attn_out.w"}
    total = np.zeros_like(want)
    for head in range(8):
        share = REF.head_share(full, head, 1, cfg)
        for key, name in names.items():
            assert tuple(scope.find_var(f"one.{name}").shape) \
                == share[key].shape
            scope.set_var(f"one.{name}", jnp.asarray(share[key]))
        out, = exe.run(main, feed={"h": h}, fetch_list=[y], scope=scope)
        total = total + np.asarray(out)[0]
    assert _rel(total, want) < 1e-5


# ---------------------------------------------------------------------------
# the block through the programs and the engine
# ---------------------------------------------------------------------------

def _pools_to_nan(eng):
    import jax.numpy as jnp

    for name in eng.cache_names:
        pool = np.asarray(eng.scope.find_var(name))
        eng.scope.set_var(name, jnp.full(pool.shape, np.nan, jnp.float32))


@pytest.fixture(scope="module")
def served():
    """One engine's run: every page starts as NaN; slots 0 and 1 decode
    all the while; slot 2 serves a request, is left, and takes the
    compared ones: a prompt of one small chunk, one of three chunks on
    rungs 32 / 32 / 16 with three pad rows, one of five."""
    from paddle_tpu import telemetry

    cfg = _cfg()
    eng = _engine(cfg)
    try:
        _pools_to_nan(eng)
        t0 = telemetry.get_spans()[-1].start if telemetry.get_spans() else 0
        sides = [eng.submit(_prompt(50 + i, 9 + i), 70) for i in range(2)]
        first = eng.submit(_prompt(52, 30), 6)
        assert first.result(300)["slot"] == 2
        res = {}
        for n in (5, 77, 150):
            prompt = _prompt(60 + n, n)
            r = eng.generate(prompt, 9, timeout=300)
            assert r["slot"] == 2
            res[n] = (prompt, r)
        rest = [f.result(300) for f in sides]
        stats = eng.stats()
        spans = [s for s in telemetry.get_spans()
                 if s.end is not None and s.start >= t0]
    finally:
        eng.close()
    return {"cfg": cfg, "eng": eng, "res": res, "rest": rest,
            "stats": stats, "spans": spans}


def test_chunks_then_steps_in_a_reused_slot_are_the_reference(served):
    """The chunked prefill over latent pages and eight absorbed decode
    steps are the reference's single forward, logits not tokens."""
    cfg, eng = served["cfg"], served["eng"]
    # two latent caches a published layer, one pool each
    assert eng.cache_names == [f"llama.pool_c_{i}" for i in range(4)]
    assert [r["slot"] for r in served["rest"]] == [0, 1]
    for prompt, r in served["res"].values():
        # one router row a PUBLISHED layer, over all 16 + 8 outputs
        assert np.stack(r["router_logits"]).shape == (9, 2, 24)
        assert _off_reference(eng, cfg, prompt, r) < TOL
    for f, r in zip((50, 51), served["rest"]):
        assert _off_reference(eng, cfg, _prompt(f, 9 + f - 50), r) < TOL
    counters, paged = served["stats"]["counters"], served["stats"]["paged"]
    assert counters["prefill_chunks"] == 1 + 1 + 1 + 1 + 3 + 5
    assert paged["latent_layers"] == 4 and paged["pages_live"] == 0
    assert counters["moe_tokens_dropped"] == 0
    assert 0 < counters["moe_pairs_zero"] < counters["moe_pairs_routed"]
    assert 0 < counters["moe_pairs_held"] < counters["moe_pairs_routed"] \
        - counters["moe_pairs_zero"]


@pytest.mark.parametrize("join", ["before_second_sublayer", "dropped"])
def test_a_branch_that_joins_elsewhere_is_not_the_reference(served, join):
    """The reference with the branch joined BEFORE the second sublayer, or
    never: another model, and the program's logits lie far off it."""
    prompt, r = served["res"][77]
    assert _off_reference(served["eng"], served["cfg"], prompt, r,
                          join=join) > 10 * TOL


def test_other_shares_and_a_bias_of_zeros_are_not_the_reference(served):
    import jax.numpy as jnp

    cfg, eng = served["cfg"], served["eng"]
    prompt, r = served["res"][150]
    assert _off_reference(eng, cfg, prompt, r, held=(5, 4)) > 10 * TOL
    # the selection bias is part of the model: without it picks differ
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    for layer in params["layers"]:
        layer["bias"] = jnp.zeros_like(layer["bias"])
    n = len(prompt)
    want = np.asarray(REF.forward(
        params, np.asarray(prompt + r["tokens"], "int32"), cfg,
        np.arange(n - 1, n + 8)))
    assert _rel(np.stack(r["logits"]), want) > 10 * TOL


def test_the_spans_split_the_routed_pairs_three_ways(served):
    steps = [s for s in served["spans"]
             if s.name == "generation/decode_step"
             and "pairs_routed" in s.attrs]
    fetches = [s for s in served["spans"]
               if s.name == "generation/prefill_fetch"
               and "pairs_routed" in s.attrs]
    assert steps and fetches
    for s in steps + fetches:
        a = s.attrs
        assert a["pairs_held"] + a["pairs_absent"] + a["pairs_zero"] \
            == a["pairs_routed"]
        assert min(a["pairs_held"], a["pairs_absent"], a["pairs_zero"]) >= 0
    assert sum(s.attrs["pairs_zero"] for s in steps) > 0
    assert sum(s.attrs["pairs_absent"] for s in steps) > 0
    # a held expert is one of the 4 held; identity experts load nothing
    assert all(0 <= s.attrs["experts_held_touched"] <= 4 for s in steps)
    assert all(s.attrs["experts_touched"] <= 16 for s in steps)


def test_the_whole_prompt_forward_is_the_reference(served):
    """The uncached full forward (``build_llama_forward``: every row at
    once, the expanded latent path) on the engine's weights."""
    from paddle_tpu.models.llama import build_llama_forward

    cfg, eng = served["cfg"], served["eng"]
    seq = _prompt(9, 50)
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        _, fetches = build_llama_forward(1, 64, name="llama",
                                         attn_impl="xla",
                                         **BUILDER.model_args(cfg))
    ids = np.zeros((1, 64), "int64")
    ids[0, :50] = seq
    out, = pt.Executor().run(main, feed={"input_ids": ids},
                             fetch_list=[fetches["logits"]], scope=eng.scope)
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    want = np.asarray(REF.forward(params, np.asarray(seq, "int32"), cfg))
    assert _rel(np.asarray(out)[0, :50], want) < TOL


def test_the_reference_takes_the_programs_choice_at_a_near_tie_only():
    """The 5th and 6th of ``p + bias`` within the margin: the reference
    takes the program's five and counts the row; outside the margin its
    own choice stands."""
    import jax.numpy as jnp

    cfg = _cfg()
    logits = np.zeros((2, 24), "float32")
    logits[:, :4] = [4.0, 3.5, 3.0, 2.5]
    logits[:, 4], logits[:, 20] = 2.0, 2.0         # a real and an identity
    logits[1, 4] = 2.4                             # row 1: no tie
    prog = logits.copy()
    prog[0, 20] += 1e-6                # the program saw the identity ahead
    prog[1, 20] += 0.6                 # ... and here too, but it is no tie
    bias = jnp.zeros(24)
    mine, _ = REF.route(jnp.asarray(logits), bias, cfg)
    got, report = REF.route(jnp.asarray(logits), bias, cfg, jnp.arange(2),
                            jnp.asarray(prog))
    mine, got = np.asarray(mine) > 0, np.asarray(got) > 0
    assert mine[0].nonzero()[0].tolist() == [0, 1, 2, 3, 4]
    assert got[0].nonzero()[0].tolist() == [0, 1, 2, 3, 20]
    assert np.array_equal(got[1], mine[1])
    assert np.asarray(report)[2:].tolist() == [1.0, 1.0]


# ---------------------------------------------------------------------------
# what is refused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pattern,reason", [
    (lambda b, j: [j, b], "no branch carried"),
    (lambda b, j: [b, b], "before the first has joined"),
    (lambda b, j: [b], "no later layer"),
    (lambda b, j: [dict(b, ffn=None), j], "beside a dense FFN"),
])
def test_what_a_branch_cannot_do_is_refused(pattern, reason):
    from paddle_tpu.models.llama import build_llama_forward

    model = BUILDER.model_args(_cfg())
    branch, join = model["layer_pattern"]
    layers = pattern(branch, join)
    model = dict(model, layer_pattern=layers, num_layers=len(layers))
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pytest.raises(ValueError, match=reason):
            build_llama_forward(1, 16, name="llama", attn_impl="xla",
                                **model)


def test_identity_experts_outside_what_is_built_are_refused():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [1, 4, 8], append_batch_size=False)
        with pytest.raises(ValueError, match="holds experts"):
            pt.layers.moe_routed_ffn(x, x, 16, 2, 8, held=(8, 8),
                                     zero_experts=4)
        with pytest.raises(ValueError, match="identity experts"):
            pt.layers.moe_routed_ffn(x, x, 16, 2, 8, zero_experts=16)
        with pytest.raises(ValueError, match="identity experts"):
            pt.layers.moe_routed_ffn(x, x, 16, 2, 8, zero_experts=4,
                                     n_group=4, topk_group=2)


def test_the_builder_refuses_a_program_without_the_mechanisms(monkeypatch):
    import paddle_tpu.parallel.moe as moe

    def plain(x, router_x, router_w, w_gate_up, w_down, *, top_k):
        raise AssertionError("never called")

    monkeypatch.setattr(moe, "moe_routed_tokens", plain)
    monkeypatch.delattr(moe, "_softmax_biased")
    with pytest.raises(SystemExit, match="cannot run longcat-flash-chat") \
            as e:
        BUILDER.require_program()
    assert "zero_experts" in str(e.value) and "softmax + bias" in str(e.value)
    assert "branch" not in str(e.value)


# ---------------------------------------------------------------------------
# the grouped matmul's blocks at this model's widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,n,tn", [
    (6144, 4096, 128),       # this model's gate | up: [6144, 256] does not
    (2048, 6144, 768),       # ... fit the scoped VMEM; its down product
    (5120, 3072, 256), (1536, 5120, 1024),       # deepseek-v2, as before
    (7168, 4096, 128), (2048, 7168, 512),        # gigachat35
    (4096, 2560, 256), (1280, 4096, 1024),       # solar-open2
    (4096, 8192, 256), (4096, 4096, 256),        # command-a-plus
])
def test_scoped_blocks_stay_inside_the_scoped_vmem(k, n, tn):
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    assert gm.tiles(64, k, n, scoped=True) == (64, tn)
    # unscoped calls keep the wide blocks
    assert gm.tiles(64, k, n)[1] >= tn
