"""``deepseek-v2`` at a small size (PR 56): every layer latent (MLA)
attention, prompts prefilled in chunks over latent pages, group-limited
greedy routing with one whole group held.

* **The routing** (``parallel/moe.py`` ``route_top_k(n_group=,
  topk_group=)``) against a written-out loop at the published geometry
  (160 experts in 8 groups, 3 kept, 6 a token, times 16, not
  renormalised), ties to the lower index; the 8 shares of a 160-wide
  router and the shared experts once add up to the uncut reference layer;
  ``_held_share`` drops no pair when a row's six pairs are all held.
* **YaRN at ``mscale_all_dim`` 0.707** (``ops/rope_ops.py``
  ``yarn_mscale``, ``models/llama.py`` ``_mla_scale``) against a
  written-out table and the published scale 0.11472.
* **The chunk over latent pages**: the kernel
  (``ops/pallas/latent_attention.py`` ``mla_chunk_attention``, interpret
  mode) against the op's einsum lowering at base 0, mid-prompt and at the
  view's end; a prompt in chunks on three rungs, its last chunk with pad
  rows, is the same prompt in one rung and the reference, then eight
  absorbed decode steps, in a reused slot between live neighbours with NaN
  in every page no slot owns.
* **The engine** (``serving/generation.py``): ``prefill_chunk`` over
  latent pages is accepted and still refused over slot state; the spans,
  attributes and counters a latent chunk and a grouped router leave.
"""
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.monitor import stat_get

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
TOL = 2.0 ** -10          # of the logits' range; float32 reads 1e-6 here


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "dsv2_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "deepseek-v2")
BUILDER = _load("builders", "deepseek_v2_engine")
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 64,
        "type": "yarn"}


def _cfg(**over):
    """The published keys at a toy size: hidden 64; three layers of latent
    attention (8 heads of nope 16 + rope 8 over a latent of 32, values of
    16, query rank 24), the first over the dense SwiGLU, the others over a
    router of 16 experts in 4 groups of which 2 are kept, 3 a token, times
    16 and not renormalised; experts 4..7, one whole group, are held,
    beside two shared experts of width 32."""
    cfg = {"model_type": "deepseek_v2", "hidden_size": 64,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "num_hidden_layers": 3, "num_attention_heads": 8,
           "num_key_value_heads": 8, "vocab_size": 97,
           "n_shared_experts": 2, "n_routed_experts": 4,
           "routed_scaling_factor": 16, "kv_lora_rank": 32,
           "q_lora_rank": 24, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "qk_nope_head_dim": 16, "n_group": 4, "topk_group": 2,
           "num_experts_per_tok": 3, "first_k_dense_replace": 1,
           "moe_layer_freq": 1, "norm_topk_prob": False,
           "scoring_func": "softmax",
           "topk_method": "group_limited_greedy", "hidden_act": "silu",
           "attention_bias": False, "rms_norm_eps": 1e-6,
           "rope_theta": 10000, "rope_scaling": dict(YARN),
           "tie_word_embeddings": False,
           "expert_share": {"router_experts": 16, "first": 4},
           "assumed": {"eos_id": -1},
           "check_tolerance": {
               "near_tie_margin_share_of_router_range": 4e-4}}
    cfg.update(over)
    return cfg


def _engine(cfg=None, **kw):
    from paddle_tpu.serving import GenerationEngine

    args = dict(num_slots=3, max_seq_len=256, prefill_buckets=[8, 16, 32],
                page_tokens=PAGE, attn_impl="xla", keep_logits=True,
                prefill_chunk=32, prefix_reuse=False, speculate=False,
                eos_id=-1, deadline_ms=600000)
    args.update(kw)
    return GenerationEngine(BUILDER.model_args(cfg or _cfg()), **args)


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


# ---------------------------------------------------------------------------
# group-limited greedy selection
# ---------------------------------------------------------------------------

def _routing_loop(logits, n_group, topk_group, top_k, factor):
    """The rule written out, a row at a time, in float64 on the program's
    float32 scores: ``(experts [n, k], weights [n, k])``."""
    e = logits.shape[1]
    per = e // n_group
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s = (s / s.sum(-1, keepdims=True)).astype("float32").astype("float64")
    experts, weights = [], []
    for row in s:
        group = [max(row[g * per:(g + 1) * per]) for g in range(n_group)]
        # (a stable sort on the negated score: ties to the lower index)
        kept = sorted(range(n_group), key=lambda g: -group[g])[:topk_group]
        left = [row[i] if i // per in kept else 0.0 for i in range(e)]
        pick = sorted(range(e), key=lambda i: -left[i])[:top_k]
        experts.append(pick)
        weights.append([factor * row[i] for i in pick])
    return np.asarray(experts), np.asarray(weights)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_grouped_routing_is_the_written_out_rule(seed):
    """160 experts in 8 groups of 20, the 3 best groups by their MAX kept,
    the 6 largest of what is left, their softmax scores times 16 and not
    renormalised."""
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import route_top_k

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(50, 32)).astype("float32")
    w = rng.normal(size=(32, 160)).astype("float32") * 0.4
    logits, experts, weights = route_top_k(
        jnp.asarray(x), jnp.asarray(w), 6, "softmax", None, False, 16.0,
        n_group=8, topk_group=3)
    want_e, want_w = _routing_loop(np.asarray(logits, "float64"), 8, 3, 6,
                                   16.0)
    assert np.array_equal(np.asarray(experts), want_e)
    assert _rel(weights, want_w) < 1e-5
    # at most three groups a row, and the weights are no distribution
    assert max(len(set(r // 20)) for r in np.asarray(experts)) <= 3
    assert np.asarray(weights).sum(-1).min() > 1.0 / 160 * 6 * 16 * 0.5


def test_grouped_routing_breaks_ties_to_the_lower_index():
    """Equal logits everywhere: every group scores the same, so groups 0-2
    are kept, and of their 60 equal experts the first six are chosen."""
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import group_keep, route_top_k

    _, experts, weights = route_top_k(
        jnp.ones((4, 8)), jnp.zeros((8, 160)), 6, "softmax", None, False,
        16.0, n_group=8, topk_group=3)
    assert np.array_equal(np.asarray(experts), np.tile(np.arange(6), (4, 1)))
    assert np.allclose(np.asarray(weights), 16.0 / 160)
    kept = np.asarray(group_keep(jnp.ones((2, 160)), 8, 3))
    assert kept.tolist() == [[True] * 3 + [False] * 5] * 2


def test_what_grouped_routing_is_not_built_over_is_refused():
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import route_top_k

    with pytest.raises(ValueError, match="softmax"):
        route_top_k(jnp.ones((2, 4)), jnp.ones((4, 8)), 2, "sigmoid",
                    n_group=2, topk_group=1)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [1, 4, 8], append_batch_size=False)
        with pytest.raises(ValueError, match="groups"):
            pt.layers.moe_routed_ffn(x, x, 10, 2, 8, n_group=4,
                                     topk_group=2)


def test_eight_shares_and_the_shared_experts_once_are_the_uncut_layer():
    """A 160-wide router in 8 groups at toy widths: the chip that holds
    group g multiplies the pairs whose expert lies in it; the eight parts
    and the two shared experts, counted once, are the reference's uncut
    layer.  A row whose six pairs all lie in one held group loses none."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_routed_tokens

    cfg = _cfg(hidden_size=32, moe_intermediate_size=8, n_group=8,
               topk_group=3, num_experts_per_tok=6, n_routed_experts=160,
               expert_share={"router_experts": 160, "first": 0})
    rng = np.random.default_rng(5)
    n, hid, inter = 70, 32, 8
    h = jnp.asarray(rng.normal(size=(n, hid)), jnp.float32)
    p = {"router": jnp.asarray(rng.normal(size=(hid, 160)) * 0.5,
                               jnp.float32),
         "gate_up": jnp.asarray(rng.normal(size=(160, hid, 2 * inter)) * 0.2,
                                jnp.float32),
         "down": jnp.asarray(rng.normal(size=(160, inter, hid)) * 0.2,
                             jnp.float32),
         "shared_gate_up": jnp.asarray(
             rng.normal(size=(hid, 4 * inter)) * 0.2, jnp.float32),
         "shared_down": jnp.asarray(
             rng.normal(size=(2 * inter, hid)) * 0.2, jnp.float32)}
    with jax.default_matmul_precision("highest"):
        want, logits, _ = REF.ffn(h, p, cfg, (0, 160))
        shared = REF.shared_sum(h, p["shared_gate_up"], p["shared_down"], 2)
    total, held_pairs = np.asarray(shared), 0
    for g in range(8):
        out, counts, _ = moe_routed_tokens(
            h, h, p["router"], p["gate_up"][20 * g:20 * g + 20],
            p["down"][20 * g:20 * g + 20], top_k=6, activation="silu",
            precision=jax.lax.Precision.HIGHEST, norm_topk=False,
            route_scale=16.0, held_first=20 * g, n_group=8, topk_group=3)
        total = total + np.asarray(out)
        held_pairs += int(np.asarray(counts)[20 * g:20 * g + 20].sum())
        assert int(np.asarray(counts).sum()) == n * 6
    assert held_pairs == n * 6
    assert _rel(total, want) < 1e-5
    # a burst: every row's six experts inside group 3, all held there
    burst = p["router"].at[:, 60:80].add(8.0 * jnp.abs(p["router"][:, :20]))
    hb = jnp.abs(h)
    out, counts, _ = moe_routed_tokens(
        hb, hb, burst, p["gate_up"][60:80], p["down"][60:80], top_k=6,
        activation="silu", precision=jax.lax.Precision.HIGHEST,
        norm_topk=False, route_scale=16.0, held_first=60, n_group=8,
        topk_group=3)
    with jax.default_matmul_precision("highest"):
        want, _, _ = REF.ffn(hb, dict(p, router=burst), cfg, (0, 160),
                             shared=False)
    assert int(np.asarray(counts)[60:80].sum()) == n * 6
    assert _rel(out, want) < 1e-5


def test_the_fused_shared_swiglu_is_two_summed():
    """The program keeps the two shared experts as ONE SwiGLU of twice the
    width; the reference computes two and sums."""
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(size=(11, 16)), jnp.float32)
    gate_up = jnp.asarray(rng.normal(size=(16, 4 * 8)), jnp.float32)
    down = jnp.asarray(rng.normal(size=(2 * 8, 16)), jnp.float32)
    assert _rel(REF.swiglu(h, gate_up, down),
                REF.shared_sum(h, gate_up, down, 2)) < 1e-5


# ---------------------------------------------------------------------------
# YaRN at mscale_all_dim 0.707
# ---------------------------------------------------------------------------

def test_yarn_at_mscale_all_dim_is_the_written_out_table_and_scale():
    from paddle_tpu.models.llama import _mla_scale
    from paddle_tpu.ops.rope_ops import yarn_inv_freq, yarn_mscale

    published = {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "rope_theta": 10000, "rope_scaling": dict(
                     YARN, original_max_position_embeddings=4096)}
    # the table, written out: 32 frequencies of 64 rotary dimensions
    d, base, factor = 64, 10000.0, 40.0
    f = [base ** (-2 * i / d) for i in range(d // 2)]

    def dim_of(turns):
        return d * np.log(4096 / (turns * 2 * np.pi)) / (2 * np.log(base))

    low, high = np.floor(dim_of(32)), np.ceil(dim_of(1))
    table = []
    for i, fi in enumerate(f):
        keep = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        table.append((1 - keep) * fi / factor + keep * fi)
    got = yarn_inv_freq(np.asarray(f), base, d, factor, 4096, 32, 1)
    assert np.allclose(got, table, rtol=1e-12)
    assert np.allclose(REF.yarn_frequencies(published), table, rtol=1e-12)
    assert (low, high) == (10, 23) and got[0] == f[0] \
        and np.isclose(got[-1], f[-1] / 40)
    # the scale: 192^-1/2 x (0.1 x 0.707 x ln 40 + 1)^2
    assert abs(yarn_mscale(40, 0.707) - 1.2608) < 1e-4
    assert yarn_mscale(1, 0.707) == 1.0
    mla = {"nope_dim": 128, "rope_dim": 64, "yarn": {
        "factor": 40, "original_max": 4096, "beta_fast": 32,
        "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707}}
    assert abs(_mla_scale(mla) - 0.11472) < 1e-5
    assert abs(REF.mla_scale(published) - 0.11472) < 1e-5
    assert _mla_scale(dict(mla, scale=0.5)) == 0.5
    assert _mla_scale({"nope_dim": 128, "rope_dim": 64}) == 192 ** -0.5
    with pytest.raises(ValueError, match="mscale"):
        _mla_scale(dict(mla, yarn=dict(mla["yarn"], mscale=1.0)))


# ---------------------------------------------------------------------------
# the chunk over latent rows
# ---------------------------------------------------------------------------

def _chunk_case(seed, rows, view, heads=8, c=32, dn=16, dr=8, dv=16):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    lanes = 128
    latent = np.zeros((view, lanes), "float32")
    latent[:, :c + dr] = rng.normal(size=(view, c + dr))
    return (jnp.asarray(rng.normal(size=(heads, rows, dn)), jnp.float32),
            jnp.asarray(rng.normal(size=(heads, rows, dr)), jnp.float32),
            latent,
            jnp.asarray(rng.normal(size=(c, heads * (dn + dv))) * 0.3,
                        jnp.float32))


@pytest.mark.parametrize("base,rows", [(0, 16), (0, 64), (40, 16), (72, 24),
                                       (192, 64), (250, 8)])
def test_chunk_kernel_is_the_einsum_lowering_at_any_base(base, rows):
    """``mla_chunk_attention`` under interpret mode against the op's
    einsum lowering (the op itself, off the chip) on the same pool: base
    0, mid-prompt, at the view's end; key blocks of 32, so blocks wholly
    before the chunk, on its diagonal and behind it all occur."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import latent_attention as la

    view, c, dn, dr, dv = 256 + 8 * (base == 250), 32, 16, 8, 16
    view = -(-view // 32) * 32
    qn, qr, latent, w = _chunk_case(base + rows, rows, view)
    n_real = rows - 3                  # the chunk's last rows are pad
    cut = latent.copy()
    cut[base + n_real:] = np.nan       # a recycled page's garbage
    pages = view // PAGE
    table = np.random.default_rng(base).permutation(pages) + 1
    pool = np.full((pages + 1, 1, PAGE, 128), np.nan, "float32")
    pool[table, 0] = cut.reshape(pages, PAGE, 128)
    before = stat_get("attention_lowered_latent_chunk_reference")
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        data = pt.layers.data
        args = [data("qn", [1, 8, rows, dn], append_batch_size=False),
                data("qr", [1, 8, rows, dr], append_batch_size=False),
                data("w", [c, 8 * (dn + dv)], append_batch_size=False),
                data("pool", list(pool.shape), append_batch_size=False),
                data("bt", [1, pages], dtype="int32",
                     append_batch_size=False),
                data("pos", [1], dtype="int32", append_batch_size=False),
                data("len", [1], dtype="int32", append_batch_size=False)]
        out = pt.layers.latent_chunk_attention(*args, 0.21, dv)
    want, = pt.Executor().run(
        main, feed={"qn": np.asarray(qn)[None], "qr": np.asarray(qr)[None],
                    "w": np.asarray(w), "pool": pool,
                    "bt": table[None].astype("int32"),
                    "pos": np.asarray([base], "int32"),
                    "len": np.asarray([n_real], "int32")},
        fetch_list=[out], scope=pt.Scope())
    assert stat_get("attention_lowered_latent_chunk_reference") == before + 1
    clean = np.where(np.arange(view)[:, None] < base + n_real, latent, 0)
    got = la.mla_chunk_attention(
        qn, qr, jnp.asarray(clean), w, jnp.asarray([base], jnp.int32),
        scale=0.21, nope_dim=dn, latent_dim=c, block_k=32, interpret=True)
    assert np.isfinite(want[0, :, :n_real]).all()
    assert _rel(np.asarray(got)[:, :n_real], want[0, :, :n_real]) < 1e-5


def test_chunk_kernel_takes_the_published_shapes_and_not_others():
    from paddle_tpu.ops.pallas.latent_attention import chunk_supported

    assert chunk_supported(128, 1024, (12800, 640), 512, 128, 128)
    assert chunk_supported(128, 256, (12800, 640), 512, 128, 128)
    assert not chunk_supported(8, 16, (256, 128), 32, 16, 16)   # toy heads
    assert not chunk_supported(128, 1020, (12800, 640), 512, 128, 128)
    assert not chunk_supported(128, 1024, (12800, 512), 512, 128, 128)


# ---------------------------------------------------------------------------
# the engine: chunks over latent pages
# ---------------------------------------------------------------------------

def _pools_to_nan(eng):
    import jax.numpy as jnp

    for name in eng.cache_names:
        pool = np.asarray(eng.scope.find_var(name))
        eng.scope.set_var(name, jnp.full(pool.shape, np.nan, jnp.float32))


def test_chunks_in_a_reused_slot_between_live_neighbours_are_the_reference():
    """Every page starts as NaN.  Slots 0 and 1 decode all the while; slot
    2 serves a request, is left, and takes the compared ones: a prompt of
    one small chunk, one of three chunks on rungs 32 / 32 / 16 with three
    pad rows, one of five: the chunked prefill and eight absorbed decode
    steps are the reference's single forward, logits not tokens."""
    cfg = _cfg()
    eng = _engine(cfg)
    try:
        assert eng.cache_names == [f"llama.pool_c_{i}" for i in range(3)]
        _pools_to_nan(eng)
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
    finally:
        eng.close()
    counters = stats["counters"]
    assert [r["slot"] for r in rest] == [0, 1]
    for prompt, r in res.values():
        # one router row an EXPERT layer: the dense layer has none
        assert np.stack(r["router_logits"]).shape == (9, 2, 16)
        assert _off_reference(eng, cfg, prompt, r) < TOL
    for f, r in zip((50, 51), rest):
        assert _off_reference(eng, cfg, _prompt(f, 9 + f - 50), r) < TOL
    assert counters["prefill_chunks"] == 1 + 1 + 1 + 1 + 3 + 5
    assert stats["paged"]["latent_layers"] == 3
    assert stats["paged"]["pages_live"] == 0          # slots left
    assert counters["moe_tokens_dropped"] == 0
    assert counters["moe_shared_expert_rows"] * 3 \
        == counters["moe_pairs_routed"]


def test_a_prompt_in_chunks_is_the_same_prompt_in_one_rung():
    """The same weights, the same prompt: three chunks over latent pages
    (the kernel's arithmetic: cached rows expanded block by block) against
    the single-shot prefill (all rows expanded at once), then eight
    absorbed steps each."""
    cfg = _cfg()
    prompt = _prompt(7, 77)
    chunked = _engine(cfg)
    try:
        got = chunked.generate(prompt, 9, timeout=300)
    finally:
        chunked.close()
    chunked.scope.erase(list(chunked.cache_names))
    whole = _engine(cfg, scope=chunked.scope, prefill_chunk=0,
                    prefill_buckets=[128])
    try:
        want = whole.generate(prompt, 9, timeout=300)
        counters = whole.stats()["counters"]
    finally:
        whole.close()
    assert counters["prefill_chunks"] == 0
    assert got["tokens"] == want["tokens"]
    assert _rel(np.stack(got["logits"]), np.stack(want["logits"])) < 1e-5
    assert _off_reference(whole, cfg, prompt, want) < TOL


def test_a_planted_routing_fault_is_not_the_reference():
    """The reference without the group step (the six largest of all 16) is
    another model: the program's logits lie far off it."""
    cfg = _cfg()
    eng = _engine(cfg)
    try:
        prompt = _prompt(8, 60)
        res = eng.generate(prompt, 9, timeout=300)
    finally:
        eng.close()
    assert _off_reference(eng, cfg, prompt, res) < TOL
    assert _off_reference(eng, cfg, prompt, res, grouped=False) > 10 * TOL
    assert _off_reference(eng, cfg, prompt, res, held=(5, 4)) > 10 * TOL


def test_the_reference_takes_the_programs_choice_at_a_near_tie_only():
    """Two GROUPS' scores within the margin: the reference takes the
    program's groups and experts and counts the row; outside the margin
    its own choice stands."""
    import jax.numpy as jnp

    cfg = _cfg()
    logits = np.zeros((2, 16), "float32")
    logits[:, 0], logits[:, 4], logits[:, 8] = 3.0, 2.0, 2.0
    logits[:, 1], logits[:, 5], logits[:, 9] = 1.0, 0.9, 0.8
    logits[1, 4] = 2.3                 # row 1: group 1 clearly ahead
    prog = logits.copy()
    prog[0, 8] += 1e-6                 # the program saw group 2 ahead
    prog[1, 8] += 0.5                  # ... and here too, but it is no tie
    mine, _ = REF.route(jnp.asarray(logits), cfg)
    got, report = REF.route(jnp.asarray(logits), cfg, jnp.arange(2),
                            jnp.asarray(prog))
    mine, got = np.asarray(mine) > 0, np.asarray(got) > 0
    assert mine[0].nonzero()[0].tolist() == [0, 1, 4]
    assert got[0].nonzero()[0].tolist() == [0, 1, 8]
    assert np.array_equal(got[1], mine[1])
    assert np.asarray(report)[2:].tolist() == [1.0, 1.0]


@pytest.mark.parametrize("kw,reason", [
    ({"prefill_chunk": 8}, "prefill_chunk > 0"),
])
def test_chunks_stay_refused_over_slot_state(kw, reason):
    from paddle_tpu.serving import GenerationEngine

    model = BUILDER.model_args(_cfg())
    conv = {"mixer": {"kind": "conv", "L_cache": 3, "bias": False}}
    model = dict(model, layer_pattern=[model["layer_pattern"][0], conv,
                                       model["layer_pattern"][2]])
    with pytest.raises(ValueError, match="slot state") as e:
        GenerationEngine(model, num_slots=2, max_seq_len=64,
                         prefill_buckets=[8], page_tokens=PAGE,
                         prefix_reuse=False, speculate=False, **kw)
    assert reason in str(e.value)


def test_the_builder_refuses_a_program_without_the_mechanisms(monkeypatch):
    import paddle_tpu.parallel.moe as moe

    def plain(router_x, router_w, top_k):
        raise AssertionError("never called")

    monkeypatch.setattr(moe, "route_top_k", plain)
    with pytest.raises(SystemExit, match="cannot run deepseek-v2") as e:
        BUILDER.require_program()
    assert "n_group" in str(e.value)


def test_spans_and_counters_say_what_a_latent_chunk_and_the_groups_did():
    from paddle_tpu import telemetry
    from paddle_tpu.ops.latent_attention_ops import CHUNK_BLOCK_K

    assert CHUNK_BLOCK_K == 512
    names = ("attention_lowered_latent_chunk",
             "attention_lowered_latent_chunk_reference",
             "attention_lowered_latent_prefill", "kv_pool_write_pages",
             "kv_pool_write_rows")
    before = {n: stat_get(n) for n in names}
    eng = _engine()
    try:
        t0 = telemetry.get_spans()[-1].start if telemetry.get_spans() else 0
        eng.generate(_prompt(41, 77), 4, timeout=300)
        spans = [s for s in telemetry.get_spans()
                 if s.end is not None and s.start >= t0]
        counters = eng.stats()["counters"]
    finally:
        eng.close()
    grew = {n: stat_get(n) - before[n] for n in names}
    # two rungs (32, 16) were built, three latent layers each, every write
    # whole pages; no single-shot prefill exists
    assert grew == {"attention_lowered_latent_chunk": 0,
                    "attention_lowered_latent_chunk_reference": 6,
                    "attention_lowered_latent_prefill": 0,
                    "kv_pool_write_pages": 6, "kv_pool_write_rows": 0}
    chunks = [s for s in spans if s.name == "generation/prefill_chunk"][-3:]
    assert [(s.attrs["base"], s.attrs["tokens"], s.attrs["bucket"],
             s.attrs["pad_rows"]) for s in chunks] \
        == [(0, 32, 32, 0), (32, 32, 32, 0), (64, 13, 16, 3)]
    assert [(s.attrs["latent_rows_written"], s.attrs["latent_rows_attended"],
             s.attrs["latent_rows_expanded"]) for s in chunks] \
        == [(32, 32, 256), (32, 64, 256), (13, 77, 256)]
    # the pairs of the three latent layers
    assert [s.attrs["attended_pairs"] for s in chunks] == [
        3 * sum(range(1, 33)), 3 * sum(range(33, 65)),
        3 * sum(range(65, 78))]
    fetch = [s for s in spans if s.name == "generation/prefill_fetch"][-1]
    assert fetch.attrs["pairs_routed"] == 2 * 77 * 3
    assert 0 < fetch.attrs["pairs_held"] < fetch.attrs["pairs_routed"]
    assert fetch.attrs["pairs_held"] <= 3 * fetch.attrs["rows_group_held"] \
        <= 3 * 2 * 77
    steps = [s for s in spans if s.name == "generation/decode_step"
             and "latent_positions" in s.attrs]
    assert steps
    for s in steps[-3:]:
        assert s.attrs["pairs_routed"] == 2 * 3
        assert 78 <= s.attrs["latent_positions"] <= 81
        assert 0 <= s.attrs["rows_group_held"] <= 2
        assert 0 <= s.attrs["experts_held_touched"] <= 3
    assert counters["moe_rows_group_held"] >= fetch.attrs["rows_group_held"]
    assert counters["moe_pairs_held"] <= 3 * counters["moe_rows_group_held"]
