"""Block-diffusion generation (PR 32): a step that yields a block of B
positions, over SiLU-gated experts routed from the normed stream and
QK-normed attention, every piece against the benchmark's plain reference
(``benchmark/reference/sdar-30b-a3b-chat.py``: another implementation of
the same equations) at small sizes on the CPU.

* **The layer**: ``build_llama_forward(qk_norm=, mask_block=)`` with
  ``silu`` experts routed from the normed stream, against the reference's
  forward, QK-norm on and off.
* **Kernels**: ``flash_attention(mask_block=)`` (Pallas in interpret mode,
  the blockwise scan, ``impl='xla'``) against a masked softmax; the
  R-row ``paged_decode_attention`` kernel in interpret mode against the
  gather + einsum formulation, R in {1, 4}; the unmasking op against the
  reference's host loop.
* **The engine**: paged block-causal prefill plus every denoising and
  commit pass's logits against the reference's full forward over prompt +
  committed blocks + the block as it was fed; the streamed tokens equal
  ``reference.generate``; budgets that cut a block; a prompt that holds
  the mask id; slots joining and leaving in the middle of others' blocks
  with one pass kept in flight; pages; what is left in the pool after a
  commit; the refusals.

Tolerances: float32 on both sides, differing in the order of accumulation
only: ``TOL`` 4e-6 of the range for a kernel against a softmax row
(measured 5e-7), ``TOL_LOGITS`` 2e-5 of the range for logits through two
layers (measured 3e-6).
"""
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.monitor import stat_get

TOL = 4e-6
TOL_LOGITS = 2e-5
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, MASK_ID, PAGE = 4, 96, 8


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "blk_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "sdar-30b-a3b-chat")
BUILDER = _load("builders", "sdar_engine")


def _cfg(passes=2, qk_norm=True):
    """The published keys at a toy size: hidden 64, 4 query over 2 KV
    heads of 32, 8 SiLU experts top-3 of width 32, two layers."""
    return {"vocab_size": 97, "hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 32, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
            "num_experts": 8, "num_experts_per_tok": 3,
            "moe_intermediate_size": 32, "hidden_act": "silu",
            "decoder_sparse_step": 1, "mlp_only_layers": [],
            "as_run": {"attention_precision": "highest"},
            "assumed": {"generation": {
                "block_length": B, "mask_token_id": MASK_ID,
                "passes": passes, "qk_norm": qk_norm}}}


def _engine(cfg=None, **kw):
    from paddle_tpu.serving import GenerationEngine

    args = dict(num_slots=3, max_seq_len=64, prefill_buckets=[16, 32, 48],
                page_tokens=PAGE, attn_impl="xla", keep_logits=True,
                prefill_chunk=0, prefix_reuse=False, speculate=False,
                eos_id=-1, deadline_ms=600000)
    args.update(kw)
    return GenerationEngine(BUILDER.model_args(cfg or _cfg()), **args)


def _rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, MASK_ID, n).tolist()


_FORWARDS = {}
PAD = 64


def _forward_fn(eng, cfg):
    """``reference.forward`` on the engine's weights, jitted once per
    configuration at a padded length (the block-causal mask keeps the pad
    out of every real row's sight), as ``generate`` takes it."""
    import jax

    key = json.dumps(cfg, sort_keys=True)
    if key not in _FORWARDS:
        _FORWARDS[key] = jax.jit(
            lambda p, ids, m, rows: REF.forward(p, ids, m, cfg, rows))
    params = REF.params_from_scope(eng.scope, cfg, "llama")

    def fn(ids, masked, rows):
        pad = PAD - len(ids)
        return np.asarray(_FORWARDS[key](
            params, np.pad(np.asarray(ids, "int32"), (0, pad)),
            np.pad(np.asarray(masked, bool), (0, pad)), np.asarray(rows)))

    return fn


def _generate(eng, cfg, prompt, n_new):
    return REF.generate(None, prompt, n_new, cfg,
                        forward_fn=_forward_fn(eng, cfg))


def _reference_passes(eng, cfg, prompt, res):
    """The reference's [B, V] logits for every pass of ``res``: its full
    forward over the prompt's whole blocks, the blocks committed before
    the pass, and the block as the program was fed it."""
    forward = _forward_fn(eng, cfg)
    n_whole = len(prompt) - len(prompt) % B
    out = []
    for p in res["passes"]:
        committed = (list(prompt) + res["tokens"])[:p["base"]]
        assert p["base"] >= n_whole and len(committed) == p["base"]
        ids = np.asarray(committed + [int(t) for t in p["tokens"]])
        masked = np.concatenate([np.zeros(p["base"], bool),
                                 p["masked"].astype(bool)])
        out.append(forward(ids, masked,
                           np.arange(p["base"], p["base"] + B)))
    return out


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qk_norm", [True, False])
def test_layer_equals_the_plain_reference(qk_norm):
    """The uncached forward under the block-causal mask: QK-norm (on and
    off), SiLU experts routed from the normed stream."""
    from paddle_tpu.models.llama import build_llama_forward

    cfg = _cfg(qk_norm=qk_norm)
    model = BUILDER.model_args(cfg)
    model.pop("block_diffusion")
    S = 24
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    startup.random_seed = main.random_seed = 3
    with pt.program_guard(main, startup):
        _, fetches = build_llama_forward(1, S, name="llama",
                                         attn_impl="xla", mask_block=B,
                                         **model)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    rng = np.random.default_rng(11)
    for i in range(2):
        if qk_norm:
            # a weight that is not all ones, or the norm's weight is
            # untested
            for n in ("q_norm", "k_norm"):
                scope.set_var(f"llama.blk{i}.{n}", rng.uniform(
                    0.5, 1.5, 32).astype("float32"))
        else:
            assert scope.find_var(f"llama.blk{i}.q_norm") is None
    ids = rng.integers(0, 97, (1, S))
    got, = exe.run(main, feed={"input_ids": ids.astype("int64")},
                   fetch_list=[fetches["logits"]], scope=scope)
    params = REF.params_from_scope(scope, cfg, "llama")
    want = np.asarray(REF.forward(params, ids[0], np.zeros(S, bool), cfg))
    assert _rel(np.asarray(got)[0], want) < TOL_LOGITS
    # the mask is block-causal, not causal: row 0 sees position 3
    ids2 = ids.copy()
    ids2[0, 3] = (ids2[0, 3] + 1) % 97
    got2, = exe.run(main, feed={"input_ids": ids2.astype("int64")},
                    fetch_list=[fetches["logits"]], scope=scope)
    assert np.abs(np.asarray(got2)[0, 0] - np.asarray(got)[0, 0]).max() > 0
    ids2 = ids.copy()
    ids2[0, 4] = (ids2[0, 4] + 1) % 97       # the next block: unseen
    got2, = exe.run(main, feed={"input_ids": ids2.astype("int64")},
                    fetch_list=[fetches["logits"]], scope=scope)
    assert np.array_equal(np.asarray(got2)[0, :4], np.asarray(got)[0, :4])


def test_silu_experts_equal_a_plain_loop():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_routed_tokens

    rng = np.random.default_rng(5)
    x = rng.standard_normal((10, 16)).astype(np.float32)
    wr = rng.standard_normal((16, 8)).astype(np.float32)
    wgu = rng.standard_normal((8, 16, 24)).astype(np.float32) * 0.3
    wd = rng.standard_normal((8, 12, 16)).astype(np.float32) * 0.3
    out, counts, logits = moe_routed_tokens(
        jnp.asarray(x), jnp.asarray(x), wr, wgu, wd, top_k=3,
        activation="silu", precision=jax.lax.Precision.HIGHEST)
    want = np.zeros_like(x)
    for t in range(10):
        l = x[t].astype(np.float64) @ wr
        top = np.argsort(-l)[:3]
        w = np.exp(l[top] - l[top].max())
        w /= w.sum()
        for e, we in zip(top, w):
            gu = x[t].astype(np.float64) @ wgu[e]
            g = gu[:12]
            want[t] += we * ((g / (1 + np.exp(-g)) * gu[12:]) @ wd[e])
    assert _rel(np.asarray(out), want) < TOL
    assert int(counts.sum()) == 30
    with pytest.raises(ValueError, match="unknown expert activation"):
        moe_routed_tokens(jnp.asarray(x), jnp.asarray(x), wr, wgu, wd,
                          top_k=3, activation="gelu")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _block_causal_attention(q, k, v, block):
    d = q.shape[-1]
    s = np.einsum("bhqd,bhkd->bhqk", q.astype(np.float64),
                  k.astype(np.float64)) / np.sqrt(d)
    i = np.arange(q.shape[2])[:, None]
    j = np.arange(k.shape[2])[None, :]
    s = np.where(j // block <= i // block, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v.astype(np.float64))


@pytest.mark.parametrize("impl", ["pallas", "blockwise", "xla"])
@pytest.mark.parametrize("S,block", [(64, 4), (64, 16), (32, 3), (48, 64)])
def test_flash_attention_mask_block_is_a_masked_softmax(impl, S, block):
    """Blocks of 16 queries and 8 keys: mask blocks inside a kernel
    block, of whole kernel blocks, that divide nothing, and wider than
    the sequence (full attention)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (blockwise_attention,
                                                       flash_attention)

    rng = np.random.default_rng(S + block)
    q, k, v = (rng.standard_normal((2, 2, S, 16)).astype(np.float32)
               for _ in range(3))
    want = _block_causal_attention(q, k, v, block)
    if impl == "pallas":
        got = flash_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), True, None, 16, 8, True,
                              None, block)
    elif impl == "blockwise":
        got, _ = blockwise_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=True,
                                     block_k=8, mask_block=block)
    else:
        qv, kv, vv = (layers.data(n, list(q.shape), append_batch_size=False)
                      for n in "qkv")
        out = layers.flash_attention(qv, kv, vv, causal=True, impl="xla",
                                     mask_block=block)
        got, = pt.Executor().run(feed={"q": q, "k": k, "v": v},
                                 fetch_list=[out])
    assert _rel(np.asarray(got), want) < TOL


def test_mask_block_needs_causal_and_no_window():
    qv, kv, vv = (layers.data(n, [1, 2, 16, 16], append_batch_size=False)
                  for n in "qkv")
    x = np.zeros((1, 2, 16, 16), "float32")
    for kw in ({"causal": False}, {"causal": True, "window": 8}):
        out = layers.flash_attention(qv, kv, vv, impl="xla", mask_block=4,
                                     **kw)
        with pytest.raises(Exception, match="mask_block needs"):
            pt.Executor().run(feed={"q": x, "k": x, "v": x},
                              fetch_list=[out])


def _dot_precisions(jaxpr, found):
    """The precision of every ``dot_general`` under ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dot_precisions(sub, found)
    return found


@pytest.mark.parametrize("mask", [{}, {"window": 24}, {"mask_block": 4}])
def test_attention_precision_is_an_argument_whatever_the_mask(mask):
    """``precision="highest"`` reaches both products of the Pallas kernel
    and of the blockwise scan under the causal, the windowed and the
    block-causal mask alike; left out, no product names a precision (the
    kernel of before) and the numbers are the same here, where the CPU's
    default is already whole float32."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (blockwise_attention,
                                                       flash_attention)

    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 64, 16)), jnp.float32)
               for _ in range(3))

    def kernel(prec):
        return lambda q, k, v: flash_attention(
            q, k, v, True, None, 16, 8, True, mask.get("window"),
            mask.get("mask_block"), prec)

    def scan(prec):
        return lambda q, k, v: blockwise_attention(
            q, k, v, causal=True, block_k=8, precision=prec, **mask)[0]

    for build in (kernel, scan):
        plain = _dot_precisions(
            jax.make_jaxpr(build(None))(q, k, v).jaxpr, [])
        whole = _dot_precisions(
            jax.make_jaxpr(build("highest"))(q, k, v).jaxpr, [])
        assert len(plain) == len(whole) == 2
        assert all(p is None for p in plain)
        assert all(p is not None and "HIGHEST" in str(p) for p in whole)
        assert _rel(np.asarray(build("highest")(q, k, v)),
                    np.asarray(build(None)(q, k, v))) < TOL
    with pytest.raises(NotImplementedError, match="no backward kernel"):
        jax.grad(lambda q: kernel("highest")(q, k, v).sum())(q)


@pytest.mark.parametrize("precision", [None, "highest"])
def test_layer_pattern_sets_the_prefill_attentions_precision(precision):
    """``attn_precision`` of a layer's pattern entry is the ``precision``
    attribute of that layer's prefill attention op; the default pattern
    leaves the op without one (the program of before)."""
    from paddle_tpu.models.llama import build_llama_prefill

    model = BUILDER.model_args(_cfg())
    model.pop("block_diffusion")
    model["layer_pattern"][0]["attn_precision"] = precision
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        build_llama_prefill(1, 16, name="llama", cache_slots=2,
                            max_seq_len=32, paged=True, num_pages=9,
                            page_tokens=PAGE, mask_block=B, **model)
    ops = [op for op in main.global_block().ops
           if op.type == "flash_attention"]
    assert len(ops) == 2
    assert all(op.attr("precision", None) == precision for op in ops)
    assert all(op.attr("mask_block", None) == B for op in ops)


def _paged_case(rng, bases, R, H=8, Hkv=2, D=128, pt_=8, NP=6):
    """Slots with ``bases[b]`` committed positions and a block of R rows
    on top; unmapped and dead pool rows hold NaN."""
    n = len(bases)
    P = n * NP + 1
    pool_k = np.full((P, Hkv, pt_, D), np.nan, np.float32)
    pool_v = np.full((P, Hkv, pt_, D), np.nan, np.float32)
    bt = np.zeros((n, NP), np.int32)
    nxt = 1
    for b, base in enumerate(bases):
        live = base + R
        for page in range(-(-live // pt_)):
            bt[b, page] = nxt
            rows = min(pt_, live - page * pt_)
            pool_k[nxt, :, :rows] = rng.standard_normal((Hkv, rows, D))
            pool_v[nxt, :, :rows] = rng.standard_normal((Hkv, rows, D))
            nxt += 1
    q = rng.standard_normal((n, H, R, D)).astype(np.float32)
    return q, pool_k, pool_v, bt, np.asarray(bases, np.int32)


@pytest.mark.parametrize("R", [1, 4])
@pytest.mark.parametrize("granule", [8, 128])
def test_paged_kernel_rows_of_a_block_share_the_page_walk(R, granule):
    """The Pallas kernel in interpret mode, R query rows a slot, against
    the gather + einsum formulation it is held to: every row attends the
    committed columns and the whole block."""
    import jax.numpy as jnp

    from paddle_tpu.ops.decode_ops import _attend_cache, _gather_pages
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    rng = np.random.default_rng(R * 10 + granule)
    q, pk, pv, bt, base = _paged_case(rng, [0, 8, 20, 36], R)
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(bt),
        jnp.asarray(base + R - 1), interpret=True, granule=granule)
    clean_k, clean_v = np.nan_to_num(pk), np.nan_to_num(pv)
    want = _attend_cache(jnp.asarray(q),
                         _gather_pages(jnp.asarray(clean_k), bt),
                         _gather_pages(jnp.asarray(clean_v), bt),
                         jnp.asarray(base), block=R > 1)
    assert got.shape == q.shape
    assert np.isfinite(np.asarray(got)).all()
    assert _rel(np.asarray(got), np.asarray(want)) < TOL


def test_paged_op_with_block_rows_books_the_reference_counter():
    """Off the TPU the op is the gather formulation, R rows included, and
    books ``attention_lowered_paged_decode_reference``."""
    rng = np.random.default_rng(2)
    q, pk, pv, bt, base = _paged_case(rng, [4, 12], 4, D=16)
    pk, pv = np.nan_to_num(pk), np.nan_to_num(pv)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        qv = layers.data("q", list(q.shape), append_batch_size=False)
        kv = layers.data("pk", list(pk.shape), append_batch_size=False)
        vv = layers.data("pv", list(pv.shape), append_batch_size=False)
        btv = layers.data("bt", list(bt.shape), dtype="int32",
                          append_batch_size=False)
        pv_ = layers.data("pos", [2], dtype="int32",
                          append_batch_size=False)
        out = layers.paged_decode_attention(qv, kv, vv, btv, pv_)
    before = stat_get("attention_lowered_paged_decode_reference")
    got, = pt.Executor().run(main, feed={"q": q, "pk": pk, "pv": pv,
                                         "bt": bt, "pos": base},
                             fetch_list=[out])
    assert stat_get("attention_lowered_paged_decode_reference") == before + 1
    # row 0 of a block sees the block's last column
    d = q.shape[-1]
    s = np.einsum("hd,hkd->hk", q[0, :, 0].astype(np.float64),
                  np.repeat(pk[bt[0, 0]], 4, 0)[:, :8].astype(np.float64))
    p = np.exp(s / np.sqrt(d) - (s / np.sqrt(d)).max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hk,hkd->hd", p, np.repeat(pv[bt[0, 0]], 4, 0)[:, :8])
    assert _rel(np.asarray(got)[0, :, 0], want) < TOL


@pytest.mark.parametrize("seed", range(4))
def test_unmask_op_is_the_references_host_loop(seed):
    """``block_begin`` + ``block_unmask`` against ``reference.unmask``:
    quotas 0 to B, ties to the lower index, fresh slots."""
    rng = np.random.default_rng(seed)
    S, V = 6, 13
    logits = rng.standard_normal((S, B, V)).astype(np.float32)
    logits[1, 2] = logits[1, 0]              # an exact tie of confidence
    tokens = rng.integers(0, V - 1, (S, B)).astype(np.int64)
    masked = rng.integers(0, 2, (S, B)).astype(np.int32)
    masked[1] = 1
    quota = np.asarray([0, 2, 1, 4, 3, 2], np.int32)
    fresh = np.asarray([0, 0, 0, 0, 1, 1], np.int32)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        lv = layers.data("l", [S, B, V], append_batch_size=False)
        tv = layers.data("t", [S, B], dtype="int64",
                         append_batch_size=False)
        mv = layers.data("m", [S, B], dtype="int32",
                         append_batch_size=False)
        qv = layers.data("q", [S], dtype="int32", append_batch_size=False)
        fv = layers.data("f", [S], dtype="int32", append_batch_size=False)
        t0, m0 = layers.block_begin(tv, mv, fv, V - 1)
        t1, m1 = layers.block_unmask(lv, t0, m0, qv)
    got_t, got_m = pt.Executor().run(
        main, feed={"l": logits, "t": tokens, "m": masked, "q": quota,
                    "f": fresh}, fetch_list=[t1, m1])
    for s in range(S):
        tok = np.where(fresh[s], V - 1, tokens[s])
        msk = np.where(fresh[s], 1, masked[s]).astype(bool)
        want_t, want_m = REF.unmask(logits[s], tok, msk, int(quota[s]))
        assert np.array_equal(np.asarray(got_t)[s], want_t)
        assert np.array_equal(np.asarray(got_m)[s].astype(bool), want_m)
        assert int(msk.sum() - want_m.sum()) == min(quota[s], msk.sum())


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=[1, 2, B])
def served_passes(request):
    """One engine per T (denoising passes a block), with what it made of
    prompts whose tails are 0, 1 and B-1: three blocks each."""
    passes = request.param
    cfg = _cfg(passes)
    eng = _engine(cfg, num_slots=2)
    runs = {}
    for n_prompt in (12, 13, 15):
        prompt = _prompt(n_prompt * 10 + passes, n_prompt)
        n_new = 3 * B - n_prompt % B
        runs[n_prompt] = (prompt, n_new,
                          eng.generate(prompt, n_new, timeout=300))
    yield eng, cfg, passes, runs
    eng.close()


@pytest.mark.parametrize("n_prompt", [12, 13, 15])
def test_every_pass_equals_the_references_full_forward(served_passes,
                                                       n_prompt):
    """Prompt tails 0, 1 and B-1, T in {1, 2, B}: the paged block-causal
    prefill and then every denoising and commit pass's logits of three
    blocks, teacher-forced; and the tokens are ``reference.generate``'s."""
    eng, cfg, passes, runs = served_passes
    prompt, n_new, res = runs[n_prompt]
    assert res["finish"] == "length" and len(res["tokens"]) == n_new
    want = _reference_passes(eng, cfg, prompt, res)
    tail = n_prompt % B
    # T denoising passes and a commit a block (fewer where fewer
    # positions than passes are undecided)
    first = min(passes, B - tail) + 1
    assert len(res["passes"]) == first + 2 * (passes + 1)
    assert res["steps"] == len(res["passes"])
    for p, w in zip(res["passes"], want):
        assert _rel(p["logits"], w) < TOL_LOGITS
    # the static schedule of a whole block: ceil(left / passes_left)
    assert [p["quota"] for p in res["passes"][first:first + passes + 1]] \
        == {1: [4, 0], 2: [2, 2, 0], 4: [1, 1, 1, 1, 0]}[passes]
    assert res["tokens"] == _generate(eng, cfg, prompt, n_new)
    # the commit pass's input is the block's final tokens, no mask
    commits = [p for p in res["passes"] if p["quota"] == 0]
    assert len(commits) == 3
    assert all(not p["masked"].any() for p in commits)
    got = [int(t) for p in commits for t in p["tokens"]][tail:]
    assert got == res["tokens"]


def test_prefill_keeps_every_rows_router_logits(served_passes):
    """Under ``keep_logits`` a result's ``router_logits`` are the
    prefill's, ``[L, bucket, E]``: over the prompt's whole blocks the
    reference's own (a check reads them for its near-tie rule on the
    rows whose K/V every later pass attends)."""
    import jax.numpy as jnp

    eng, cfg, passes, runs = served_passes
    prompt, n_new, res = runs[13]
    (router,) = res["router_logits"]
    assert router.shape == (2, 16, 8)
    whole = len(prompt) - len(prompt) % B
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    ids = np.pad(np.asarray(prompt[:whole], "int32"), (0, PAD - whole))
    _, want = REF.forward(params, ids, np.zeros(PAD, bool), cfg,
                          jnp.arange(whole), keep_router=True)
    got = np.transpose(router[:, :whole], (1, 0, 2))        # [rows, L, E]
    assert _rel(got, np.asarray(want)) < TOL_LOGITS


@pytest.mark.parametrize("covered", [True, False])
def test_reference_takes_the_programs_experts_at_a_covered_near_tie(covered):
    """The near-tie rule on a CONTEXT row: handed router logits whose 3rd
    and 4th expert are swapped on one row of the prompt, the reference
    takes the program's three there (and reports one row) if the row is
    covered, and keeps its own choice if it is not."""
    import jax.numpy as jnp

    cfg = dict(_cfg(), check_tolerance={
        "near_tie_margin_share_of_router_range": 0.9})
    eng = _engine(cfg, num_slots=2)
    eng.close()
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    n, row = 24, 5
    ids = np.pad(np.asarray(_prompt(77, n), "int32"), (0, PAD - n))
    masked = np.zeros(PAD, bool)
    rows = jnp.arange(n - B, n)
    own, router = REF.forward(params, ids, masked, cfg, jnp.arange(PAD),
                              keep_router=True)
    own = np.asarray(own)[n - B:n]
    prog = np.array(router)                                  # [PAD, L, E]
    order = np.argsort(-prog[row, 0])
    third, fourth = order[2], order[3]
    prog[row, 0, third], prog[row, 0, fourth] = \
        prog[row, 0, fourth], prog[row, 0, third]
    covers = np.zeros(PAD, bool)
    covers[:n] = True
    covers[row] = covered
    got, report = REF.forward(params, ids, masked, cfg, rows,
                              program_router=prog, router_covers=covers)
    report = np.asarray(report)
    assert report.shape == (2, 4)
    assert report[0, 3] == (1 if covered else 0) and report[1, 3] == 0
    same = _rel(np.asarray(got), own) < TOL
    assert same == (not covered)


@pytest.fixture(scope="module")
def served():
    eng = _engine()
    eng.warmup()
    yield eng, _cfg()
    eng.close()


@pytest.mark.parametrize("n_prompt,n_new", [(10, 1), (10, 3), (12, 5),
                                            (9, 10), (3, 6)])
def test_budget_cuts_the_last_block(served, n_prompt, n_new):
    """``max_new_tokens`` inside a block (and inside the first, behind
    the prompt's tail; and a prompt shorter than a block): exactly that
    many tokens, streamed as booked, equal to the reference's."""
    eng, cfg = served
    prompt = _prompt(n_prompt + n_new, n_prompt)
    streamed = []
    res = eng.submit(prompt, n_new,
                     on_token=lambda t, ts: streamed.append((t, ts))
                     ).result(300)
    assert res["finish"] == "length" and len(res["tokens"]) == n_new
    assert [t for t, _ in streamed] == res["tokens"]
    assert res["tokens"] == _generate(eng, cfg, prompt, n_new)
    # a block's tokens share their commit's timestamp
    first_block = B - n_prompt % B
    stamps = [ts for _, ts in streamed]
    assert len(set(stamps[:first_block])) == 1
    assert res["ttft_ms"] is not None
    assert eng.kv.live_pages() == 0


def test_a_prompt_may_hold_the_mask_id(served):
    """Whether a position is undecided is carried as its own boolean: a
    prompt that holds the mask token, in whole blocks and in the tail,
    is read as given."""
    eng, cfg = served
    prompt = _prompt(77, 14)
    prompt[2] = prompt[9] = prompt[13] = MASK_ID
    res = eng.generate(prompt, 6, timeout=300)
    first = res["passes"][0]
    assert list(first["tokens"]) == [prompt[12], MASK_ID, MASK_ID, MASK_ID]
    assert list(first["masked"]) == [0, 0, 1, 1]
    assert res["tokens"] == _generate(eng, cfg, prompt, 6)
    for p, w in zip(res["passes"],
                    _reference_passes(eng, cfg, prompt, res)):
        assert _rel(p["logits"], w) < TOL_LOGITS


def _counters(eng):
    with eng._n_lock:
        return dict(eng._n)


def test_slots_join_and_leave_in_the_middle_of_others_blocks():
    """Three slots, five requests of unequal budgets sent while others
    are mid-block: every stream is the reference's, steady passes go out
    ahead of the settle, every dispatched row is booked or counted as
    discarded, and every page is back in the pool."""
    from paddle_tpu import telemetry

    cfg = _cfg()
    eng = _engine(cfg, keep_logits=False)
    try:
        eng.warmup()
        before = _counters(eng)
        prompts = [_prompt(100 + i, n) for i, n in
                   enumerate([9, 14, 20, 11, 16])]
        budgets = [21, 6, 13, 9, 18]
        futs = [eng.submit(p, m) for p, m in zip(prompts[:2], budgets[:2])]
        # the others join while the first two are inside their blocks
        gate = threading.Event()

        def on_token(_t, _ts):
            gate.set()

        futs.append(eng.submit(prompts[2], budgets[2], on_token=on_token))
        gate.wait(60)
        futs += [eng.submit(p, m)
                 for p, m in zip(prompts[3:], budgets[3:])]
        results = [f.result(300) for f in futs]
        for prompt, m, res in zip(prompts, budgets, results):
            assert res["finish"] == "length"
            assert res["tokens"] == _generate(eng, cfg, prompt, m)
        n = {k: v - before[k] for k, v in _counters(eng).items()}
        slot_passes = n["block_passes_denoise"] + n["block_passes_commit"]
        assert slot_passes == sum(r["steps"] for r in results)
        assert n["block_tokens_committed"] == sum(budgets) \
            == n["generated_tokens"]
        # one pass in flight: most grid steps went out ahead of a settle
        assert n["decode_steps_ahead"] >= n["decode_steps"] // 2
        assert n["decode_rows_discarded"] == 0   # no EOS: the host knows
        assert eng.kv.live_pages() == 0 and eng._inflight is None
        if telemetry.enabled():
            steps = [s for s in telemetry.get_spans()
                     if s.name == "generation/decode_step"
                     and "passes_commit" in s.attrs]
            assert sum(s.attrs["passes_denoise"] + s.attrs["passes_commit"]
                       for s in steps) >= slot_passes
            assert any(s.attrs.get("ahead") == 1 for s in steps)
    finally:
        eng.close()


@pytest.mark.parametrize("passes", [1, 2])
def test_a_joiners_first_block_rides_the_pass_ahead(passes):
    """A long request is inside its blocks when two others finish their
    prefills: each joiner's first block, made on the host, goes into the
    pass dispatched ahead of the settle beside the blocks the device
    carries, so only the very first pass was not ahead; every pass of
    every request is the reference's full forward, and the merged feeds
    bind the one compiled pass."""
    cfg = _cfg(passes=passes)
    eng = _engine(cfg)
    try:
        eng.warmup()
        before = _counters(eng)
        prompts = [_prompt(400 + i, n) for i, n in enumerate([9, 14, 20])]
        budgets = [40, 6, 11]
        gate = threading.Event()
        futs = [eng.submit(prompts[0], budgets[0],
                           on_token=lambda _t, _ts: gate.set())]
        assert gate.wait(60)
        futs += [eng.submit(p, m)
                 for p, m in zip(prompts[1:], budgets[1:])]
        results = [f.result(300) for f in futs]
        for prompt, m, res in zip(prompts, budgets, results):
            assert res["finish"] == "length"
            assert res["tokens"] == _generate(eng, cfg, prompt, m)
            for p, w in zip(res["passes"],
                            _reference_passes(eng, cfg, prompt, res)):
                assert _rel(p["logits"], w) < TOL_LOGITS
        deadline = time.monotonic() + 10.0
        while eng._inflight is not None and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)
        n = {k: v - before[k] for k, v in _counters(eng).items()}
        assert n["decode_joiners_ahead"] == 2
        assert n["decode_steps_ahead"] == n["decode_steps"] - 1
        assert n["decode_rows_discarded"] == 0
        assert eng.kv.live_pages() == 0
        assert eng._decode_exe.cache_info()["compiled"] == 1
    finally:
        eng.close()


@pytest.mark.parametrize("num_slots", [3, 5])
def test_passes_of_a_busy_grid_equal_the_references_full_forward(num_slots):
    """Seven requests sent at once through ``num_slots`` slots, so slots
    run in every phase side by side, join as others finish and ride one
    pass in flight: EVERY pass of every request is the reference's full
    forward, and each pass record says how many slots rode its pass."""
    cfg = _cfg()
    eng = _engine(cfg, num_slots=num_slots)
    try:
        eng.warmup()
        before = _counters(eng)
        prompts = [_prompt(300 + i, n)
                   for i, n in enumerate([9, 14, 20, 11, 16, 13, 8])]
        budgets = [14, 6, 11, 9, 7, 10, 12]
        futs = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
        results = [f.result(300) for f in futs]
        steps = _counters(eng)["decode_steps"] - before["decode_steps"]
        seen = 0.0
        for prompt, m, res in zip(prompts, budgets, results):
            assert res["finish"] == "length" and len(res["tokens"]) == m
            for p, w in zip(res["passes"],
                            _reference_passes(eng, cfg, prompt, res)):
                assert _rel(p["logits"], w) < TOL_LOGITS
                assert 1 <= p["riders"] <= num_slots
                seen += 1.0 / p["riders"]
        # a grid pass with r riders left r records
        assert round(seen, 6) == steps
        assert max(p["riders"] for r in results
                   for p in r["passes"]) == num_slots
    finally:
        eng.close()


def test_eos_inside_a_block_ends_the_sequence_and_discards_the_row_ahead():
    """A sequence the host cannot see the end of: EOS inside a block
    finishes it there, and the row of the pass dispatched ahead is
    counted as discarded."""
    cfg = _cfg()
    probe = _engine(cfg, keep_logits=False)
    prompt = _prompt(5, 10)
    try:
        tokens = probe.generate(prompt, 14, timeout=300)["tokens"]
        scope = probe.scope
    finally:
        probe.close()
    eos = tokens[7]
    cut = tokens.index(eos)
    eng = _engine(cfg, keep_logits=False, eos_id=eos, scope=scope)
    try:
        res = eng.generate(prompt, 14, timeout=300)
        assert res["finish"] == "eos"
        assert res["tokens"] == tokens[:cut + 1]
        # the future resolves inside the pass that books the EOS, a
        # moment before that pass counts the row dispatched ahead
        deadline = time.monotonic() + 10.0
        while eng._inflight is not None and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)
        n = _counters(eng)
        assert n["generated_tokens"] == cut + 1
        assert n["decode_rows_discarded"] == 1
        assert eng.kv.live_pages() == 0
    finally:
        eng.close()


def test_a_denoising_passs_kv_is_gone_after_its_blocks_commit():
    """What the pool holds at the generated positions after a sequence
    ends is what a block-causal prefill of prompt + answer writes there:
    the commit pass overwrote every row the denoising passes (whose
    input held the mask token) had written."""
    cfg = _cfg()
    eng = _engine(cfg, num_slots=1, keep_logits=False)
    try:
        kept = {}
        release = eng.kv.release_pages

        def spy(slot):
            if slot.pages:
                kept["pages"] = list(slot.pages)
                kept["k"] = [np.asarray(eng.scope.find_var(
                    f"llama.pool_k_{i}")).copy() for i in range(2)]
            release(slot)

        eng.kv.release_pages = spy
        prompt = _prompt(9, 10)
        res = eng.generate(prompt, 14, timeout=300)     # ends at 24
        first = dict(kept)
        seq = prompt + res["tokens"]
        eng.generate(seq, 1, timeout=300)    # 24 rows prefilled, clean
        for layer in range(2):
            def rows(rec):
                pool = rec["k"][layer]
                return np.concatenate(
                    [pool[p] for p in rec["pages"][:3]], axis=1)[:, 8:24]
            assert _rel(rows(first), rows(kept)) < TOL_LOGITS
    finally:
        eng.close()


@pytest.mark.parametrize("refused", [
    {"prefix_reuse": True}, {"prefill_chunk": 16}, {"speculate": True},
    {"role": "prefill"}, {"role": "decode"}])
def test_block_engine_refuses_what_walks_one_token_a_step(refused):
    with pytest.raises(ValueError, match="block-diffusion model commits"):
        _engine(**refused)


def test_block_engine_refuses_adoption_and_bad_settings(served):
    eng, cfg = served
    with pytest.raises(ValueError, match="cannot adopt"):
        eng.adopt(object())
    model = BUILDER.model_args(cfg)
    for bad in ({"block": 4, "passes": 5, "mask_id": 1},
                {"block": 1, "passes": 1, "mask_id": 1},
                {"block": 4, "passes": 2, "mask_id": 97}):
        with pytest.raises(ValueError, match="block_diffusion needs"):
            _engine_with(model, bad)
    with pytest.raises(ValueError, match="multiple of the block"):
        _engine_with(model, {"block": 3, "passes": 2, "mask_id": 1})
    windowed = dict(model, layer_pattern=[dict(model["layer_pattern"][0],
                                               window=16)])
    with pytest.raises(ValueError, match="sliding-window"):
        _engine_with(windowed, model["block_diffusion"])


def _engine_with(model, bd):
    from paddle_tpu.serving import GenerationEngine

    return GenerationEngine(
        dict(model, block_diffusion=bd), num_slots=2, max_seq_len=64,
        prefill_buckets=[16], page_tokens=PAGE, attn_impl="xla",
        prefill_chunk=0, prefix_reuse=False, speculate=False,
        autostart=False)


def test_one_token_models_take_the_feeds_they_took():
    """A model without ``block_diffusion`` builds the decode program of
    four feeds and one row a slot, and its engine's step is one row."""
    from paddle_tpu.models.llama import build_llama_decode

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        feeds, fetches, _ = build_llama_decode(
            2, 32, vocab_size=97, hidden=64, num_layers=1, num_heads=4,
            num_kv_heads=2, intermediate=96, num_pages=9, page_tokens=8)
    assert feeds == ["tokens", "positions", "block_tables", "live"]
    assert set(fetches) == {"logits", "next_token"}
    ops = [op.type for op in main.global_block().ops]
    assert "block_unmask" not in ops and "block_begin" not in ops
    writes = [op for op in main.global_block().ops
              if op.type == "kv_pool_write"]
    assert all(not op.attr("per_head", False) for op in writes)
