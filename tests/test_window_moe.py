"""Routed experts, sliding-window attention and the two-kind page pool
(PR 28): every new piece against a plain formulation, at small sizes on
the CPU.

* **Routing** (``parallel/moe.py``): softmax over the k selected logits
  equals softmax over all, select, renormalise; nothing is dropped, even
  when the router sends every token to one expert; the grouped matmul
  equals a plain loop over the experts.
* **Window attention**: ``flash_attention(window=)`` (Pallas in interpret
  mode, the blockwise scan and ``impl='xla'``) against a masked softmax;
  ``paged_decode_attention(window=)`` (the kernel in interpret mode and
  the gather formulation) with contexts shorter than, equal to and longer
  than the window, pages left of the window on the NaN-filled trash page.
* **Engine**: a model with ``head_dim != hidden // num_heads``, NoPE
  full layers beside RoPE window layers and routed experts, served
  across the window boundary, against the uncached full
  forward and against the benchmark's plain reference; the window pool's
  bound, release and reuse of pages, admission by both pools; the guards.

Tolerances: everything here is float32 on both sides and differs in the
order of accumulation only.  ``TOL`` 4e-6 of the range for a kernel
against a softmax row (measured 4e-7); ``TOL_LOGITS`` 2e-5 of the range
for logits through four layers (measured 2e-6).
"""
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.monitor import stat_get

TOL = 4e-6
TOL_LOGITS = 2e-5
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MOE = {"experts": 8, "top_k": 3, "width": 32, "activation": "relu"}
WINDOW, PAGE = 16, 8
# hidden 64, 4 query over 2 KV heads of 32 (q is 128 wide, not 64),
# pattern [full-NoPE, window, window, window], 8 experts top-3 of 32
MODEL = dict(vocab_size=97, hidden=64, num_layers=4, num_heads=4,
             num_kv_heads=2, intermediate=0, head_dim=32,
             rms_norm_eps=1e-6, rope_base=1.5e6,
             layer_pattern=[{"window": None, "rope": False, "ffn": MOE}]
             + [{"window": WINDOW, "rope": True, "ffn": MOE}] * 3)


def _engine(model=MODEL, **kw):
    from paddle_tpu.serving import GenerationEngine

    args = dict(num_slots=3, max_seq_len=64, prefill_buckets=[16, 32, 48],
                page_tokens=PAGE, attn_impl="xla",
                keep_logits=True, prefill_chunk=0, prefix_reuse=False,
                speculate=False, eos_id=-1, deadline_ms=600000)
    args.update(kw)
    return GenerationEngine(dict(model), **args)


def _full_forward(scope, model, seq, S=64):
    """Uncached logits [len(seq), V] through ``build_llama_forward`` on
    the engine's weights."""
    from paddle_tpu.models.llama import build_llama_forward

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        _, fetches = build_llama_forward(1, S, name="llama",
                                         attn_impl="xla", **model)
    ids = np.zeros((1, S), "int64")
    ids[0, :len(seq)] = seq
    out, = pt.Executor().run(main, feed={"input_ids": ids},
                             fetch_list=[fetches["logits"]], scope=scope)
    return np.asarray(out)[0, :len(seq)]


def _rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


# -- routing -----------------------------------------------------------------

@pytest.mark.parametrize("top_k", [1, 3, 6])
def test_softmax_over_selected_is_softmax_all_select_renormalise(top_k):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import route_top_k

    rng = np.random.default_rng(top_k)
    x = jnp.asarray(rng.standard_normal((40, 24)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    logits, experts, weights = route_top_k(x, w, top_k)
    p = np.asarray(jax.nn.softmax(logits, -1))
    order = np.argsort(-np.asarray(logits), -1)[:, :top_k]
    assert np.array_equal(np.sort(order, -1), np.sort(experts, -1))
    sel = np.take_along_axis(p, np.asarray(experts), -1)
    np.testing.assert_allclose(weights, sel / sel.sum(-1, keepdims=True),
                               rtol=2e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)


def _plain_experts(x, rx, rw, gu, dn, top_k):
    """A loop over tokens and their experts, in numpy float64."""
    x, rx, rw, gu, dn = (np.asarray(a, np.float64)
                         for a in (x, rx, rw, gu, dn))
    inter = dn.shape[1]
    logits = rx @ rw
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        idx = np.argsort(-logits[n])[:top_k]
        w = np.exp(logits[n, idx] - logits[n, idx].max())
        w /= w.sum()
        for j, e in enumerate(idx):
            h = x[n] @ gu[e]
            out[n] += w[j] * ((np.maximum(h[:inter], 0) * h[inter:])
                              @ dn[e])
    return out


@pytest.mark.parametrize("n_tokens,top_k", [(1, 3), (10, 3), (33, 6)])
def test_routed_experts_equal_a_plain_loop(n_tokens, top_k):
    """The router reads ``router_x``, the experts ``x``: two tensors."""
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_routed_tokens

    rng = np.random.default_rng(n_tokens)
    H, E, inter = 16, 8, 12
    x, rx = (jnp.asarray(rng.standard_normal((n_tokens, H)), jnp.float32)
             for _ in range(2))
    rw = jnp.asarray(rng.standard_normal((H, E)), jnp.float32)
    gu = jnp.asarray(rng.standard_normal((E, H, 2 * inter)), jnp.float32)
    dn = jnp.asarray(rng.standard_normal((E, inter, H)), jnp.float32)
    out, counts, _ = moe_routed_tokens(x, rx, rw, gu, dn, top_k=top_k)
    want = _plain_experts(x, rx, rw, gu, dn, top_k)
    assert _rel(np.asarray(out), want) < TOL
    assert int(counts.sum()) == n_tokens * top_k


@pytest.mark.parametrize("top_k", [1, 3])
def test_nothing_is_dropped_when_one_expert_gets_every_token(top_k):
    """No capacity: a router forced to one favourite sends it all 50
    tokens, and the counts still sum to tokens x k."""
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_routed_tokens

    rng = np.random.default_rng(0)
    N, H, E, inter = 50, 16, 8, 12
    x = jnp.asarray(np.abs(rng.standard_normal((N, H))) + 0.1, jnp.float32)
    rw = np.zeros((H, E), np.float32)
    rw[:, 5] = 1.0                       # positive x: expert 5 always first
    rw[:, 2] = 0.5
    rw[:, 7] = 0.25
    gu = jnp.asarray(rng.standard_normal((E, H, 2 * inter)), jnp.float32)
    dn = jnp.asarray(rng.standard_normal((E, inter, H)), jnp.float32)
    valid = jnp.arange(N) < 40
    out, counts, _ = moe_routed_tokens(x, x, jnp.asarray(rw), gu, dn,
                                       top_k=top_k, valid=valid)
    counts = np.asarray(counts)
    assert counts[5] == 40 and counts.sum() == 40 * top_k
    assert _rel(np.asarray(out)[:40],
                _plain_experts(x, x, rw, gu, dn, top_k)[:40]) < TOL
    # the rows behind ``valid`` went through no expert
    assert not np.asarray(out)[40:].any()


def test_grouped_matmul_with_empty_groups_matches_a_loop():
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import grouped_matmul

    rng = np.random.default_rng(1)
    sizes = np.array([3, 0, 5, 0, 0, 1, 7, 0], np.int32)
    rows = rng.standard_normal((sizes.sum(), 16)).astype(np.float32)
    w = rng.standard_normal((8, 16, 12)).astype(np.float32)
    got = np.asarray(grouped_matmul(jnp.asarray(rows), jnp.asarray(w),
                                    jnp.asarray(sizes)))
    want = np.concatenate([rows[s:s + n] @ w[g] for g, (s, n) in enumerate(
        zip(np.cumsum(sizes) - sizes, sizes)) if n])
    assert _rel(got, want) < TOL


# -- window attention --------------------------------------------------------

def _masked_softmax_attention(q, k, v, window):
    S, D = q.shape[2], q.shape[3]
    s = np.einsum("bhqd,bhkd->bhqk", q, k).astype(np.float64) / np.sqrt(D)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    keep = (j <= i) & (j > i - window)
    s = np.where(keep, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("impl", ["pallas", "blockwise", "xla"])
@pytest.mark.parametrize("S,window", [(64, 12), (64, 16), (32, 32),
                                      (48, 100)])
def test_flash_attention_window_is_a_masked_softmax(impl, S, window):
    """Blocks of 16 queries and 8 keys: windows inside a block, of whole
    blocks, of the whole sequence and wider than it."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (blockwise_attention,
                                                       flash_attention)

    rng = np.random.default_rng(S + window)
    q, k, v = (rng.standard_normal((2, 2, S, 16)).astype(np.float32)
               for _ in range(3))
    want = _masked_softmax_attention(q, k, v, window)
    if impl == "pallas":
        got = flash_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), True, None, 16, 8, True,
                              window)
    elif impl == "blockwise":
        got, _ = blockwise_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=True,
                                     block_k=8, window=window)
    else:
        qv, kv, vv = (layers.data(n, list(q.shape), append_batch_size=False)
                      for n in "qkv")
        out = layers.flash_attention(qv, kv, vv, causal=True, impl="xla",
                                     window=window)
        before = stat_get("attention_lowered_xla_window")
        got, = pt.Executor().run(feed={"q": q, "k": k, "v": v},
                                 fetch_list=[out])
        assert stat_get("attention_lowered_xla_window") == before + 1
    assert _rel(np.asarray(got), want) < TOL


def test_flash_attention_without_a_window_is_untouched():
    """``window=None`` adds no attribute to the op and books no windowed
    lowering."""
    q = layers.data("q", [1, 2, 16, 8], append_batch_size=False)
    layers.flash_attention(q, q, q, causal=True, impl="xla")
    op = pt.default_main_program().global_block().ops[-1]
    assert "window" not in op.attrs
    x = np.random.default_rng(0).standard_normal((1, 2, 16, 8)) \
        .astype(np.float32)
    before = stat_get("attention_lowered_xla_window")
    pt.Executor().run(feed={"q": x}, fetch_list=[op.output("Out")[0]])
    assert stat_get("attention_lowered_xla_window") == before


def _paged_case(rng, lengths, window, H=4, Hkv=2, D=128, NP=8):
    """Pools whose pages left of each slot's window are the NaN-filled
    trash page: a slot at position p keeps pages from (p - W + 1) // pt."""
    import jax.numpy as jnp

    B = len(lengths)
    P = B * NP + 1
    pk = rng.standard_normal((P, Hkv, PAGE, D)).astype(np.float32)
    pv = rng.standard_normal((P, Hkv, PAGE, D)).astype(np.float32)
    pk[0] = pv[0] = np.nan
    q = rng.standard_normal((B, H, 1, D)).astype(np.float32)
    bt = rng.permutation(np.arange(1, P)).reshape(B, NP).astype(np.int32)
    pos = np.array([n - 1 for n in lengths], np.int32)
    for b, p in enumerate(pos):
        bt[b, :max(0, p - window + 1) // PAGE] = 0
        bt[b, p // PAGE + 1:] = 0
    return tuple(jnp.asarray(a) for a in (q, pk, pv, bt, pos))


def _window_columns_reference(q, pk, pv, bt, pos, window):
    """Each slot's window columns gathered one by one, float64."""
    q, pk, pv = (np.asarray(a, np.float64) for a in (q, pk, pv))
    bt, pos = np.asarray(bt), np.asarray(pos)
    B, H, _, D = q.shape
    rep = H // pk.shape[1]
    out = np.zeros((B, H, 1, D))
    for b in range(B):
        cols = range(max(0, pos[b] - window + 1), pos[b] + 1)
        k = np.stack([pk[bt[b, j // PAGE], :, j % PAGE] for j in cols], 1)
        v = np.stack([pv[bt[b, j // PAGE], :, j % PAGE] for j in cols], 1)
        for h in range(H):
            s = k[h // rep] @ q[b, h, 0] / np.sqrt(D)
            p = np.exp(s - s.max())
            out[b, h, 0] = (p / p.sum()) @ v[h // rep]
    return out


# contexts shorter than, equal to, one past and far past the window of 16
LENGTHS = [3, 15, 16, 17, 40, 64]


@pytest.mark.parametrize("granule", [8, 16, 128])
def test_paged_decode_kernel_window_reads_the_window_only(granule):
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_decode_attention

    case = _paged_case(np.random.default_rng(granule), LENGTHS, WINDOW)
    got = np.asarray(paged_decode_attention(
        *case, interpret=True, granule=granule, window=WINDOW))
    assert np.isfinite(got).all()
    assert _rel(got, _window_columns_reference(*case, WINDOW)) < TOL


@pytest.mark.parametrize("window", [8, 16, 24])
def test_paged_decode_op_window_is_the_gather_formulation(window):
    """The op on the CPU: gather + einsum with the window as a mask,
    booked as the windowed reference lowering."""
    import jax.numpy as jnp

    q, pk, pv, bt, pos = _paged_case(np.random.default_rng(window),
                                     LENGTHS, window)
    # the gather formulation multiplies masked columns by zero: the
    # trash page must be finite for it (the engine's is: zeros, or K/V
    # rows that slid out)
    pk, pv = (jnp.nan_to_num(a) for a in (pk, pv))
    names = ("q", "pk", "pv", "bt", "pos")
    arrays = tuple(np.asarray(a) for a in (q, pk, pv, bt, pos))
    vs = [layers.data(n, list(a.shape), dtype=str(a.dtype),
                      append_batch_size=False)
          for n, a in zip(names, arrays)]
    out = layers.paged_decode_attention(*vs, window=window)
    before = stat_get("attention_lowered_paged_decode_reference_window")
    got, = pt.Executor().run(feed=dict(zip(names, arrays)),
                             fetch_list=[out])
    assert stat_get("attention_lowered_paged_decode_reference_window") \
        == before + 1
    want = _window_columns_reference(q, pk, pv, bt, pos, window)
    assert _rel(np.asarray(got), want) < TOL


# -- the engine --------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One paged engine over the small model, and what it generated for
    prompts that end six before the window's edge, on it and past it."""
    eng = _engine()
    eng.warmup()
    rng = np.random.default_rng(7)
    runs = {}
    for n in (10, 16, 30):
        prompt = rng.integers(1, 97, n).tolist()
        runs[n] = (prompt, eng.generate(prompt, 26, timeout=300))
    yield eng, runs
    eng.close()


@pytest.mark.parametrize("n_prompt", [10, 16, 30])
def test_paged_window_moe_engine_equals_the_full_forward(served, n_prompt):
    """Paged prefill plus 25 cached decode steps, from six positions
    before the window's edge (prompt 10) to twenty past it, against the
    uncached forward of the same graph."""
    eng, runs = served
    prompt, res = runs[n_prompt]
    want = _full_forward(eng.scope, MODEL, prompt + res["tokens"])
    want = want[n_prompt - 1:n_prompt - 1 + len(res["tokens"])]
    got = np.stack(res["logits"])
    assert _rel(got, want) < TOL_LOGITS
    assert np.array_equal(want.argmax(-1), res["tokens"])


@pytest.mark.parametrize("n_prompt", [10, 30])
def test_engine_equals_the_benchmarks_plain_reference(served, n_prompt):
    """The same logits against ``benchmark/reference/smallthinker-21b-
    a3b.py``: another implementation of the same equations."""
    path = os.path.join(REPO, "benchmark", "reference",
                        "smallthinker-21b-a3b.py")
    spec = importlib.util.spec_from_file_location("ref_smallthinker", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    eng, runs = served
    prompt, res = runs[n_prompt]
    cfg = {"num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 32, "rms_norm_eps": 1e-6, "rope_theta": 1.5e6,
           "moe_num_active_primary_experts": 3, "num_hidden_layers": 4,
           "sliding_window_size": WINDOW,
           "sliding_window_layout": [0, 1, 1, 1],
           "rope_layout": [0, 1, 1, 1]}
    params = ref.params_from_scope(eng.scope, cfg, "llama")
    seq = prompt + res["tokens"]
    rows = np.arange(n_prompt - 1, n_prompt - 1 + len(res["tokens"]))
    want = np.asarray(ref.forward(params, seq, cfg, rows))
    assert _rel(np.stack(res["logits"]), want) < TOL_LOGITS


def test_engine_books_experts_and_window_pages(served):
    eng, _ = served
    st = eng.stats()
    n = st["counters"]
    assert n["moe_tokens_routed"] > 0 and n["moe_tokens_dropped"] == 0
    assert stat_get("moe_tokens_dropped") == 0
    # every prefill row and decode step, four layers, three experts each
    tokens = n["prefill_tokens"] + n["generated_tokens"] - n["served"]
    assert n["moe_tokens_routed"] == tokens * 4 * 3
    # the rows no expert multiplied: the rungs' tails behind prompts of
    # 10, 16 and 30 in rungs of 16, 16 and 32, and two idle slots of
    # three beside every decode step's one rider
    idle = 6 + 0 + 2 + 2 * (n["generated_tokens"] - n["served"])
    assert n["moe_pad_pairs_left_out"] == idle * 4 * 3
    assert stat_get("moe_pad_pairs_left_out") >= idle * 4 * 3
    win = st["paged"]["window"]
    assert win["pages_per_slot"] == WINDOW // PAGE + 1
    assert win["pages_released"] > 0 and win["pages_live"] == 0
    assert st["paged"]["pages_live"] == 0
    assert stat_get("serving_kv_window_pages_released") > 0


def test_window_pool_holds_a_window_and_reuses_released_pages():
    """A slot decoding from six before the window's edge to twenty past
    it never holds more than W / page + 1 window pages; the pages it let
    go serve another slot in a pool too small for two whole contexts."""
    per_slot = WINDOW // PAGE + 1
    eng = _engine(num_slots=2, keep_logits=False, autostart=False,
                  num_window_pages=2 * per_slot + 1)
    peak, handed = [], []
    grow = eng.kv.ensure_pages

    def watched(slot, n_tokens):
        had = list(slot.wpages)
        grow(slot, n_tokens)
        # (the sliding table grows at its end; 0: a page never mapped)
        handed.extend(p for p in slot.wpages[len(had):] if p)
        peak.append(sum(1 for p in slot.wpages if p))
        assert eng.kv.live_pages("window") <= 2 * per_slot

    eng.kv.ensure_pages = watched
    eng.start()
    try:
        rng = np.random.default_rng(3)
        futs = [eng.submit(rng.integers(1, 97, n).tolist(), 26)
                for n in (10, 30, 12)]
        res = [f.result(300) for f in futs]
    finally:
        eng.close()
    assert [r["finish"] for r in res] == ["length"] * 3
    assert max(peak) == per_slot
    st = eng.stats()
    # 36 and 38 positions map 5 logical pages each and end holding 3: two
    # slid out; the prompt of 30 maps pages 1-3 at once (page 0 was never
    # its to hold) and then 4-6: three slid out
    assert st["paged"]["window"]["pages_released"] == 7
    # 16 pages handed out of a pool of 6: the released ones served again
    assert len(handed) == 16
    assert len(set(handed)) <= 2 * per_slot
    assert st["counters"]["pool_stalls"] == 0


def test_admission_needs_room_in_both_pools():
    """A window pool short of one slot's window refuses the prompt (the
    grid is otherwise empty, so it fails rather than waits); the full
    pool has room, and gives its pages back."""
    from paddle_tpu.serving.engine import RequestFailed

    eng = _engine(num_slots=2, keep_logits=False, num_window_pages=3)
    try:
        with pytest.raises(RequestFailed, match="window page pool"):
            eng.generate(list(range(1, 31)), 4, timeout=300)
        assert eng.stats()["paged"]["pages_live"] == 0
        assert eng.stats()["paged"]["window"]["pages_live"] == 0
        # a prompt inside two pages still runs
        assert eng.generate([5, 6, 7], 2, timeout=300)["finish"] == "length"
    finally:
        eng.close()


@pytest.mark.parametrize("refused", [
    {"prefix_reuse": True}, {"speculate": True}, {"prefill_chunk": 12},
    {"role": "prefill"}, {"role": "decode"}])
def test_window_model_refuses_what_walks_one_block_table(refused):
    """(Since PR 51 a chunk program walks both tables: a chunk of whole
    pages is taken, ``tests/test_command_a_plus.py``; one that is not
    whole pages is refused, as window pages go back page by page.)"""
    match = "multiple of page_tokens" if "prefill_chunk" in refused \
        else "sliding-window layers"
    with pytest.raises(ValueError, match=match):
        _engine(autostart=False, **refused)


def test_one_kind_model_keeps_one_pool():
    """No window layer: no second pool, no second feed, the gauges and
    the page arithmetic of before."""
    model = dict(MODEL, layer_pattern=[{"rope": False, "ffn": MOE}])
    eng = _engine(model, autostart=False)
    try:
        assert eng.kv.window is None and eng.num_window_pages == 0
        assert eng._decode_feeds == ["tokens", "positions", "block_tables",
                                     "live"]
        assert eng.page_bytes == 8 * 2 * PAGE * 32 * 4
        assert eng.stats()["paged"]["window"] is None
    finally:
        eng.close()


@pytest.mark.parametrize("change", ["rope", "window"])
def test_pattern_keys_change_what_they_say(change):
    """NoPE and the window are not inert."""
    base = dict(MODEL, layer_pattern=[{"window": None, "rope": True,
                                       "ffn": "dense"}], intermediate=48)
    entry = dict(base["layer_pattern"][0],
                 **({"rope": False} if change == "rope" else {"window": 8}))
    other = dict(base, layer_pattern=[entry])
    prompt = np.random.default_rng(9).integers(1, 97, 20).tolist()
    a = _engine(base)
    try:
        b = _engine(other, scope=a.scope)
        try:
            la = np.stack(a.generate(prompt, 3, timeout=300)["logits"])
            lb = np.stack(b.generate(prompt, 3, timeout=300)["logits"])
        finally:
            b.close()
    finally:
        a.close()
    assert _rel(lb, la) > 1e-3
