"""Paged KV cache tests: block-paged decode against the uncached
forward, shared-prefix copy-on-write reuse, page refcount lifecycle,
chunked-prefill interleaving, and pool-exhaustion ``cache_full``.

The reference roles: the plain engine (``plain_ref``: no prefix reuse,
no chunking, no speculation) answers to the uncached forward
(``conftest.uncached_logits``), which has no pages; every feature
engine here answers to the plain engine on the same weights (a child
of its scope: shared weights, pools of its own).

The load-bearing contracts (ISSUE 11 acceptance):

* **Routing through block tables** — the engine's token streams AND
  per-step logits are those of the uncached forward
  (``conftest.assert_logits_match``; tokens exactly) on ragged
  concurrent prompts spanning page boundaries (len = page-1 / page /
  page+1).  The mechanism: prefill scatters the forward's own K/V into
  the slot's pages, and ``kv_pool_gather`` rebuilds the logical cache
  layout for ``cached_attention`` at the forward's contraction length.
* **COW isolation** — pages a prefix-index hit maps into a slot are
  never written by that slot (decode and tail-prefill writes target
  pages past the shared prefix; idle/pad writes redirect to the trash
  page), so concurrent borrowers cannot corrupt each other — asserted
  both on token streams and on the raw pool bytes.
* **Refcounts** — a reclaimed slot's pages return to the free list
  except those the prefix index still holds; eviction frees them too.
* **Chunked prefill** — a long prompt pays out one chunk per scheduler
  iteration while a rider keeps decoding (decode steps advance between
  chunks), and the rider's stream stays bit-exact.
* **Pool exhaustion** — a budget beyond the pool finishes
  ``cache_full`` with exactly ``usable_pages * page_tokens -
  prompt_len + 1`` tokens.
"""
import time

import numpy as np
import pytest
from conftest import assert_logits_match, uncached_logits

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.serving import GenerationEngine, batcher

# GQA config (kv_heads < heads) so the paged gather runs under cache
# expansion, matching tests/test_generation.py
MODEL = dict(vocab_size=61, hidden=32, num_layers=2, num_heads=4,
             num_kv_heads=2, intermediate=64)
PAGE = 16


def _paged(scope=None, **kw):
    base = dict(num_slots=3, max_seq_len=96, max_new_tokens=8,
                keep_logits=True, attn_impl="xla", seed=0,
                queue_cap=64, deadline_ms=600000.0,
                page_tokens=PAGE, prefill_chunk=0, prefix_reuse=False,
                # (held to the float32 uncached forward: the program the
                # engine's rule would pick, bfloat16, is held to it at a
                # tolerance in tests/test_serving_dtype.py)
                dtype="float32")
    base.update(kw)
    return GenerationEngine(MODEL, scope=scope, **base)


@pytest.fixture(scope="module")
def plain_ref():
    """The plain engine every feature engine answers to.  They take a
    child of its scope: the same weights, page pools of their own."""
    eng = _paged()
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def paged_ref(plain_ref):
    """Module-shared engine with prefix reuse ON (chunking off) —
    one program-build cost for the bit-exactness / COW / refcount
    tests; tests needing deterministic pool counts drain the prefix
    index first via :func:`_drain_index`."""
    eng = _paged(plain_ref.scope.new_scope(), prefix_reuse=True)
    yield eng
    eng.close()


def _drain_index(eng):
    eng.kv.flush_prefix()
    assert eng.kv.live_pages() == 0


# ---------------------------------------------------------------------------
# op level: scatter/gather round trip + trash-page redirect
# ---------------------------------------------------------------------------

def test_kv_pool_write_gather_roundtrip():
    """Rows land in the block-table-routed pages at the right in-page
    offsets; rows beyond Lengths redirect to the trash page; gather
    reassembles the logical layout."""
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        block = main.global_block()
        pool = block.create_var(name="t_pool", persistable=True,
                                shape=[4, 1, 4, 2], dtype="float32",
                                stop_gradient=True)
        new = layers.data("new", [2, 1, 3, 2], dtype="float32",
                          append_batch_size=False)
        positions = layers.data("positions", [2], dtype="int32",
                                append_batch_size=False)
        bt = layers.data("bt", [2, 2], dtype="int32",
                         append_batch_size=False)
        lengths = layers.data("lengths", [2], dtype="int32",
                              append_batch_size=False)
        out = layers.kv_pool_write(pool, new, positions, bt, lengths)
        view = layers.kv_pool_gather(out, bt)
    scope = pt.Scope()
    scope.set_var("t_pool", np.zeros((4, 1, 4, 2), "float32"))
    new_v = np.arange(12, dtype="float32").reshape(2, 1, 3, 2)
    # slot 0: 3 rows from logical position 3 (crosses page boundary
    # 3 -> page bt[0,0]=1 off 3; 4,5 -> page bt[0,1]=2 off 0,1)
    # slot 1: only 1 valid row at logical 0 -> page bt[1,0]=3 off 0;
    # its 2 invalid rows must land on the trash page 0
    got_pool, got_view = pt.Executor().run(
        main,
        feed={"new": new_v,
              "positions": np.array([3, 0], "int32"),
              "bt": np.array([[1, 2], [3, 0]], "int32"),
              "lengths": np.array([3, 1], "int32")},
        fetch_list=[out, view], scope=scope)
    want = np.zeros((4, 1, 4, 2), "float32")
    want[1, 0, 3] = new_v[0, 0, 0]
    want[2, 0, 0] = new_v[0, 0, 1]
    want[2, 0, 1] = new_v[0, 0, 2]
    want[3, 0, 0] = new_v[1, 0, 0]
    # trash page (0) caught the two invalid rows of slot 1 — exact
    # contents indeterminate (duplicate scatter), but nothing else may
    # be touched
    assert np.array_equal(got_pool[1:], want[1:])
    # gather: slot 0's logical view is pages [1, 2] flattened
    assert np.array_equal(got_view[0, :, 0:8],
                          got_pool[[1, 2]].reshape(1, 8, 2))
    assert np.array_equal(got_view[1, :, 0:4],
                          got_pool[[3]].reshape(1, 4, 2))


def _pool_write(pool0, new, base, table, n, **form):
    """One ``kv_pool_write`` of ``new`` [B, Hkv, T, D] into a copy of
    ``pool0`` through the executor, in the form the attrs ask for."""
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        pool = main.global_block().create_var(
            name="w_pool", persistable=True, shape=list(pool0.shape),
            dtype="float32", stop_gradient=True)
        feeds = {"new": new, "base": base, "table": table, "n": n}
        args = [layers.data(k, list(v.shape), dtype=str(v.dtype),
                            append_batch_size=False)
                for k, v in feeds.items()]
        out = layers.kv_pool_write(pool, *args, **form)
    scope = pt.Scope()
    scope.set_var("w_pool", pool0.copy())
    got, = pt.Executor().run(main, feed=feeds, fetch_list=[out],
                             scope=scope)
    return got


# page 4, a rung of four pages, a slot of six: prompt lengths 1, k * pt,
# k * pt + 1 and the whole rung
@pytest.mark.parametrize("n", [1, 8, 9, 16])
@pytest.mark.parametrize("table", ["permuted", "window"])
@pytest.mark.parametrize("head_dim", [8, 64])
def test_whole_pages_write_leaves_the_bytes_the_row_form_leaves(
        n, table, head_dim):
    """The whole-prompt prefill's page-by-page write against the [Hkv, D]
    window a row: every page but the trash page byte for byte.  The
    pool's pages that the table does not name, and the rows behind the
    prompt's end in its last page, hold NaN and must keep it; a window
    layer's table sends the pages its window left to the trash page; a
    head of 64 lies packed two heads a row."""
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.ops.decode_ops import pool_shape

    rng = np.random.default_rng(100 * n + head_dim)
    pt_, T, NP, Hkv = 4, 16, 6, 2
    P = 2 * NP + 1
    new = rng.normal(size=(1, Hkv, T, head_dim)).astype("float32")
    pool0 = rng.normal(size=pool_shape(P, Hkv, pt_, head_dim))
    pool0 = pool0.astype("float32")
    bt = rng.permutation(np.arange(1, P))[:NP].astype("int32")[None]
    named = set(bt[0].tolist())
    live = -(-n // pt_)
    if table == "window":          # the window covers the last two pages
        bt[0, :max(0, live - 2)] = 0
    for page in range(1, P):
        if page not in named:
            pool0[page] = np.nan
    if n % pt_:
        pool0[bt[0, live - 1], :, n % pt_:] = np.nan
    feeds = (new, np.zeros(1, "int32"), bt, np.asarray([n], "int32"))

    before = {k: stat_get("kv_pool_write_" + k) for k in ("pages", "rows")}
    want = _pool_write(pool0, *feeds)
    assert stat_get("kv_pool_write_rows") == before["rows"] + 1
    got = _pool_write(pool0, *feeds, whole_pages=True)
    assert stat_get("kv_pool_write_pages") == before["pages"] + 1
    assert stat_get("kv_pool_write_rows") == before["rows"] + 1
    assert got[1:].tobytes() == want[1:].tobytes()
    # and the row form's bytes are the right ones: the prompt's rows where
    # the table says, everything else as it was
    touched = np.zeros(P, bool)
    for i in range(live):
        page, rows = bt[0, i], min(pt_, n - i * pt_)
        if page == 0:
            continue
        touched[page] = True
        fresh = new[0, :, i * pt_:i * pt_ + rows]         # [Hkv, rows, D]
        fresh = fresh.transpose(1, 0, 2).reshape(rows, -1, pool0.shape[3])
        assert np.array_equal(got[page, :, :rows], fresh.transpose(1, 0, 2))
        assert np.array_equal(got[page, :, rows:], pool0[page, :, rows:],
                              equal_nan=True)
    assert touched.sum() == (live if table == "permuted" else min(live, 2))
    untouched = ~touched
    untouched[0] = False
    assert np.array_equal(got[untouched], pool0[untouched], equal_nan=True)


def test_whole_pages_write_from_a_later_page_boundary():
    """``whole_pages`` takes any page boundary for its base, as the row
    form takes any position."""
    rng = np.random.default_rng(5)
    pool0 = rng.normal(size=(9, 2, 4, 8)).astype("float32")
    feeds = (rng.normal(size=(1, 2, 8, 8)).astype("float32"),
             np.asarray([8], "int32"),
             rng.permutation(np.arange(1, 9))[None, :6].astype("int32"),
             np.asarray([7], "int32"))
    want = _pool_write(pool0, *feeds)
    got = _pool_write(pool0, *feeds, whole_pages=True)
    assert got[1:].tobytes() == want[1:].tobytes()
    assert not np.array_equal(got, pool0)


@pytest.mark.parametrize("B,T", [(1, 6), (1, 3), (2, 8)])
def test_whole_pages_write_refuses_what_is_not_one_slots_whole_pages(B, T):
    """A rung that is not a whole number of pages, or more than one slot,
    has no page form: the op says so when the program is lowered."""
    pool0 = np.zeros((5, 2, 4, 8), "float32")
    with pytest.raises(Exception, match="whole_pages"):
        _pool_write(pool0, np.zeros((B, 2, T, 8), "float32"),
                    np.zeros(B, "int32"), np.ones((B, 4), "int32"),
                    np.full(B, T, "int32"), whole_pages=True)


@pytest.mark.parametrize("bucket,whole", [(32, True), (24, False)])
def test_the_whole_prompt_prefill_asks_for_whole_pages(bucket, whole):
    """The builder of the whole-prompt prefill asks for the page form
    where its bucket is whole pages, and for nothing else; the chunk
    program, whose base position is a feed, never does."""
    from paddle_tpu.models.llama import (build_llama_prefill,
                                         build_llama_prefill_chunk)

    def attrs(build):
        main, startup = pt.Program(), pt.Program()
        startup._is_startup = True
        with pt.program_guard(main, startup):
            build()
        return [op.attr("whole_pages", False)
                for op in main.global_block().ops
                if op.type == "kv_pool_write"]

    got = attrs(lambda: build_llama_prefill(
        1, bucket, name="wp", cache_slots=2, max_seq_len=96, num_pages=13,
        page_tokens=PAGE, **MODEL))
    assert got == [whole] * (2 * MODEL["num_layers"])
    got = attrs(lambda: build_llama_prefill_chunk(
        bucket, 96, 13, PAGE, name="wp", **MODEL))
    assert got == [False] * (2 * MODEL["num_layers"])


def test_chunk_spans():
    assert batcher.chunk_spans(0, 20, 8) == [(0, 8), (8, 16), (16, 20)]
    assert batcher.chunk_spans(32, 40, 8) == [(32, 40)]
    assert batcher.chunk_spans(5, 5, 8) == []
    assert batcher.chunk_spans(0, 20, 0) == [(0, 20)]


# ---------------------------------------------------------------------------
# bit-exactness: paged == the uncached forward, across page boundaries
# ---------------------------------------------------------------------------

def test_paged_bitexact_concurrent_ragged(paged_ref):
    """Prompts of page-1 / page / page+1 tokens decode CONCURRENTLY in
    the paged grid; every request's token stream and per-step logits
    are those of its own uncached forward, which knows no pages.  (The
    prompts are distinct randoms — no prefix hits — so this exercises
    the pure paged path; registration alone cannot perturb streams.)"""
    eng = paged_ref
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, MODEL["vocab_size"], size=n).tolist()
               for n in (PAGE - 1, PAGE, PAGE + 1)]
    steps = [6, 5, 7]
    futs = [eng.submit(p, n) for p, n in zip(prompts, steps)]
    for prompt, n, res in zip(prompts, steps,
                              [f.result(120) for f in futs]):
        assert res["finish"] == "length" and len(res["tokens"]) == n
        ref = uncached_logits(eng, prompt + res["tokens"][:-1])
        want = ref[len(prompt) - 1:len(prompt) - 1 + n]
        assert res["tokens"] == [int(t) for t in want.argmax(-1)]
        for i, got in enumerate(res["logits"]):
            assert_logits_match(got, want[i],
                                f"step {i}: paged vs the uncached forward")
    # every slot-held page was returned: only index-registered full
    # prefix pages stay live
    st = eng.stats()["paged"]
    assert st["pages_live"] == st["prefix_index_entries"]
    _drain_index(eng)


# ---------------------------------------------------------------------------
# shared-prefix reuse: hits skip prefill, COW isolation holds
# ---------------------------------------------------------------------------

def test_prefix_reuse_cow_isolation(plain_ref, paged_ref):
    """Requests sharing a page-aligned system header reuse its pages:
    the borrowers skip the header's prefill (counters prove it), their
    token streams are the plain engine's, concurrent borrowers don't
    corrupt each other, and the shared pages' raw bytes are untouched
    by the borrowers' decode writes (the COW contract)."""
    eng = paged_ref
    _drain_index(eng)
    hits0 = eng.stats()["counters"]["prefix_hits"]
    rng = np.random.RandomState(11)
    header = rng.randint(1, MODEL["vocab_size"], size=2 * PAGE
                         ).tolist()
    tails = [rng.randint(1, MODEL["vocab_size"], size=7).tolist()
             for _ in range(3)]
    # donor run registers the header's 2 pages
    ra = eng.generate(header + tails[0], 6)
    refs = [plain_ref.generate(header + t, 6) for t in tails]
    assert ra["tokens"] == refs[0]["tokens"]
    assert eng.stats()["counters"]["prefix_hits"] == hits0
    # shared-page bytes before the borrowers run
    idx_pages = sorted(
        p for p in range(1, eng.num_pages)
        if eng.kv.refcount(p) > 0)
    assert len(idx_pages) == 2
    pool_k0 = np.asarray(eng.scope.find_var("llama.pool_k_0"))
    shared_before = pool_k0[idx_pages].copy()
    # two borrowers decode CONCURRENTLY, both hitting the header
    futs = [eng.submit(header + t, 6) for t in tails[1:]]
    results = [f.result(120) for f in futs]
    for res, ref in zip(results, refs[1:]):
        assert res["tokens"] == ref["tokens"], \
            "borrower stream drifted — shared pages corrupted?"
        assert res["prefix_hit_tokens"] == 2 * PAGE
    st = eng.stats()
    assert st["counters"]["prefix_hits"] == hits0 + 2
    # the reused pages' bytes are bit-identical after the borrowers
    # wrote their private pages
    pool_k0 = np.asarray(eng.scope.find_var("llama.pool_k_0"))
    assert np.array_equal(pool_k0[idx_pages], shared_before), \
        "a borrower's write leaked into a shared prefix page"


def test_refcount_release_on_reclaim(paged_ref):
    """Finished slots return every private page; only the prefix
    index's refs persist, and eviction releases those too."""
    eng = paged_ref
    _drain_index(eng)
    rng = np.random.RandomState(13)
    header = rng.randint(1, MODEL["vocab_size"], size=PAGE).tolist()
    for i in range(3):
        tail = rng.randint(1, MODEL["vocab_size"], size=5).tolist()
        eng.generate(header + tail, 4)
    st = eng.stats()["paged"]
    # exactly the 1 indexed header page is live; all private pages
    # (tail + decode growth, per request) went back to the free list
    # at slot reclaim
    assert st["prefix_index_entries"] == 1
    assert st["pages_live"] == 1
    assert st["pages_free"] == eng.num_pages - 2
    assert eng.kv_live_bytes == eng.page_bytes
    _drain_index(eng)


# ---------------------------------------------------------------------------
# chunked prefill: long prompts interleave with decode steps
# ---------------------------------------------------------------------------

def test_chunked_prefill_interleaves_decode(plain_ref):
    """A long prompt pays out in chunks while a rider keeps decoding:
    decode steps advance BETWEEN chunks (one chunk per scheduler
    iteration — the inter-token-latency bound), and both streams stay
    correct."""
    eng = _paged(plain_ref.scope.new_scope(), prefill_chunk=8,
                 max_new_tokens=64)
    try:
        rng = np.random.RandomState(17)
        rider_prompt = rng.randint(1, MODEL["vocab_size"],
                                   size=4).tolist()
        long_prompt = rng.randint(1, MODEL["vocab_size"],
                                  size=40).tolist()
        rider_fut = eng.submit(rider_prompt, 36)
        deadline = time.monotonic() + 60
        while eng.stats()["counters"]["decode_steps"] < 3:
            assert time.monotonic() < deadline, "rider never decoded"
            time.sleep(0.01)
        s0 = eng.stats()["counters"]
        long_res = eng.submit(long_prompt, 4).result(120)
        s1 = eng.stats()["counters"]
        chunks = s1["prefill_chunks"] - s0["prefill_chunks"]
        assert chunks == 5  # ceil(40 / 8)
        # the rider decoded between chunks: >= one decode step per
        # chunk boundary (the scheduler runs at most one chunk, then a
        # grid step, per iteration)
        assert s1["decode_steps"] - s0["decode_steps"] >= chunks - 1
        rider_res = rider_fut.result(120)
        ref_long = plain_ref.generate(long_prompt, 4)
        rider_ref = plain_ref.generate(rider_prompt, 36)
        assert long_res["tokens"] == ref_long["tokens"]
        assert rider_res["tokens"] == rider_ref["tokens"], \
            "rider stream corrupted by interleaved chunk prefill"
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# pool exhaustion: cache_full exactness + recovery
# ---------------------------------------------------------------------------

def test_whole_prompt_prefills_first_come_first_served(plain_ref):
    """Unchunked, slots claimed in one pass prefill in the order their
    requests arrived: one whole prompt an iteration, oldest first (the
    round-robin cursor is for slices of chunked prompts)."""
    from paddle_tpu import telemetry

    old = pt.get_flags(["FLAGS_telemetry"])
    pt.set_flags({"FLAGS_telemetry": True})
    eng = _paged(plain_ref.scope.new_scope(), autostart=False)
    try:
        rng = np.random.default_rng(3)
        futures = [eng.submit(rng.integers(1, 61, 20).tolist(), 2)
                   for _ in range(3)]
        telemetry.clear_spans()
        eng.start()                      # all three claimed at once
        slots = [f.result(300)["slot"] for f in futures]
        # the engine's own record, not three host-clock readings that
        # differ by less than the clock's jitter: the prefill launches in
        # the order they were made, by the slot each filled
        launched = [s.attrs["slot"] for s in telemetry.get_spans()
                    if s.name == "generation/prefill"]
        assert sorted(slots) == [0, 1, 2] and launched == slots
    finally:
        eng.close()
        pt.set_flags(old)


def test_pool_exhaustion_cache_full(plain_ref):
    """A budget beyond the pool finishes cache_full with EXACTLY
    usable_pages * page_tokens - prompt_len + 1 tokens (every page
    filled, the +1 is the prefill's token which costs no cache row
    until the step after), and the freed pages serve the next
    request."""
    eng = GenerationEngine(MODEL, scope=plain_ref.scope.new_scope(),
                           num_slots=1, max_seq_len=96, attn_impl="xla",
                           seed=0, queue_cap=64, deadline_ms=600000.0,
                           page_tokens=8, num_pages=5,
                           prefill_chunk=0, prefix_reuse=False)
    try:
        prompt = list(range(1, 11))          # 10 tokens
        capacity = (eng.num_pages - 1) * eng.page_tokens  # 32
        res = eng.generate(prompt, 500)
        assert res["finish"] == "cache_full"
        assert len(res["tokens"]) == capacity - len(prompt) + 1
        # pool drained and fully recovered
        assert eng.kv.live_pages() == 0
        res2 = eng.generate(prompt, 500)
        assert res2["finish"] == "cache_full"
        assert res2["tokens"] == res["tokens"]
    finally:
        eng.close()


def test_loadgen_shared_prefix_prompts():
    """tools/serving_loadgen.py --gen-prompt-dist shared-prefix: every
    prompt starts with the SAME header, tails vary, determinism
    holds."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "lg", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "serving_loadgen.py"))
    lg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lg)
    mk = lg.prompt_maker(64, 4, 8, 8.0, 16, pool=32,
                         prompt_dist="shared-prefix", prefix_tokens=24)
    mk2 = lg.prompt_maker(64, 4, 8, 8.0, 16, pool=32,
                          prompt_dist="shared-prefix", prefix_tokens=24)
    header = mk(0)[0][:24]
    tails = set()
    for i in range(32):
        p, out_len = mk(i)
        assert np.array_equal(p[:24], header)
        assert 24 + 4 <= p.size <= 24 + 8
        assert 1 <= out_len <= 16
        assert np.array_equal(p, mk2(i)[0])  # deterministic
        tails.add(p[24:].tobytes())
    assert len(tails) > 1  # tails actually vary
    with pytest.raises(ValueError):
        lg.prompt_maker(64, 4, 8, 8.0, 16, prompt_dist="zipf")
    with pytest.raises(ValueError):
        lg.prompt_maker(64, 4, 8, 8.0, 16,
                        prompt_dist="shared-prefix", prefix_tokens=0)


def test_pool_stall_requeues_until_pages_free(plain_ref):
    """Pool exhaustion during PREFILL while other sequences hold the
    pages is transient saturation, not a broken request: the prefill
    requeues at the queue head (`serving_kv_pool_stalls`) and succeeds
    once the live sequence finishes — zero failed requests."""
    eng = GenerationEngine(MODEL, scope=plain_ref.scope.new_scope(),
                           num_slots=2, max_seq_len=64, attn_impl="xla",
                           seed=0, queue_cap=64, deadline_ms=600000.0,
                           page_tokens=8, num_pages=6,
                           prefill_chunk=0, prefix_reuse=False,
                           autostart=False)
    try:
        rng = np.random.RandomState(19)
        # A: short prompt, long budget — claims first, holds pages
        # while decoding.  B: 30-token prompt needing 4 pages; only 3
        # are free while A lives -> deterministic stall, then success
        fa = eng.submit(rng.randint(1, MODEL["vocab_size"],
                                    size=10).tolist(), 24)
        b_prompt = rng.randint(1, MODEL["vocab_size"],
                               size=30).tolist()
        fb = eng.submit(b_prompt, 4)
        eng.start()
        ra, rb = fa.result(120), fb.result(120)
        assert ra["finish"] == "length" and rb["finish"] == "length"
        ref = plain_ref.generate(b_prompt, 4)
        assert rb["tokens"] == ref["tokens"]
        n = eng.stats()["counters"]
        assert n["pool_stalls"] >= 1
        assert n["failed"] == 0
    finally:
        eng.close()


def test_paged_config_validation():
    with pytest.raises(ValueError):  # not a power of two
        GenerationEngine(MODEL, num_slots=1, max_seq_len=96,
                         autostart=False, page_tokens=12)
    with pytest.raises(ValueError):  # does not divide max_seq_len
        GenerationEngine(MODEL, num_slots=1, max_seq_len=100,
                         autostart=False, page_tokens=16)
    # the keyword the benchmark builders still pass refuses the cache
    # that is gone
    with pytest.raises(ValueError, match="removed at PR 30"):
        GenerationEngine(MODEL, num_slots=1, max_seq_len=96,
                         autostart=False, paged=False)
