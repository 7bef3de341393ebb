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
from paddle_tpu.serving.generation import PagePool, PrefixIndex

# GQA config (kv_heads < heads) so the paged gather runs under cache
# expansion, matching tests/test_generation.py
MODEL = dict(vocab_size=61, hidden=32, num_layers=2, num_heads=4,
             num_kv_heads=2, intermediate=64)
PAGE = 16


def _paged(scope=None, **kw):
    base = dict(num_slots=3, max_seq_len=96, max_new_tokens=8,
                keep_logits=True, attn_impl="xla", seed=0,
                queue_cap=64, deadline_ms=600000.0,
                page_tokens=PAGE, prefill_chunk=0, prefix_reuse=False)
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
    while eng._prefix is not None and eng._prefix.evict_one():
        pass
    assert eng._pool.live_pages == 0


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


def test_chunk_spans():
    assert batcher.chunk_spans(0, 20, 8) == [(0, 8), (8, 16), (16, 20)]
    assert batcher.chunk_spans(32, 40, 8) == [(32, 40)]
    assert batcher.chunk_spans(5, 5, 8) == []
    assert batcher.chunk_spans(0, 20, 0) == [(0, 20)]


# ---------------------------------------------------------------------------
# allocator / prefix index units
# ---------------------------------------------------------------------------

def test_page_pool_refcounts():
    pool = PagePool(5)  # pages 1..4 usable
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {1, 2} and pool.free_pages == 2
    pool.incref([a])          # a shared (slot + index)
    pool.decref([a, b])       # slot releases both
    assert pool.free_pages == 3 and pool.refcount(a) == 1
    pool.decref([a])          # index releases a
    assert pool.free_pages == 4 and pool.live_pages == 0
    assert pool.alloc() is not None
    with pytest.raises(ValueError):
        PagePool(1)           # no room beyond the trash page


def test_prefix_index_lookup_register_evict():
    pool = PagePool(8)
    idx = PrefixIndex(pool, 4)
    prompt = np.arange(1, 11, dtype="int64")     # 10 tokens, 2 full pages
    p0, p1 = pool.alloc(), pool.alloc()
    idx.register(prompt, [p0, p1])
    assert pool.refcount(p0) == 2 and pool.refcount(p1) == 2
    # exact-prefix hit; a diverging prompt misses
    assert idx.lookup(np.arange(1, 14, dtype="int64")) == [p0, p1]
    other = np.arange(1, 14, dtype="int64")
    other[2] = 55
    assert idx.lookup(other) == []
    # a prompt equal to one indexed page must leave >= 1 token to
    # prefill: only page 0 may be served for a 5-token prompt, and
    # NOTHING for a 4-token prompt
    assert idx.lookup(np.arange(1, 6, dtype="int64")) == [p0]
    assert idx.lookup(np.arange(1, 5, dtype="int64")) == []
    pool.decref([p0, p1])     # the registering slot finishes
    assert pool.free_pages == 5  # 7 usable; index still holds p0, p1
    assert idx.evict_one() and pool.free_pages == 6
    assert idx.evict_one() and pool.free_pages == 7
    assert not idx.evict_one()
    # flush: the decode-crash integrity valve drops every entry
    q0, q1 = pool.alloc(), pool.alloc()
    idx.register(prompt, [q0, q1])
    pool.decref([q0, q1])
    assert idx.flush() == 2 and len(idx) == 0
    assert pool.free_pages == 7 and pool.live_pages == 0


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
        if eng._pool.refcount(p) > 0)
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
    eng = _paged(plain_ref.scope.new_scope(), autostart=False)
    try:
        rng = np.random.default_rng(3)
        futures = [eng.submit(rng.integers(1, 61, 20).tolist(), 2)
                   for _ in range(3)]
        eng.start()                      # all three claimed at once
        ttft = [f.result(300)["ttft_ms"] for f in futures]
        assert ttft == sorted(ttft)
    finally:
        eng.close()


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
        assert eng._pool.live_pages == 0
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
