"""Speculative decoding tests: n-gram self-drafting, one-chunk
verification, bit-exact acceptance, and rejected-draft page rollback.

The load-bearing contracts (ISSUE 17 acceptance):

* **Bit-exact vs non-speculative decode** — a speculating engine's
  token streams (exactly) AND per-step logits
  (``conftest.assert_logits_match``: a verify chunk and a grid step are
  two batch shapes of one matmul) equal the plain engine's: verify row
  0 writes exactly what
  the plain step writes, accepted rows replay the same argmax chain,
  and rejected rows' garbage K/V is causally masked and overwritten.
  Holds at page-boundary ±1 prompt lengths, with concurrent MIXED
  speculating/plain slots, and through prefix-index hits.
* **Drafter** — longest-suffix n-gram match over the sequence's own
  prompt + generated history; the LAST earlier occurrence wins; no
  match / degenerate history / k<1 propose nothing (the slot falls
  through to the plain one-token step).
* **Rollback accounting** — rejected drafts decref their provisional
  pages; after every request drains the pool returns to zero live
  pages, including when the pool exhausts MID-DRAFT.
* **Opt-out** — ``submit(..., speculate=False)`` (and the HTTP
  ``"speculate"`` field) bypasses drafting per-request.

All engines read the plain reference's weights: weight init depends on
global state, so only engines on one scope bind identical weights
(the ``tests/test_paged_generation.py`` pattern).  Each takes a child
of that scope, so its page pools are its own.
"""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest
from conftest import assert_logits_match

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.serving import GenerationEngine, ServingEngine, serve
from paddle_tpu.serving.generation import ngram_draft

MODEL = dict(vocab_size=61, hidden=32, num_layers=2, num_heads=4,
             num_kv_heads=2, intermediate=64)
PAGE = 8


def _spec(plain, **kw):
    base = dict(num_slots=3, max_seq_len=96, max_new_tokens=8,
                keep_logits=True, attn_impl="xla", seed=0,
                queue_cap=64, deadline_ms=600000.0,
                page_tokens=PAGE, prefill_chunk=0, prefix_reuse=False,
                speculate=True, spec_tokens=4, spec_ngram=3)
    base.update(kw)
    return GenerationEngine(
        MODEL, scope=plain.scope.new_scope() if plain else None, **base)


@pytest.fixture(scope="module")
def plain_ref():
    """The plain non-speculative reference (it answers to the uncached
    forward in ``tests/test_generation.py``); spec engines read its
    weights through a child of its scope."""
    eng = _spec(None, speculate=False)
    yield eng
    eng.close()


def _repetitive(rng, n, period=4):
    """A period-`period` prompt: every suffix n-gram has an earlier
    occurrence, so the drafter proposes every round."""
    pattern = rng.randint(1, MODEL["vocab_size"], size=period).tolist()
    return (pattern * (n // period + 1))[:n]


# ---------------------------------------------------------------------------
# drafter units
# ---------------------------------------------------------------------------

def test_ngram_draft_hit():
    # suffix [2, 3] recurs at index 1; the following tokens are the draft
    assert ngram_draft([1, 2, 3, 4, 2, 3], 3, 3) == [4, 2, 3]
    # k caps the proposal
    assert ngram_draft([1, 2, 3, 4, 2, 3], 1, 3) == [4]


def test_ngram_draft_last_occurrence_wins():
    # [1, 2] occurs at 0 (followed by 9) and at 3 (followed by 7): the
    # most recent occurrence is the better n-gram LM estimate
    assert ngram_draft([1, 2, 9, 1, 2, 7, 1, 2], 1, 2) == [7]


def test_ngram_draft_longest_ngram_first():
    # the trigram [9, 1, 2] matches at index 2 and beats the more
    # recent bigram-only match of [1, 2]
    h = [5, 9, 1, 2, 8, 1, 2, 6, 9, 1, 2]
    assert ngram_draft(h, 1, 3) == [8]


def test_ngram_draft_miss_and_guards():
    assert ngram_draft([1, 2, 3], 3, 3) == []     # no recurrence
    assert ngram_draft([1, 2, 3, 4], 0, 3) == []  # k < 1
    assert ngram_draft([7], 3, 3) == []           # history too short
    assert ngram_draft([], 3, 3) == []


def test_ngram_draft_degenerate_repetition():
    # [5, 5, 5, 5]: suffix trigram matches at index 0, only one token
    # follows — a short draft, not an infinite self-match
    assert ngram_draft([5, 5, 5, 5], 4, 3) == [5]


# ---------------------------------------------------------------------------
# bit-exactness: speculating == plain
# ---------------------------------------------------------------------------

def _assert_streams_equal(ref_results, got_results):
    for a, b in zip(ref_results, got_results):
        assert a["tokens"] == b["tokens"]
        assert a["finish"] == b["finish"]
        for i, (la, lb) in enumerate(zip(a["logits"], b["logits"])):
            assert_logits_match(lb, la,
                                f"step {i}: speculative vs plain")


def test_spec_bitexact_concurrent_ragged(plain_ref):
    """Repetitive prompts of page-1 / page / page+1 tokens decode
    concurrently with speculation on; every stream and per-step logit
    vector is the plain non-speculative engine's, and the
    drafter demonstrably fired (otherwise the test is vacuous)."""
    rng = np.random.RandomState(11)
    prompts = [_repetitive(rng, n) for n in (PAGE - 1, PAGE, PAGE + 1)]
    steps = [6, 5, 7]
    rd = [f.result(120) for f in
          [plain_ref.submit(p, n) for p, n in zip(prompts, steps)]]
    eng = _spec(plain_ref)
    try:
        rs = [f.result(120) for f in
              [eng.submit(p, n) for p, n in zip(prompts, steps)]]
        _assert_streams_equal(rd, rs)
        sp = eng.stats()["speculate"]
        assert sp["drafts"] > 0 and sp["tokens_proposed"] > 0
        assert sp["tokens_accepted"] <= sp["tokens_proposed"]
        assert eng.kv.live_pages() == 0
    finally:
        eng.close()


def test_spec_bitexact_mixed_slots(plain_ref):
    """Speculating and per-request-opted-out slots decode CONCURRENTLY
    in one grid (the mixed-grid path: ``_decode_step(skip=...)``);
    every stream matches plain regardless of which side of the fence
    it decoded on."""
    rng = np.random.RandomState(13)
    prompts = [_repetitive(rng, n) for n in (PAGE - 1, PAGE + 1, 12)]
    steps = [7, 6, 7]
    flags = [None, False, None]  # slot 1 opts out mid-grid
    rd = [f.result(120) for f in
          [plain_ref.submit(p, n) for p, n in zip(prompts, steps)]]
    eng = _spec(plain_ref)
    try:
        fs = [eng.submit(p, n, speculate=sp)
              for p, n, sp in zip(prompts, steps, flags)]
        rs = [f.result(120) for f in fs]
        _assert_streams_equal(rd, rs)
        assert eng.stats()["speculate"]["drafts"] > 0
    finally:
        eng.close()


def test_spec_bitexact_prefix_hits(plain_ref):
    """Streams riding prefix-index hits (borrowed COW pages, tail-only
    prefill) speculate bit-exactly: a plain paged engine and a
    speculating one see the same submission order, take the same index
    hits, and emit identical tokens AND logits."""
    rng = np.random.RandomState(17)
    header = _repetitive(rng, 2 * PAGE)  # two full shared pages
    prompts = [header + _repetitive(rng, 5) for _ in range(3)]
    steps = [6, 6, 6]

    def run(speculate):
        eng = _spec(plain_ref, prefix_reuse=True, speculate=speculate)
        try:
            out = [eng.submit(p, n).result(120)
                   for p, n in zip(prompts, steps)]
            st = eng.stats()
            return out, st
        finally:
            eng.close()

    plain, st_plain = run(False)
    spec, st_spec = run(True)
    _assert_streams_equal(plain, spec)
    assert st_plain["counters"]["prefix_hits"] > 0
    assert st_spec["counters"]["prefix_hits"] > 0
    assert st_spec["speculate"]["drafts"] > 0


# ---------------------------------------------------------------------------
# rollback accounting
# ---------------------------------------------------------------------------

def test_spec_rollback_refcount_balance(plain_ref):
    """Rejected drafts roll their provisional pages back: rollbacks
    fire (the tiny random model rarely follows the prompt's period),
    accepted <= proposed, and the pool drains to ZERO live pages once
    every request finishes."""
    rng = np.random.RandomState(19)
    eng = _spec(plain_ref)
    try:
        for n in (PAGE - 1, PAGE, PAGE + 1, 12):
            eng.generate(_repetitive(rng, n), 8)
        sp = eng.stats()["speculate"]
        assert sp["drafts"] > 0
        assert sp["rollbacks"] >= 1
        assert sp["rollbacks"] <= sp["drafts"]
        assert sp["tokens_accepted"] <= sp["tokens_proposed"]
        assert 0.0 <= sp["acceptance_rate"] <= 1.0
        assert eng.kv.live_pages() == 0
    finally:
        eng.close()


def test_spec_pool_exhaustion_mid_draft(plain_ref):
    """A draft that cannot get pages falls through to the plain step,
    which finishes the sequence ``cache_full`` at EXACTLY the plain
    engine's truncation point with the plain engine's tokens — then
    the freed pages serve the next request (full recovery)."""
    def run(speculate):
        eng = GenerationEngine(MODEL, scope=plain_ref.scope.new_scope(),
                               num_slots=1, max_seq_len=96,
                               attn_impl="xla", seed=0, queue_cap=64,
                               deadline_ms=600000.0,
                               page_tokens=PAGE, num_pages=5,
                               prefill_chunk=0, prefix_reuse=False,
                               speculate=speculate, spec_tokens=4,
                               spec_ngram=3)
        try:
            rng = np.random.RandomState(23)
            prompt = _repetitive(rng, 10)
            res = eng.generate(prompt, 500)
            live = eng.kv.live_pages()
            res2 = eng.generate(prompt, 500)
            sp = eng.stats()["speculate"]
            return res, live, res2, sp
        finally:
            eng.close()

    res_p, live_p, res2_p, _ = run(False)
    res_s, live_s, res2_s, sp = run(True)
    capacity = 4 * PAGE  # (num_pages - 1) usable, page 0 is trash
    assert res_p["finish"] == res_s["finish"] == "cache_full"
    assert len(res_p["tokens"]) == capacity - 10 + 1
    assert res_s["tokens"] == res_p["tokens"]
    assert live_p == live_s == 0
    assert res2_s["tokens"] == res2_p["tokens"] == res_p["tokens"]
    assert sp["drafts"] > 0  # speculation ran before the pool dried


# ---------------------------------------------------------------------------
# opt-out
# ---------------------------------------------------------------------------

def test_spec_per_request_opt_out(plain_ref):
    """speculate=False per request on a speculating engine: zero
    drafts, stream identical to plain."""
    rng = np.random.RandomState(29)
    prompt = _repetitive(rng, PAGE + 2)
    ref = plain_ref.generate(prompt, 7)
    eng = _spec(plain_ref)
    try:
        res = eng.submit(prompt, 7, speculate=False).result(120)
        assert res["tokens"] == ref["tokens"]
        sp = eng.stats()["speculate"]
        assert sp["drafts"] == 0 and sp["tokens_proposed"] == 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# HTTP e2e
# ---------------------------------------------------------------------------

def _post(url, doc):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, json.loads(r.read())


def _tiny_predictor():
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        out = layers.fc(x, 2, name="spec_http_fc")
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    from paddle_tpu.inference import Predictor
    return Predictor(main, ["x"], [out], scope=scope)


def test_http_generate_speculate(plain_ref):
    """POST /generate carries the per-request ``"speculate"`` field
    end-to-end, /statusz exposes the speculate stats block (the
    loadgen acceptance-rate embed reads it), and a non-bool value is a
    400, not a crash."""
    gen = _spec(plain_ref)
    eng = ServingEngine(_tiny_predictor(), workers=1, max_batch=2,
                        max_delay_ms=1.0, deadline_ms=60000)
    eng.attach_generator(gen)
    srv = serve(eng)
    try:
        rng = np.random.RandomState(31)
        prompt = _repetitive(rng, PAGE + 1)
        ref = plain_ref.generate(prompt, 6)

        code, doc = _post(srv.url + "/generate",
                          {"prompt": prompt, "max_new_tokens": 6})
        assert code == 200 and doc["tokens"] == ref["tokens"]
        code, doc = _post(srv.url + "/generate",
                          {"prompt": prompt, "max_new_tokens": 6,
                           "speculate": False})
        assert code == 200 and doc["tokens"] == ref["tokens"]

        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.url + "/generate",
                  {"prompt": prompt, "speculate": "yes"})
        assert ei.value.code == 400

        with urllib.request.urlopen(srv.url + "/statusz",
                                    timeout=30) as r:
            sz = json.loads(r.read())
        spec = sz["engine"]["generator"]["stats"]["speculate"]
        assert spec["drafts"] >= 1
        assert 0.0 <= spec["acceptance_rate"] <= 1.0
    finally:
        eng.generator = None
        srv.close()
        eng.close()
        gen.close()
