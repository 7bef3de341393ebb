"""Generation serving tests: KV-cached decode bit-exactness, slot-based
continuous batching vs FIFO head-run static batching, shedding
semantics, and the HTTP ``/generate`` front end.

The load-bearing contracts:

* **Bit-exactness** — cached decode logits must equal the uncached
  full-forward logits step-for-step (``conftest.assert_logits_match``:
  to the accumulation order of one matmul; token streams exactly)
  with requests of ragged lengths decoding *concurrently* in the slot
  grid.  Both sides pin ``attn_impl="xla"`` (the einsum formulation
  ``cached_attention`` mirrors).  The plain engine (no prefix reuse, no
  chunking, no speculation) answers to this uncached forward; every
  feature engine elsewhere answers to the plain engine.
* **Continuous batching ≥ 2x static** — on a deterministic long-tail
  workload (three short sequences and one long per four slots), slot
  reclaim must finish the same token set in under half the wall time of
  batch-drain scheduling, at no worse p99 (ISSUE 7 acceptance bar).
"""
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from conftest import assert_logits_match
from conftest import uncached_logits as _reference_logits

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.serving import (GenerationEngine, OverloadedError,
                                ServingEngine, batcher, serve)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tiny GQA config shared by the module fixture (kv_heads < heads so the
# repeat-interleave cache expansion is under test, not just MHA)
MODEL = dict(vocab_size=61, hidden=32, num_layers=2, num_heads=4,
             num_kv_heads=2, intermediate=64)


@pytest.fixture(scope="module")
def gen_engine():
    """Shared plain KV-cached engine: 3 slots, keep_logits for the
    bit-exactness comparisons, attn_impl pinned to the einsum
    formulation."""
    eng = GenerationEngine(MODEL, num_slots=3, max_seq_len=48,
                           max_new_tokens=8, keep_logits=True,
                           attn_impl="xla", seed=0, queue_cap=64,
                           deadline_ms=600000.0, prefix_reuse=False,
                           # (held to the float32 uncached forward; the
                           # engine's own choice for MODEL, bfloat16, is
                           # held in tests/test_serving_dtype.py)
                           dtype="float32")
    yield eng
    eng.close()


# ---------------------------------------------------------------------------
# batcher: prompt buckets + ragged-length pad/stack round trip
# ---------------------------------------------------------------------------

def test_prompt_bucket_policy():
    assert batcher.prompt_buckets(64) == (8, 16, 32, 64)
    assert batcher.prompt_buckets(48) == (8, 16, 32, 48)
    assert batcher.prompt_buckets(64, buckets=[16, 64]) == (16, 64)
    assert batcher.prompt_bucket_for(9, (8, 16, 32)) == 16
    assert batcher.prompt_bucket_for(8, (8, 16, 32)) == 8
    with pytest.raises(ValueError):
        batcher.prompt_bucket_for(33, (8, 16, 32))
    with pytest.raises(ValueError):
        batcher.prompt_buckets(64, buckets=[16, 128])  # > max_len


def test_pad_prompt():
    ids = np.arange(1, 6)
    padded = batcher.pad_prompt(ids, 8)
    assert padded.shape == (8,) and padded.dtype == np.int64
    assert np.array_equal(padded[:5], ids)
    assert np.all(padded[5:] == 0)
    with pytest.raises(ValueError):
        batcher.pad_prompt(np.arange(9), 8)


def test_pad_stack_split_rows_ragged_lengths():
    """Requests with ragged sequence lengths ride one batch: each pads
    to the shared bucket, pad_stack concatenates the ragged row counts,
    split_rows is a bit-exact inverse."""
    rng = np.random.RandomState(0)
    raw = [rng.randint(1, 50, size=n) for n in (3, 9, 14)]
    bucket_len = 16
    reqs = [(batcher.pad_prompt(ids, bucket_len)[None].repeat(rows, 0),)
            for ids, rows in zip(raw, (1, 3, 2))]
    padded, real_rows = batcher.pad_stack(reqs, 8)
    assert real_rows == 6
    assert padded[0].shape == (8, bucket_len)
    # pad rows replicate row 0 (a real row: no NaN/garbage reaches XLA)
    assert np.array_equal(padded[0][6], padded[0][0])
    outs = [padded[0] * 2]  # any row-wise "model" output
    split = batcher.split_rows(outs, [1, 3, 2])
    assert [s[0].shape[0] for s in split] == [1, 3, 2]
    for req, got in zip(reqs, split):
        assert np.array_equal(got[0], req[0] * 2)


# ---------------------------------------------------------------------------
# bit-exactness: cached decode == uncached full forward
# ---------------------------------------------------------------------------

def test_cached_decode_bitexact_concurrent_ragged(gen_engine):
    """Three prompts of ragged lengths (crossing prefill buckets)
    decode CONCURRENTLY in the slot grid — per-slot positions differ
    every step — and every request's per-step next-token logits are
    those of its own uncached full forward."""
    eng = gen_engine
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, MODEL["vocab_size"], size=n).tolist()
               for n in (3, 9, 14)]  # buckets 8, 16, 16
    steps = [6, 4, 7]
    futs = [eng.submit(p, n) for p, n in zip(prompts, steps)]
    results = [f.result(120) for f in futs]
    for prompt, n, res in zip(prompts, steps, results):
        assert res["finish"] == "length" and res["steps"] == n - 1
        assert len(res["tokens"]) == n == len(res["logits"])
        ref = _reference_logits(eng, prompt + res["tokens"][:-1])
        for i, got in enumerate(res["logits"]):
            assert_logits_match(
                got, ref[len(prompt) - 1 + i],
                f"step {i}: cached decode vs the uncached forward")
        # greedy argmax: the token streams agree exactly
        assert res["tokens"] == [int(np.argmax(ref[len(prompt) - 1 + i]))
                                 for i in range(n)]


def test_eos_frees_slot(gen_engine):
    """EOS finish: re-run a known stream with eos_id set to its second
    token — generation stops there with finish='eos'."""
    eng = gen_engine
    prompt = [5, 11, 2, 9]
    base = eng.generate(prompt, 6)
    assert base["finish"] == "length"
    eos = base["tokens"][1]
    old = eng.eos_id
    try:
        eng.eos_id = eos
        res = eng.generate(prompt, 6)
    finally:
        eng.eos_id = old
    assert res["finish"] == "eos"
    assert res["tokens"] == base["tokens"][:2]


def test_cache_full_finish(gen_engine):
    """A budget beyond the cache capacity left after the prompt decodes
    until the slot cache fills: finish='cache_full' with exactly
    max_seq_len - prompt_len + 1 tokens (the last written cache index
    is max_seq_len - 1 — the out-of-bounds guard fires BEFORE a write
    could clamp onto the last row)."""
    eng = gen_engine
    prompt = [5, 11, 2]
    res = eng.generate(prompt, eng.max_seq_len * 2)
    assert res["finish"] == "cache_full"
    assert len(res["tokens"]) == eng.max_seq_len - len(prompt) + 1
    # the capped stream is a prefix of what a roomier budget yields
    # step-for-step (same caches, same weights): compare via logits
    # against the uncached forward on the LAST step, whose cache row
    # sits at max_seq_len - 1
    ref = _reference_logits(eng, prompt + res["tokens"][:-1])
    assert_logits_match(res["logits"][-1],
                        ref[len(prompt) - 1 + len(res["tokens"]) - 1],
                        "the step that fills the cache")


def test_prompt_validation(gen_engine):
    with pytest.raises(ValueError):
        gen_engine.submit([])
    with pytest.raises(ValueError):
        gen_engine.submit([[1, 2], [3, 4]])
    with pytest.raises(ValueError):
        gen_engine.submit([0.5, 1.5])
    with pytest.raises(ValueError):  # beyond the largest prefill bucket
        gen_engine.submit(list(range(1, eng_max(gen_engine) + 2)))


def eng_max(eng):
    return eng.max_prompt_len


def test_introspection(gen_engine):
    eng = gen_engine
    s = eng.stats()
    assert s["slots"] == 3 and s["queue_cap"] == 64
    assert s["counters"]["served"] >= 4
    assert s["counters"]["decode_steps"] > 0
    # cache accounting: (slots * pages_per_slot + the trash page) pages
    # of n_kv * page_tokens * head_dim * 4B, * 2KV * L
    head_dim = MODEL["hidden"] // MODEL["num_heads"]
    pages = 3 * (48 // eng.page_tokens) + 1
    want = (pages * MODEL["num_kv_heads"] * eng.page_tokens * head_dim * 4
            * 2 * MODEL["num_layers"])
    assert eng.kv_cache_bytes == want == s["kv_cache_bytes"]
    intro = eng.introspect()
    assert intro["decode_executables"]["entries"], \
        "decode executor compiled nothing?"
    man = intro["decode_manifest"]
    if man is not None:  # backend exposes cost analysis (CPU/TPU do)
        assert man["flops"] > 0
        assert intro["decode_mfu"] is None or intro["decode_mfu"] > 0


# ---------------------------------------------------------------------------
# continuous batching: under half the decode steps of FIFO head-run static
# ---------------------------------------------------------------------------

def _run_workload(continuous):
    """Deterministic long-tail workload (3 short + 1 long per claim
    group of 4): all requests queued BEFORE the scheduler starts, so
    claim order — and therefore the static grouping — is exact.  The
    long sequences (88 tokens vs 2) put the structural step ratio near
    3.2x."""
    eng = GenerationEngine(MODEL, num_slots=4, max_seq_len=96,
                           max_new_tokens=88, continuous=continuous,
                           autostart=False, seed=0, queue_cap=64,
                           deadline_ms=600000.0, attn_impl="xla")
    eng.warmup()
    prompts, lens = [], []
    rng = np.random.RandomState(3)
    for _g in range(4):
        for n in (2, 2, 2, 88):
            prompts.append(rng.randint(
                1, MODEL["vocab_size"], size=4).tolist())
            lens.append(n)
    futs = [eng.submit(p, n) for p, n in zip(prompts, lens)]
    eng.start()
    results = [f.result(300) for f in futs]
    stats = eng.stats()
    eng.close()
    # every request ran to its budget
    assert sum(len(r["tokens"]) for r in results) == sum(lens)
    return stats


def test_continuous_2x_over_static():
    """The static scheduler needs over 2x the decode steps for the same
    token set, because drained slots idle until the group's longest
    sequence finishes.  Counts only: a rate on the CPU is not evidence."""
    st_static = _run_workload(False)
    st_cont = _run_workload(True)
    steps_static = st_static["counters"]["decode_steps"]
    steps_cont = st_cont["counters"]["decode_steps"]
    # batch drain pays max(lens) per group
    assert steps_static >= 2 * steps_cont, \
        f"static {steps_static} steps vs continuous {steps_cont}"
    assert st_cont["counters"]["slot_reclaims"] > 0
    assert st_static["counters"]["slot_reclaims"] == 0


# ---------------------------------------------------------------------------
# admission control / shedding
# ---------------------------------------------------------------------------

def test_queue_full_and_draining_shed():
    eng = GenerationEngine(MODEL, num_slots=1, max_seq_len=48,
                           queue_cap=2, autostart=False, seed=0,
                           deadline_ms=600000.0)
    f1 = eng.submit([1, 2, 3])
    f2 = eng.submit([4, 5])
    with pytest.raises(OverloadedError) as ei:
        eng.submit([6])
    assert ei.value.reason == "queue_full"
    eng.close(drain=False)
    for f in (f1, f2):
        with pytest.raises(OverloadedError) as ei:
            f.result(5)
        assert ei.value.reason == "draining"
    with pytest.raises(OverloadedError) as ei:
        eng.submit([7])
    assert ei.value.reason == "draining"
    # queue_full + two queued futures shed at close + the post-close
    # submit = 4 sheds
    assert eng.stats()["counters"]["shed"] == 4


def test_deadline_shed_before_claim():
    eng = GenerationEngine(MODEL, num_slots=1, max_seq_len=48,
                           queue_cap=8, autostart=False, seed=0,
                           deadline_ms=1.0)
    futs = [eng.submit([1, 2, 3]), eng.submit([4, 5])]
    time.sleep(0.05)  # both requests outlive the 1ms deadline queued
    eng.start()
    for f in futs:
        with pytest.raises(OverloadedError) as ei:
            f.result(30)
        assert ei.value.reason == "deadline"
    eng.close()


# ---------------------------------------------------------------------------
# HTTP front end: POST /generate
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
        out = layers.fc(x, 2, name="gen_http_fc")
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    from paddle_tpu.inference import Predictor
    return Predictor(main, ["x"], [out], scope=scope)


def test_http_generate(gen_engine):
    eng = ServingEngine(_tiny_predictor(), workers=1, max_batch=2,
                        max_delay_ms=1.0, deadline_ms=60000)
    srv = serve(eng)
    try:
        # no generator attached yet -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.url + "/generate", {"prompt": [1, 2, 3]})
        assert ei.value.code == 404

        eng.attach_generator(gen_engine)
        code, doc = _post(srv.url + "/generate",
                          {"prompt": [3, 1, 4, 1, 5], "max_new_tokens": 4})
        assert code == 200
        ref = gen_engine.generate([3, 1, 4, 1, 5], 4)
        assert doc["tokens"] == ref["tokens"]
        assert doc["prompt_len"] == 5 and doc["finish"] == "length"
        assert "ms" in doc and "queue_wait_ms" in doc

        # malformed bodies -> 400
        for bad in ({"prompt": "abc"}, {"nope": 1},
                    {"prompt": list(range(1, 200))}):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(srv.url + "/generate", bad)
            assert ei.value.code == 400, bad

        # generation stats ride /healthz and /statusz
        with urllib.request.urlopen(srv.url + "/healthz",
                                    timeout=30) as r:
            hz = json.loads(r.read())
        assert hz["generation"]["counters"]["served"] >= 1
        with urllib.request.urlopen(srv.url + "/statusz",
                                    timeout=30) as r:
            sz = json.loads(r.read())
        assert "generator" in sz["engine"]
    finally:
        eng.generator = None  # module fixture owns the generator
        srv.close()
        eng.close()


# ---------------------------------------------------------------------------
# loadgen --generate CLI
# ---------------------------------------------------------------------------

def test_prompt_maker_distributions():
    """Deterministic factory; bimodal preserves the requested mean but
    carries a heavier tail than geometric (the grid's longest draw is
    what static batch-drain scheduling pays for)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "lg", os.path.join(REPO, "tools", "serving_loadgen.py"))
    lg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lg)
    for dist in ("geometric", "bimodal"):
        mk = lg.prompt_maker(64, 4, 8, 16.0, 128, pool=512, dist=dist)
        mk2 = lg.prompt_maker(64, 4, 8, 16.0, 128, pool=512, dist=dist)
        lens = [mk(i)[1] for i in range(512)]
        assert lens == [mk2(i)[1] for i in range(512)]  # deterministic
        assert all(1 <= n <= 128 for n in lens)
        assert abs(np.mean(lens) - 16.0) < 4.0, (dist, np.mean(lens))
        p = mk(3)[0]
        assert p.dtype == np.int64 and 4 <= p.size <= 8
        assert p.min() >= 1 and p.max() < 64
    with pytest.raises(ValueError):
        lg.prompt_maker(64, 4, 8, 16.0, 128, dist="zipf")


@pytest.mark.slow
def test_loadgen_generate_cli(tmp_path):
    out = tmp_path / "rep.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "serving_loadgen.py"),
         "--generate", "--mode", "closed", "--requests", "6",
         "--concurrency", "3", "--gen-slots", "2", "--gen-max-seq",
         "32", "--gen-out-mean", "4", "--gen-out-max", "8",
         "--gen-hidden", "32", "--gen-vocab", "64",
         "--out", str(out)],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    rep = json.loads(out.read_text())
    assert rep["ok"] == 6 and rep["generated_tokens"] > 0
    assert rep["tokens_per_sec"] > 0
    assert rep["engine"]["counters"]["served"] == 6
