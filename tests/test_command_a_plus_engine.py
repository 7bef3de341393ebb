"""``command-a-plus-05-2026`` at a small size through the paged engine
(PR 51; ``tests/test_command_a_plus.py`` has the kernel, the block and the
share, and the toy configuration this file borrows): chunked prefill then
eight cached decode steps against the benchmark reference's single
forward, logits not tokens, for prompts inside the window, crossing it
while they decode and crossing it while they prefill; chunked equals
single-shot and chunks of one page, three pages and the whole prompt
agree; window pages let go between chunks, the pool's bound, recycled
pages; two slots prefilling in turn while others decode; spans, counters,
and what stays refused.
"""
import time

import numpy as np
import pytest

import paddle_tpu as pt
from test_command_a_plus import (PAGE, TOL, WINDOW, _cfg, _engine,
                                 _off_reference, _prompt, _rel)

# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chunked_engine():
    """One three-slot engine in chunks of 16 for the tests that only send
    it prompts: its programs compile once."""
    cfg = _cfg()
    eng = _engine(cfg)
    yield eng, cfg
    eng.close()


@pytest.mark.parametrize("n,what", [
    (5, "one chunk inside the window"),
    (20, "inside the window, two chunks"),
    (28, "crosses the window while it decodes"),
    (70, "crosses the window while it prefills"),
    (96, "whole chunks, three windows long")])
def test_chunked_prefill_then_cached_decode_is_the_single_forward(
        n, what, chunked_engine):
    eng, cfg = chunked_engine
    before = eng.stats()["counters"]
    prompt = _prompt(n, n)
    res = eng.generate(prompt, 9, timeout=600)
    counters = {k: v - before[k] for k, v in eng.stats()["counters"].items()}
    assert res["finish"] == "length" and len(res["tokens"]) == 9
    assert _off_reference(eng, cfg, prompt, res) < TOL, what
    assert counters["prefill_chunks"] == -(-n // 16)
    assert counters["moe_tokens_dropped"] == 0
    # every chunk's pairs are booked, not the last one's alone
    assert counters["moe_pairs_routed"] == 4 * 3 * (n + 8)
    assert 0 < counters["moe_pairs_held"] < counters["moe_pairs_routed"]
    assert counters["moe_shared_expert_rows"] == 4 * (n + 8)


def test_chunked_is_single_shot_and_chunk_sizes_agree():
    """The same prompt through the parent's single-shot window prefill
    program and through chunks of one page, three pages and the whole
    prompt: the same logits to float32 rounding."""
    cfg = _cfg()
    prompt = _prompt(9, 75)
    base = _engine(cfg, prefill_chunk=0)
    try:
        want = np.stack(base.generate(prompt, 9, timeout=600)["logits"])
        assert base.stats()["counters"]["prefill_chunks"] == 0
        for chunk, chunks in ((PAGE, 10), (3 * PAGE, 4), (128, 1)):
            eng = _engine(cfg, scope=base.scope, prefill_chunk=chunk)
            try:
                got = np.stack(eng.generate(prompt, 9,
                                            timeout=600)["logits"])
                assert eng.stats()["counters"]["prefill_chunks"] == chunks
            finally:
                eng.close()
            assert _rel(got, want) < 1e-5, chunk
    finally:
        base.close()


def _spied(eng):
    """Record every chunk's feeds and the pool's state as it is sent."""
    sent = []
    feed_of = eng._chunk_feed

    def spy(ids, base, n, slot):
        feed = feed_of(ids, base, n, slot)
        if slot is not None:
            sent.append({"slot": slot.idx, "base": base, "n": n,
                         "window": feed["block_table_window"][0].copy(),
                         "full": feed["block_table"][0].copy(),
                         "live": eng.kv.live_pages("window")})
        return feed

    eng._chunk_feed = spy
    return sent


def test_window_pages_are_let_go_between_chunks_and_never_read_again():
    """Before the chunk at ``base`` the slot's window table covers ``[base
    - window + 1, base + C)`` and every entry left of that is the trash
    page; a slot holds at most ``(window + C) / page + 1`` window pages
    however many chunks its prompt takes, the full table keeps them all,
    and a page let go is handed out again."""
    cfg = _cfg()
    eng = _engine(cfg, num_slots=2, max_seq_len=256,
                  prefill_buckets=[16, 256])
    sent = _spied(eng)
    try:
        prompt = _prompt(12, 200)
        res = eng.generate(prompt, 9, timeout=600)
        stats = eng.stats()
    finally:
        eng.close()
    assert _off_reference(eng, cfg, prompt, res) < TOL
    assert [c["base"] for c in sent] == list(range(0, 200, 16))
    bound = (WINDOW + 16) // PAGE + 1
    handed_out = set()
    for c in sent:
        first = max(0, c["base"] - WINDOW + 1) // PAGE
        last = (c["base"] + c["n"] - 1) // PAGE
        assert not c["window"][:first].any()
        assert c["window"][first:last + 1].all()
        assert not c["window"][last + 1:].any()
        assert np.count_nonzero(c["window"]) <= bound
        assert c["full"][:last + 1].all()
        handed_out |= set(c["window"][first:last + 1].tolist())
    # 25 logical pages went through the pool's 12: pages come back
    assert len(handed_out) <= eng.num_window_pages - 1 < 25
    w = stats["paged"]["window"]
    assert w["pages_released_in_prefill"] == sent[-1]["base"] // PAGE \
        - WINDOW // PAGE + 1
    assert w["pages_released"] > w["pages_released_in_prefill"]
    assert w["pages_live"] == 0 and stats["paged"]["pages_live"] == 0


def test_window_pool_is_sized_for_one_running_chunk():
    """``slots x (window / page + 1)`` and ONE chunk's pages beyond: every
    slot but the one whose chunk runs is back under a window's pages
    before the next chunk is chosen, so three long prompts that prefill
    in turn never exhaust it."""
    cfg = _cfg()
    eng = _engine(cfg, max_seq_len=256, prefill_buckets=[16, 256])
    sent = _spied(eng)
    try:
        per_slot = WINDOW // PAGE + 1
        assert eng.window_pages_per_slot == per_slot
        assert eng.num_window_pages == 3 * per_slot + 1 + 16 // PAGE
        # (seeds without a routing near tie on a context row: the third
        # of 20, 21, 22 has one at 8e-8 of a row's range, which chunks of
        # 16 rows and the reference's whole rows break differently)
        prompts = [_prompt(120 + i, 150 + 10 * i) for i in range(3)]
        futures = [eng.submit(p, 9) for p in prompts]
        results = [f.result(600) for f in futures]
        counters = eng.stats()["counters"]
    finally:
        eng.close()
    for p, r in zip(prompts, results):
        assert r["finish"] == "length"
        assert _off_reference(eng, cfg, p, r) < TOL
    # the slots took turns, chunk by chunk
    turns = [c["slot"] for c in sent[:9]]
    assert sorted(set(turns)) == [0, 1, 2] and turns[:3] != [turns[0]] * 3
    assert max(c["live"] for c in sent) <= 3 * per_slot + 16 // PAGE
    assert counters["pool_stalls"] == 0 and counters["failed"] == 0


def test_recycled_pages_do_not_reach_another_slots_output():
    """A window pool with no page to spare: what one prompt lets go the
    other takes at once and overwrites, while the first still prefills
    and decodes."""
    cfg = _cfg()
    eng = _engine(cfg, num_slots=2, max_seq_len=256,
                  prefill_buckets=[16, 256])
    try:
        assert eng.num_window_pages == 2 * 5 + 1 + 2
        prompts = [_prompt(31, 180), _prompt(32, 120)]
        futures = [eng.submit(p, 9) for p in prompts]
        results = [f.result(600) for f in futures]
    finally:
        eng.close()
    for p, r in zip(prompts, results):
        assert _off_reference(eng, cfg, p, r) < TOL


def test_two_slots_prefill_in_turn_while_others_decode():
    cfg = _cfg()
    eng = _engine(cfg, num_slots=4)
    try:
        first = [eng.submit(_prompt(40 + i, 10 + i), 40) for i in range(2)]
        long_ = [_prompt(50 + i, 90 + 7 * i) for i in range(2)]
        later = [eng.submit(p, 9) for p in long_]
        results = [f.result(600) for f in later]
        for f in first:
            assert f.result(600)["finish"] == "length"
        counters = eng.stats()["counters"]
    finally:
        eng.close()
    for p, r in zip(long_, results):
        assert _off_reference(eng, cfg, p, r) < TOL
    assert counters["decode_steps"] >= 40
    assert counters["prefill_chunks"] == 2 + 6 + 7


def test_chunks_go_round_robin_over_the_prefilling_slots(chunked_engine):
    """A long prompt and, behind it, two short ones, claimed together:
    the scheduler's round-robin (the parent's, unchanged) puts the short
    ones' chunks between the long one's, so their first tokens leave
    before the long prompt's last chunk has run; what each attends is its
    own pages all the same."""
    from paddle_tpu import telemetry

    eng, cfg = chunked_engine
    t0 = time.monotonic()
    first = {}
    prompts = [_prompt(61, 100), _prompt(62, 40), _prompt(63, 6)]
    futures = [eng.submit(p, 9, on_token=lambda _, t, i=i:
                          first.setdefault(i, t))
               for i, p in enumerate(prompts)]
    results = [f.result(600) for f in futures]
    chunks = [s.attrs["slot"] for s in telemetry.get_spans()
              if s.name == "generation/prefill_chunk" and s.start >= t0]
    long_, mid, short = (r["slot"] for r in results)
    own = [i for i, slot in enumerate(chunks) if slot == long_]
    assert len(own) == 7 and len(chunks) == 7 + 3 + 1
    # another prompt's chunks lie between the long prompt's own
    assert own[-1] - own[0] > len(own) - 1
    # every prefilling slot has had a turn before any has a second, and
    # the long prompt's last chunks run alone
    assert sorted(chunks[:3]) == sorted([long_, mid, short])
    assert chunks[-3:] == [long_] * 3
    # so the short prompts' first tokens leave before the long one's
    assert first[2] < first[1] < first[0]
    for p, r in zip(prompts, results):
        assert _off_reference(eng, cfg, p, r) < TOL


def test_spans_say_what_a_chunk_and_a_step_did(chunked_engine):
    from paddle_tpu import telemetry

    eng, _ = chunked_engine
    t0 = time.monotonic()
    eng.generate(_prompt(41, 70), 4, timeout=300)
    spans = [s for s in telemetry.get_spans()
             if s.end is not None and s.start >= t0]
    chunks = [s for s in spans if s.name == "generation/prefill_chunk"]
    assert [(c.attrs["base"], c.attrs["tokens"]) for c in chunks] \
        == [(0, 16), (16, 16), (32, 16), (48, 16), (64, 6)]
    # row t of the full layer admits base + t + 1 columns, of each of the
    # three sliding layers the last 32 of them
    ends = [np.arange(c.attrs["base"] + 1,
                      c.attrs["base"] + c.attrs["tokens"] + 1)
            for c in chunks]
    assert [c.attrs["attended_pairs"] for c in chunks] == [
        int(e.sum() + 3 * np.minimum(e, WINDOW).sum()) for e in ends]
    assert [c.attrs["window_pages_released"] for c in chunks] \
        == [0, 0, 2, 2, 0]
    assert [c.attrs["window_pages_held"] for c in chunks] == [2, 4, 6, 6, 5]
    fetch = [s for s in spans if s.name == "generation/prefill_fetch"][-1]
    assert fetch.attrs["pairs_routed"] == 4 * 70 * 3
    assert 0 < fetch.attrs["pairs_held"] < fetch.attrs["pairs_routed"]
    steps = [s for s in spans if s.name == "generation/decode_step"
             and "pairs_held" in s.attrs]
    assert steps
    for s in steps:
        assert s.attrs["live_positions_window"] == WINDOW
        assert s.attrs["live_positions"] > 70
        assert 0 <= s.attrs["experts_held_touched"] <= 3


@pytest.mark.parametrize("kw,reason", [
    ({"prefix_reuse": True}, "prefix_reuse"),
    ({"speculate": True}, "speculate"),
    ({"role": "prefill"}, "segment codec"),
    ({"role": "decode"}, "segment codec"),
])
def test_what_shares_or_hands_over_one_table_stays_refused(kw, reason):
    with pytest.raises(ValueError, match="sliding-window layers") as e:
        _engine(autostart=False, **kw)
    assert reason in str(e.value) and "chunked prefill walks both" \
        in str(e.value)


def test_a_chunk_that_is_not_whole_pages_is_refused_over_two_kinds():
    with pytest.raises(ValueError, match="multiple of page_tokens"):
        _engine(autostart=False, prefill_chunk=12)


@pytest.mark.parametrize("layer,reason", [
    ({"mixer": {"kind": "conv", "L_cache": 3}}, "slot state"),
    # (a latent layer has a chunk program since PR 56)
    ({"mla": {"q_rank": 8, "kv_rank": 16, "nope_dim": 8, "rope_dim": 8,
              "v_dim": 8}}, None),
])
def test_state_layers_still_have_no_chunk_program_and_latent_ones_do(
        layer, reason):
    from paddle_tpu.models.llama import build_llama_prefill_chunk

    def build():
        return build_llama_prefill_chunk(
            8, 64, 9, PAGE, name="llama", vocab_size=97, hidden=32,
            num_layers=1, num_heads=2, intermediate=48,
            layer_pattern=[layer])

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        if reason is None:
            assert build()[2] == ["llama.pool_c_0"]
            assert "latent_chunk_attention" in [
                op.type for op in main.global_block().ops]
            return
        with pytest.raises(ValueError, match=reason):
            build()


def test_a_long_prompt_needs_only_the_chunks_rung():
    """In chunks a prompt may pass the largest rung; whole, it may not."""
    cfg = _cfg()
    eng = _engine(cfg, prefill_buckets=[8, 16], autostart=False)
    try:
        assert eng.max_prompt_len == 127
    finally:
        eng.close()
    eng = _engine(cfg, prefill_buckets=[8, 16], prefill_chunk=0,
                  autostart=False)
    try:
        assert eng.max_prompt_len == 16
    finally:
        eng.close()


def test_the_uncut_layer_runs_through_the_same_program():
    """``held`` covering every expert of the router is the uncut model:
    the reference given all 16 agrees, through chunks."""
    cfg = _cfg(num_experts=16,
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
