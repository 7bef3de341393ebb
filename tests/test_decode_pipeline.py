"""One decode step in flight (PR 29): the scheduler hands grid step n+1
to the device before it fetches and books step n, also (PR 45) in the
pass that carries a finished prefill: the joiner rides step n+1 on its
prefill's first token as the device holds it, and the scheduler blocks
on that token only after step n+1 has gone out and step n is booked.

What must hold whatever the order of dispatch and settle: every
sequence's token stream is the one it has when it runs alone; a row of
a step that overtook its sequence's end (or its joiner's failed
prefill) is discarded and never booked to the slot's next owner; a
length-bounded answer costs no extra step; pages are mapped before the
dispatch that writes them and all return to their pools; a fault, a
weight swap and a drain meet the step in flight and leave nothing
behind; per-tenant sums stay equal to the counters.

Toy widths on the CPU: every number here is a count or an ordering.
"""
import threading
import time

import numpy as np
import pytest
from conftest import uncached_logits

import paddle_tpu as pt
from paddle_tpu import fault, telemetry
from paddle_tpu.serving import GenerationEngine, RequestFailed, usage

MODEL = dict(vocab_size=61, hidden=32, num_layers=2, num_heads=4,
             num_kv_heads=2, intermediate=64)
MOE = {"experts": 8, "top_k": 3, "width": 32, "activation": "relu"}
# one full layer without positions, then window-16 RoPE layers: two page
# kinds, pages released while decoding
WINDOW_MODEL = dict(
    vocab_size=97, hidden=64, num_layers=4, num_heads=4, num_kv_heads=2,
    intermediate=0, head_dim=32, rope_base=1.5e6,
    layer_pattern=[{"window": None, "rope": False, "ffn": MOE}]
    + [{"window": 16, "rope": True, "ffn": MOE}] * 3)
PROMPTS = [[3, 17, 5, 40, 8, 22], [9, 1, 33, 7], [12, 50, 2, 2, 31, 6, 19],
           [44, 13, 27], [21, 4, 60, 35, 11]]


def _engine(kind, **kw):
    return GenerationEngine(
        WINDOW_MODEL if kind == "window" else MODEL, num_slots=3,
        max_seq_len=64, page_tokens=8, prefill_chunk=0,
        prefix_reuse=False, speculate=False, attn_impl="xla", seed=0,
        # (held to the float32 uncached forward; the engine's own choice
        # for MODEL, bfloat16, is held in tests/test_serving_dtype.py)
        dtype="float32", **kw)


@pytest.fixture(scope="module", autouse=True)
def _weights_of_a_fresh_process():
    """The engines here draw their weights from the process-wide op-seed
    counter, and several tests need a stream with a token that occurs
    once (``_pick_eos``): start the counter where a fresh process has it,
    so the draw does not depend on which files the worker ran before."""
    from paddle_tpu.ops.registry import reset_op_seed

    reset_op_seed()


@pytest.fixture(scope="module", params=["paged"])
def eng(request):
    e = _engine(request.param)
    e.warmup()
    yield e
    e.close()


@pytest.fixture(scope="module")
def paged():
    e = _engine("paged")
    e.warmup()
    yield e
    e.close()


@pytest.fixture(scope="module")
def solo(eng):
    """Each prompt's 16-token stream when it decodes alone."""
    return [eng.generate(p, 16, timeout=120)["tokens"] for p in PROMPTS]


def _counters(e):
    return dict(e.stats()["counters"])


def _quiet(e, timeout=10.0):
    """Wait for the scheduler to go idle: a future resolves inside the
    pass that books its last token, a moment before that pass ends."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not e.stats()["slots_active"] and e._inflight is None:
            time.sleep(0.02)
            return
        time.sleep(0.005)
    raise AssertionError("scheduler did not go idle")


def _after_tokens(n):
    """``(on_token, event)``: the event fires at the n-th token."""
    ev, seen = threading.Event(), []

    def on_token(tok, ts):
        seen.append(tok)
        if len(seen) >= n:
            ev.set()
    return on_token, ev


def _pages_live(e):
    p = e.stats()["paged"]
    return p["pages_live"] + (p["window"]["pages_live"]
                              if p["window"] else 0)


# -- (1) streams -------------------------------------------------------------
def _delta(e, before):
    return {k: v - before[k] for k, v in _counters(e).items()}


@pytest.mark.parametrize("kind", ["paged", "window"])
def test_streams_equal_solo_streams_with_joiners_midstream(kind):
    e = _engine(kind)
    e.warmup()
    try:
        budgets = (40, 12, 9, 7)
        solo = [e.generate(p, n, timeout=120)["tokens"]
                for p, n in zip(PROMPTS, budgets)]
        _quiet(e)
        before = _counters(e)
        on_a, a_running = _after_tokens(4)
        fa = e.submit(PROMPTS[0], 40, on_token=on_a)  # outlives the others
        assert a_running.wait(60)
        on_b, b_running = _after_tokens(3)
        fb = e.submit(PROMPTS[1], 12, on_token=on_b)  # joins a's grid
        assert b_running.wait(60)
        fc = e.submit(PROMPTS[2], 9)                  # joins both
        fd = e.submit(PROMPTS[3], 7)                  # waits for a slot
        got = [f.result(120) for f in (fa, fb, fc, fd)]
        for res, want, n in zip(got, solo, budgets):
            assert res["tokens"] == want and res["finish"] == "length"
            assert res["steps"] == n - 1
        _quiet(e)
        n = _delta(e, before)
        # every step but a's first went out ahead of the settle before
        # it, the three that carried a joiner too: a finished prefill
        # does not make the grid wait for its first token
        assert n["decode_steps_ahead"] == n["decode_steps"] - 1
        assert n["decode_joiners_ahead"] == 3
        assert n["decode_rows_discarded"] == 0
        assert _pages_live(e) == 0
        # tokens from the host, tokens carried on the device and a
        # joiner's row among them bind the one decode executable warm-up
        # compiled
        assert e._decode_exe.cache_info()["compiled"] == 1
    finally:
        e.close()


@pytest.mark.parametrize("kind", ["paged", "window"])
def test_a_joiners_first_step_reads_its_prefills_token_on_the_device(kind):
    """The joiner's row of the step ahead is its prefill's first token,
    never fetched first: its logits, from its first decode step on, and
    the rider's beside it are those of a plain forward over prompt +
    stream."""
    e = _engine(kind, keep_logits=True)
    try:
        on_a, a_running = _after_tokens(3)
        fa = e.submit(PROMPTS[0], 40, on_token=on_a)
        assert a_running.wait(60)
        fb = e.submit(PROMPTS[2] + PROMPTS[4][:3], 20)    # 10 tokens
        for prompt, res in ((PROMPTS[0], fa.result(300)),
                            (PROMPTS[2] + PROMPTS[4][:3], fb.result(300))):
            ref = uncached_logits(e, prompt + res["tokens"])
            got = np.stack(res["logits"])
            want = ref[len(prompt) - 1:len(prompt) - 1 + len(got)]
            assert np.abs(got - want).max() <= 1e-4 * np.ptp(want)
            assert res["tokens"] == [int(t) for t in want.argmax(-1)]
        _quiet(e)
        c = _counters(e)
        assert c["decode_joiners_ahead"] == 1
        assert c["decode_steps_ahead"] == c["decode_steps"] - 1
        assert _pages_live(e) == 0
    finally:
        e.close()


@pytest.mark.parametrize("kind", ["paged", "window"])
def test_logits_under_ahead_dispatch_are_the_uncached_forwards(kind):
    """The step dispatched ahead reads its tokens from the device: its
    logits are those of a plain forward over prompt + stream, at every
    position, with one page pool and with two (the window engine crosses its
    window and releases pages while it decodes)."""
    e = _engine(kind, keep_logits=True)
    try:
        prompt = PROMPTS[2] + PROMPTS[4][:3]              # 10 tokens
        res = e.generate(prompt, 26, timeout=300)
        ref = uncached_logits(e, prompt + res["tokens"])
        got = np.stack(res["logits"])
        want = ref[len(prompt) - 1:len(prompt) - 1 + len(got)]
        assert np.abs(got - want).max() <= 1e-4 * np.ptp(want)
        assert res["tokens"] == [int(t) for t in want.argmax(-1)]
        c = _counters(e)
        assert c["decode_steps_ahead"] == c["decode_steps"] - 1 == 24
        if kind == "window":
            assert c["window_pages_released"] > 0
            assert c["moe_tokens_dropped"] == 0
        _quiet(e)
        assert _pages_live(e) == 0
    finally:
        e.close()


# -- (2) spans ---------------------------------------------------------------
def _named(spans, name):
    return [s for s in spans if s.name == name]


def _within(spans, outer):
    return sorted((s for s in spans if s.tid == outer.tid and s is not outer
                   and outer.start <= s.start and s.end <= outer.end),
                  key=lambda s: s.start)


def test_dispatch_precedes_fetch_and_ahead_marks_it(paged):
    pt.set_flags({"FLAGS_telemetry": True})
    telemetry.clear_spans()
    on_a, a_running = _after_tokens(5)
    fa = paged.submit(PROMPTS[0], 14, on_token=on_a)
    assert a_running.wait(60)
    fb = paged.submit(PROMPTS[1], 4)
    fa.result(120), fb.result(120)
    _quiet(paged)
    spans = telemetry.get_spans()
    steps = sorted(_named(spans, "generation/decode_step"),
                   key=lambda s: s.start)
    shapes = []
    for st in steps:
        inner = [k.name.split("/")[1] for k in _within(spans, st)
                 if k.name in ("generation/decode_dispatch",
                               "generation/token_fetch")]
        shapes.append((tuple(inner), st.attrs.get("ahead")))
    steady = (("decode_dispatch", "token_fetch"), 1)
    first = (("decode_dispatch",), 0)
    settle = (("token_fetch",), None)
    # a alone: its first step has nothing to overtake, the next ones go
    # out before the step before them is fetched, and so does the one in
    # the pass that carries b's finished prefill; the last only settles
    assert shapes[0] == first and shapes[-1] == settle
    assert shapes[1:-1] == [steady] * (len(shapes) - 2)
    # b's prefill pass: the prefill is launched behind the step in
    # flight, the next step goes out behind the prefill with b's row in
    # it, the step in flight is fetched and booked, and only then does
    # the scheduler block on the prefill's first token
    launch_b = sorted(_named(spans, "generation/prefill"),
                      key=lambda s: s.start)[1]
    it = next(s for s in _named(spans, "generation/iteration")
              if s.start <= launch_b.start and launch_b.end <= s.end)
    order = [k.name.split("/")[1] for k in _within(spans, it)
             if k.name in ("generation/prefill",
                           "generation/decode_dispatch",
                           "generation/token_fetch",
                           "generation/prefill_fetch")]
    assert order == ["prefill", "decode_dispatch", "token_fetch",
                     "prefill_fetch"]
    step_b = next(st for st in steps
                  if it.start <= st.start and st.end <= it.end)
    assert step_b.attrs["ahead"] == 1 and step_b.attrs["active"] == 2
    # b's first token is booked before the step that carries its second
    # is fetched: a stream's order is what it was
    fetch_b = next(s for s in _named(spans, "generation/prefill_fetch")
                   if it.start <= s.start and s.end <= it.end)
    assert fetch_b.start >= step_b.end
    assert steps[steps.index(step_b) + 1].start >= fetch_b.end
    # a alone had no prefill to wait behind: its own is read at once
    first_fetch = min(_named(spans, "generation/prefill_fetch"),
                      key=lambda s: s.start)
    assert first_fetch.end <= steps[0].start
    # spans of the scheduler thread never overlap without nesting
    for st in steps:
        for k in _within(spans, st):
            assert st.start <= k.start and k.end <= st.end


# -- (3) EOS at the settle ---------------------------------------------------
def _pick_eos(streams, rider, others):
    """An index k >= 3 of ``streams[rider]`` whose token appears nowhere
    before it there and nowhere in the ``others``' streams."""
    s = streams[rider]
    for k in range(3, len(s) - 1):
        if s[k] not in s[:k] and all(s[k] not in streams[o]
                                     for o in others):
            return k
    return None


@pytest.mark.parametrize("kind", ["paged", "window"])
def test_eos_at_settle_discards_the_row_that_overtook_it(kind):
    e = _engine(kind)
    e.warmup()
    try:
        prompts = PROMPTS + [[7, 7, 30], [58, 2, 46, 9]]
        streams = [e.generate(p, 16, timeout=120)["tokens"]
                   for p in prompts]
        pick = next(((p, k, [o for o in range(len(prompts)) if o != p])
                     for p in range(len(prompts))
                     for k in [_pick_eos(streams, p,
                                         [o for o in range(len(prompts))
                                          if o != p])]
                     if k is not None), None)
        assert pick is not None, streams
        p, k, others = pick
        r, q = others[0], others[1]
        e.eos_id = streams[p][k]
        before = _counters(e)
        # two of three slots: r decodes throughout, p ends on EOS with a
        # step in flight, q claims a slot and joins while r's row of
        # that step is still unread
        on_r, r_running = _after_tokens(2)
        fr = e.submit(prompts[r], 16, on_token=on_r)
        assert r_running.wait(60)
        fp = e.submit(prompts[p], 16)
        fq = e.submit(prompts[q], 10)
        rp, rr, rq = fp.result(120), fr.result(120), fq.result(120)
        assert rp["finish"] == "eos" and rp["tokens"] == streams[p][:k + 1]
        assert rr["tokens"] == streams[r] and rq["tokens"] == streams[q][:10]
        _quiet(e)
        after = _counters(e)
        assert after["decode_rows_discarded"] \
            - before["decode_rows_discarded"] == 1
        assert after["generated_tokens"] - before["generated_tokens"] \
            == k + 1 + 16 + 10
        assert _pages_live(e) == 0
        # the slot p left is claimed again before its discarded row is
        # read: with one slot free at a time the next owner is booked
        # nothing but its own tokens
        e.eos_id = streams[p][k]
        futs = [e.submit(prompts[i], 16 if i == p else 12)
                for i in (p, r, q, others[2])]
        for f, i in zip(futs, (p, r, q, others[2])):
            want = streams[i][:k + 1] if i == p else streams[i][:12]
            assert f.result(120)["tokens"] == want
        _quiet(e)
        assert _pages_live(e) == 0
    finally:
        e.eos_id = -1
        e.close()


# -- (3b) a joiner that ends at its first token, or before it ------------------
@pytest.mark.parametrize("kind", ["paged", "window"])
@pytest.mark.parametrize("ends", ["eos", "budget"])
def test_a_joiner_that_ends_at_its_first_token_leaves_nothing(kind, ends):
    """The host cannot see an EOS coming: the joiner's row of the step
    ahead is dispatched and then discarded.  A budget of one token it
    can see: the joiner stays out, and the step goes out ahead without
    it all the same."""
    e = _engine(kind)
    e.warmup()
    try:
        streams = [e.generate(p, 16, timeout=120)["tokens"]
                   for p in PROMPTS]
        # j's first token occurs nowhere in r's stream
        r, j = next((r, j) for r in range(len(PROMPTS))
                    for j in range(len(PROMPTS))
                    if r != j and streams[j][0] not in streams[r])
        if ends == "eos":
            e.eos_id = streams[j][0]
        _quiet(e)
        before = _counters(e)
        on_r, r_running = _after_tokens(2)
        fr = e.submit(PROMPTS[r], 16, on_token=on_r)
        assert r_running.wait(60)
        fj = e.submit(PROMPTS[j], 16 if ends == "eos" else 1)
        rj, rr = fj.result(120), fr.result(120)
        assert rj["tokens"] == streams[j][:1] and rj["steps"] == 0
        assert rj["finish"] == ("eos" if ends == "eos" else "length")
        assert rr["tokens"] == streams[r]
        _quiet(e)
        n = _delta(e, before)
        rode = int(ends == "eos")
        assert n["decode_joiners_ahead"] == rode
        assert n["decode_rows_discarded"] == rode
        assert n["decode_steps_ahead"] == n["decode_steps"] - 1 == 14
        assert n["generated_tokens"] == 17
        assert _pages_live(e) == 0
        # the slot j left serves its next owner nothing but its own tokens
        e.eos_id = -1
        assert e.generate(PROMPTS[j], 9, timeout=120)["tokens"] \
            == streams[j][:9]
    finally:
        e.eos_id = -1
        e.close()


def test_no_page_for_the_joiners_position_ahead_settles_first():
    """Two pages, a rider on one and a joiner whose prompt fills the
    other: the step cannot go out ahead with the joiner in it, so the
    settle and the joiner's first token come first, as before PR 45, and
    the joiner finishes ``cache_full`` with that token."""
    e = GenerationEngine(MODEL, num_slots=2, max_seq_len=64,
                         attn_impl="xla", seed=0,
                         page_tokens=8, num_pages=3, prefix_reuse=False,
                         prefill_chunk=0, speculate=False)
    try:
        joiner = PROMPTS[0] + PROMPTS[1][:2]               # 8 tokens: a page
        want_r = e.generate(PROMPTS[1], 12, timeout=120)["tokens"]
        want_j = e.generate(joiner, 2, timeout=120)["tokens"]
        _quiet(e)
        before = _counters(e)
        with e._cv:       # claimed in one pass: r prefills first, then j
            fr = e.submit(PROMPTS[1], 12)                  # 4 + 12: 2 pages
            fj = e.submit(joiner, 10)
        rj, rr = fj.result(120), fr.result(120)
        assert (rj["tokens"], rj["finish"]) == (want_j[:1], "cache_full")
        assert (rr["tokens"], rr["finish"]) == (want_r, "length")
        _quiet(e)
        n = _delta(e, before)
        assert n["failed"] == 0 and n["decode_joiners_ahead"] == 0
        # r's first step and the one after the joiner went out from the
        # host's tokens
        assert n["decode_steps_ahead"] == n["decode_steps"] - 2
        assert _pages_live(e) == 0
    finally:
        e.close()


@pytest.mark.parametrize("kind", ["paged", "window"])
def test_a_prefill_that_fails_under_a_step_ahead_fails_its_request_only(
        kind):
    e = _engine(kind)
    e.warmup()
    fetch = e._fetch_first_token
    try:
        want = [e.generate(p, 16, timeout=120)["tokens"]
                for p in PROMPTS[:2]]
        _quiet(e)
        before = _counters(e)

        def broken(slot, *fetched):
            if slot.req.prompt.size == len(PROMPTS[1]):
                e._fetch_first_token = fetch
                raise RuntimeError("the prefill's read failed")
            return fetch(slot, *fetched)

        e._fetch_first_token = broken
        on_a, a_running = _after_tokens(3)
        fa = e.submit(PROMPTS[0], 16, on_token=on_a)
        assert a_running.wait(60)
        fb = e.submit(PROMPTS[1], 16)
        with pytest.raises(RequestFailed, match="prefill failed"):
            fb.result(120)
        assert fa.result(120)["tokens"] == want[0]
        _quiet(e)
        n = _delta(e, before)
        # b's row of the step ahead was dispatched and nobody took it
        assert n["failed"] == 1 and n["decode_joiners_ahead"] == 1
        assert n["decode_rows_discarded"] == 1
        assert n["decode_steps_ahead"] == n["decode_steps"] - 1 == 14
        assert _pages_live(e) == 0
        assert e.generate(PROMPTS[1], 16, timeout=120)["tokens"] == want[1]
    finally:
        e._fetch_first_token = fetch
        e.close()


# -- (4) a length-bounded answer costs no extra step --------------------------
def test_no_step_is_dispatched_past_a_budget_or_a_full_cache(eng):
    pt.set_flags({"FLAGS_telemetry": True})
    for prompt, budget, tokens, finish in (
            (PROMPTS[0], 7, 7, "length"), (PROMPTS[1], 1, 1, "length"),
            (PROMPTS[1], 2, 2, "length"),
            # 6 prompt positions, 64 in the cache: 59 tokens fill it
            (PROMPTS[0], 200, eng.max_seq_len - 6 + 1, "cache_full")):
        _quiet(eng)
        telemetry.clear_spans()
        before = _counters(eng)
        res = eng.generate(prompt, budget, timeout=120)
        _quiet(eng)
        assert (len(res["tokens"]), res["finish"]) == (tokens, finish)
        after = _counters(eng)
        dispatched = len(_named(telemetry.get_spans(),
                                "generation/decode_dispatch"))
        assert dispatched == tokens - 1 == res["steps"]
        assert after["decode_steps"] - before["decode_steps"] == tokens - 1
        assert after["decode_rows_discarded"] \
            == before["decode_rows_discarded"]


# -- (5) pages are mapped before the dispatch that writes them ---------------
@pytest.mark.parametrize("kind", ["paged", "window"])
def test_page_of_the_ahead_position_is_mapped_before_the_dispatch(kind):
    e = _engine(kind)
    e.warmup()
    seen = []
    dispatch = e._dispatch_decode

    def spy(tokens, positions, bt=None, live=None, btw=None):
        ahead = e._inflight is not None
        for row in np.flatnonzero(live):
            page = int(positions[row]) // e.page_tokens
            seen.append((ahead, int(positions[row]), int(bt[row, page]),
                         None if btw is None else int(btw[row, page])))
        return dispatch(tokens, positions, bt, live, btw)

    e._dispatch_decode = spy
    try:
        # prompt of 6, page of 8, window of 16: the answers cross three
        # page edges and, on the window engine, the window's edge
        ra = e.submit(PROMPTS[0], 26)
        rb = e.submit(PROMPTS[2], 20)
        ra.result(120), rb.result(120)
        # page 0 is the trash page: a live row never writes there
        assert all(full > 0 and window != 0
                   for _, _, full, window in seen)
        crossed = [pos for ahead, pos, _, _ in seen
                   if ahead and pos % e.page_tokens == 0]
        assert len(crossed) >= 4
        if kind == "window":
            assert max(pos for _, pos, _, _ in seen) >= e.window + 8
            assert _counters(e)["window_pages_released"] > 0
        _quiet(e)
        assert _pages_live(e) == 0
    finally:
        e.close()


def test_no_page_for_the_ahead_position_settles_first_and_finishes():
    """Three pages, two sequences: the one that needs a fourth page
    finishes ``cache_full`` with all it generated, at the settle, and
    the other keeps its stream."""
    e = GenerationEngine(MODEL, num_slots=2, max_seq_len=64,
                         attn_impl="xla", seed=0,
                         page_tokens=8, num_pages=4, prefix_reuse=False,
                         prefill_chunk=0, speculate=False)
    try:
        want_a = e.generate(PROMPTS[0], 10, timeout=120)["tokens"]
        want_b = e.generate(PROMPTS[1], 20, timeout=120)["tokens"]
        on_b, b_running = _after_tokens(2)
        fb = e.submit(PROMPTS[1], 20, on_token=on_b)   # 4 + 20: 3 pages
        assert b_running.wait(60)
        fa = e.submit(PROMPTS[0], 10)                  # 6 + 10: 2 pages
        a, b = fa.result(120), fb.result(120)
        full = [r for r in (a, b) if r["finish"] == "cache_full"]
        assert len(full) == 1 and e.stats()["counters"]["failed"] == 0
        for res, want in ((a, want_a), (b, want_b)):
            assert res["tokens"] == want[:len(res["tokens"])]
            assert res["finish"] == "cache_full" \
                or len(res["tokens"]) == len(want)
        _quiet(e)
        assert _pages_live(e) == 0
    finally:
        e.close()


# -- (6) a fault with a step in flight ---------------------------------------
def test_decode_fault_with_a_step_in_flight_fails_active_serves_next(
        eng, solo):
    _quiet(eng)
    failed = _counters(eng)["failed"]
    # the third pass raises at its head: the second's step is in flight
    fault.configure("decode_step:fail@3")
    try:
        fa = eng.submit(PROMPTS[0], 12)
        fb = eng.submit(PROMPTS[1], 12)
        for f in (fa, fb):
            with pytest.raises(RequestFailed, match="decode step failed"):
                f.result(120)
    finally:
        fault.configure("")
    assert eng._inflight is None
    assert _counters(eng)["failed"] - failed == 2
    # the slots are claimed again: nothing of the dropped step is booked
    got = [eng.submit(PROMPTS[i], 9) for i in (2, 3, 0)]
    for f, i in zip(got, (2, 3, 0)):
        assert f.result(120)["tokens"] == solo[i][:9]
    _quiet(eng)
    assert _pages_live(eng) == 0


# -- (7) a weight swap and a drain settle the step in flight first -----------
def test_weight_swap_settles_the_step_in_flight_first(paged):
    donor = GenerationEngine(MODEL, num_slots=3, max_seq_len=64,
                             attn_impl="xla", seed=7,
                             page_tokens=8, prefix_reuse=False,
                             prefill_chunk=0, speculate=False,
                             name="donor", dtype="float32")
    try:
        new = {n.replace("donor", paged.name, 1):
               np.asarray(donor.scope.find_var(n))
               for n in donor._weight_names()}
        want_new = donor.generate(PROMPTS[3], 8, timeout=120)["tokens"]
    finally:
        donor.close()
    old = {n: np.asarray(paged.scope.find_var(n))
           for n in paged._weight_names()}
    want_old = paged.generate(PROMPTS[0], 40, timeout=120)["tokens"]
    seen = {}
    commit = paged._commit_swap

    def spy(arrays):
        slot = next(s for s in paged._slots if s.active)
        seen.update(inflight=paged._inflight, booked=len(slot.tokens),
                    steps=_counters(paged)["decode_steps"])
        return commit(arrays)

    paged._commit_swap = spy
    try:
        on_a, a_running = _after_tokens(6)
        fa = paged.submit(PROMPTS[0], 40, on_token=on_a)
        assert a_running.wait(60)
        steps0 = _counters(paged)["decode_steps"]
        assert paged.swap_weights(new)["weights_version"] >= 2
        res = fa.result(120)
        # nothing was in flight when the weights flipped, every token
        # booked by then is the old weights', and all 40 arrived
        assert seen["inflight"] is None and seen["steps"] >= steps0
        assert 6 <= seen["booked"] < 40 and len(res["tokens"]) == 40
        assert res["tokens"][:seen["booked"]] == want_old[:seen["booked"]]
        assert paged.generate(PROMPTS[3], 8, timeout=120)["tokens"] \
            == want_new
    finally:
        paged._commit_swap = commit
        paged.swap_weights(old)
    assert paged.generate(PROMPTS[0], 40, timeout=120)["tokens"] == want_old


@pytest.mark.parametrize("kind", ["paged"])
def test_close_with_drain_settles_the_step_in_flight(kind):
    e = _engine(kind)
    want = [e.generate(p, 12, timeout=120)["tokens"] for p in PROMPTS[:2]]
    on_a, a_running = _after_tokens(3)
    fa = e.submit(PROMPTS[0], 12, on_token=on_a)
    fb = e.submit(PROMPTS[1], 12)
    assert a_running.wait(60)
    e.close(drain=True, timeout=120)
    assert not e._thread.is_alive() and e._inflight is None
    assert [fa.result(1)["tokens"], fb.result(1)["tokens"]] == want
    assert e.stats()["slots_active"] == 0 and _pages_live(e) == 0


# -- (8) per-tenant sums equal the global counters ----------------------------
def test_tenant_sums_equal_the_counters_with_a_discarded_row(eng, solo):
    k = _pick_eos(solo, 0, [1, 2])
    assert k is not None, solo
    pt.set_flags({"FLAGS_usage": True})
    usage.reset_ledger()
    _quiet(eng)
    before = _counters(eng)
    eng.eos_id = solo[0][k]
    try:
        on_b, b_running = _after_tokens(2)
        fb = eng.submit(PROMPTS[1], 16, tenant="umbrella", on_token=on_b)
        assert b_running.wait(60)
        fa = eng.submit(PROMPTS[0], 16, tenant="acme")    # ends on EOS
        fc = eng.submit(PROMPTS[2], 11, tenant="acme")
        results = [f.result(120) for f in (fa, fb, fc)]
        _quiet(eng)
    finally:
        eng.eos_id = -1
    after = _counters(eng)
    assert results[0]["finish"] == "eos"
    assert after["decode_rows_discarded"] \
        - before["decode_rows_discarded"] == 1
    led = usage.ledger()
    snap = led.snapshot()
    assert all(v["delta"] == 0 for v in led.conservation().values())
    assert snap["totals"]["tokens_out"] \
        == after["generated_tokens"] - before["generated_tokens"] \
        == sum(len(r["tokens"]) for r in results)
    # a step books one unit to each sequence it booked a token to: the
    # discarded row bills no one
    assert snap["totals"]["decode_steps"] \
        == sum(r["steps"] for r in results)
    assert snap["tenants"]["acme"]["tokens_out"] \
        == len(results[0]["tokens"]) + len(results[2]["tokens"])
    assert snap["tenants"]["umbrella"]["decode_steps"] == results[1]["steps"]
    usage.reset_ledger()
