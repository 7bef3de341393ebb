"""The cache manager (``serving/kv_cache.py``) on the host alone: no
program is built and nothing is allocated on a device.

* the allocator and the prefix index (moved from
  ``tests/test_paged_generation.py``);
* the one refusal table, by (cache kind, feature), and what it says of
  the model kinds of the benchmark's configurations at their published
  widths;
* the window slide, draft acquire / roll-back and the page-second
  booking, driven on a bare :class:`KVCache`.
"""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

from paddle_tpu.serving import usage
from paddle_tpu.serving.kv_cache import (KVCache, PagePool, PoolExhausted,
                                         PrefixIndex, REFUSALS, SlotPages)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
FEATURES = {"prefix_reuse": {"prefix_reuse": True},
            "speculate": {"speculate": True},
            "prefill_chunk": {"prefill_chunk": 256},
            "handoff": {"role": "prefill"},
            "block_diffusion": {"block": 4}}
# what each kind refused before there was a table (generation.py's three
# lists at PR 60; latent pages had none), and a phrase of its message
REFUSED = {
    "block": ({"prefix_reuse", "speculate", "prefill_chunk", "handoff"},
              "block-diffusion model commits a block of 4 positions"),
    "slot_state": ({"prefix_reuse", "speculate", "prefill_chunk", "handoff",
                    "block_diffusion"}, "layers keep slot state"),
    "window_pages": ({"prefix_reuse", "speculate", "handoff"},
                     "sliding-window layers keeps two page pools"),
    "latent_pages": (set(), None),
    "pages": (set(), None)}


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
# the refusal table
# ---------------------------------------------------------------------------

def _raises(match, call):
    if match is None:
        return call()
    with pytest.raises(ValueError, match=match):
        call()


@pytest.mark.parametrize("feature", list(FEATURES))
@pytest.mark.parametrize("kind", list(REFUSED))
def test_a_kind_refuses_what_it_refused_and_no_more(kind, feature):
    """One cell of the table: the kind alone, the feature alone (a
    manager told it has that kind and no other)."""
    refused, phrase = REFUSED[kind]
    args = dict(FEATURES[feature])
    kv = KVCache(TOY, num_slots=1, max_seq_len=64, page_tokens=PAGE,
                 prefill_chunk=args.pop("prefill_chunk", 0),
                 prefix_reuse=args.pop("prefix_reuse", False))
    kv.kinds = {kind} - {"block"}
    if kind == "block":
        args["block"] = 4         # (block_diffusion: the kind itself)
    _raises(phrase if feature in refused else None,
            lambda: kv.check_features(**args))
    assert set(REFUSALS.get(kind, ("", {}, ""))[1]) == refused


def _model(config):
    """The model arguments of a benchmark configuration at its published
    widths (shapes only: nothing of that size is allocated)."""
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "kvc_" + cfg["builder"],
        os.path.join(BENCH, "builders", cfg["builder"] + ".py"))
    builder = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(builder)
    model = builder.model_args(cfg)
    return model, (model.pop("block_diffusion", None) or {}).get("block", 0)


def _bare(model, **kw):
    args = dict(num_slots=2, max_seq_len=1024, page_tokens=128,
                num_pages=17)
    args.update(kw)
    return KVCache(model, **args)


CONFIGS = {    # configuration: the kinds of cache its model has
    "mistral-7b-v0.1": {"pages"},
    "smallthinker-21b-a3b": {"pages", "window_pages"},
    "sdar-30b-a3b-chat": {"pages", "block"},
    "lfm2-24b-a2b": {"pages", "slot_state"},
    "olmo-hybrid-7b": {"pages", "slot_state"},
    "solar-open2-250b": {"pages", "slot_state"},
    "gigachat35-432b-a28b": {"latent_pages", "slot_state"},
    "command-a-plus-05-2026": {"pages", "window_pages"},
    "deepseek-v2": {"latent_pages"},
    "granite-4.0-h-micro": {"pages", "slot_state"},
    "nemotron3-super-120b-a12b": {"pages", "slot_state"},
    "longcat-flash-chat": {"latent_pages"}}


def test_every_serving_configuration_is_in_the_list():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        names = [c["name"] for c in json.load(f)["configs"]]
    assert sorted(CONFIGS) == sorted(set(names) - {"bert-base-mlm"})


@pytest.mark.parametrize("feature", list(FEATURES))
@pytest.mark.parametrize("config", list(CONFIGS))
def test_a_configuration_is_refused_what_its_kinds_refuse(config, feature):
    """The manager built from the configuration's own ``cache_spec``: the
    first of its kinds, in the table's order, that refuses the feature
    says so; the all-latent ``deepseek-v2`` is refused nothing."""
    model, block = _model(config)
    args = dict(FEATURES[feature])
    kv = _bare(model, prefill_chunk=args.pop("prefill_chunk", 0),
               prefix_reuse=args.pop("prefix_reuse", False))
    kinds = kv.kinds | ({"block"} if block else set())
    assert kinds == CONFIGS[config]
    want = next((REFUSED[k][1] for k in REFUSALS
                 if k in kinds and feature in REFUSED[k][0]), None)
    if feature == "block_diffusion" and "window_pages" in kinds:
        want = "block diffusion over sliding-window layers is not built"
    if feature == "block_diffusion" and block:
        return                    # (the kind itself: block= is its own)
    args.setdefault("block", block)
    _raises(want, lambda: kv.check_features(**args))


def test_a_chunk_or_a_block_the_pages_cannot_hold_is_refused():
    model, _ = _model("command-a-plus-05-2026")
    with pytest.raises(ValueError, match="multiple of page_tokens"):
        _bare(model, prefill_chunk=192).check_features()
    _bare(model, prefill_chunk=256).check_features()
    model, block = _model("sdar-30b-a3b-chat")
    with pytest.raises(ValueError, match="multiple of the block"):
        _bare(model).check_features(block=3)
    _bare(model).check_features(block=block)
    for bad, match in (({"page_tokens": 96}, "power of two"),
                       ({"max_seq_len": 1000}, "multiple of page_tokens")):
        with pytest.raises(ValueError, match=match):
            _bare(model, **bad)


# ---------------------------------------------------------------------------
# page accounting on a bare manager
# ---------------------------------------------------------------------------

WINDOW, PAGE = 32, 8
TOY = dict(vocab_size=97, hidden=64, num_layers=2, num_heads=4,
           num_kv_heads=2, intermediate=96, head_dim=16)
WINDOWED = dict(TOY, layer_pattern=[{"window": None}, {"window": WINDOW}])


def _held(slot):
    return sum(1 for p in slot.wpages if p)


def test_a_decoding_slot_holds_a_window_of_window_pages():
    """Position by position to five windows: the sliding table holds at
    most ``window / page_tokens + 1`` pages, what it let go reads 0 (the
    trash page) and went back to the pool; the full table keeps all."""
    counted = []
    kv = KVCache(WINDOWED, num_slots=2, max_seq_len=256, page_tokens=PAGE,
                 count=lambda key, n=1: counted.append((key, n)))
    assert (kv.window, kv.window_pages_per_slot) == (WINDOW, 5)
    assert kv.num_window_pages == 2 * 5 + 1
    slot = SlotPages()
    for n in range(1, 5 * WINDOW + 1):
        kv.ensure_pages(slot, n)
        assert _held(slot) <= WINDOW // PAGE + 1
        assert len(slot.wpages) == len(slot.pages) == -(-n // PAGE)
        first = max(0, n - WINDOW) // PAGE
        assert slot.wpages[:first] == [0] * first
        assert all(slot.wpages[first:])
        assert kv.live_pages("window") == _held(slot)
    assert kv.live_pages() == 5 * WINDOW // PAGE
    released = 5 * WINDOW // PAGE - _held(slot)
    assert kv.window_released == released
    assert sum(n for key, n in counted
               if key == "window_pages_released") == released
    table = kv.block_table(slot, window=True)
    assert table.shape == (256 // PAGE,) and table.dtype == np.int32
    assert list(table[:len(slot.wpages)]) == slot.wpages
    assert kv.kv_live_bytes == kv.live_pages() * kv.page_bytes \
        + _held(slot) * kv.window_page_bytes
    kv.release_pages(slot)
    assert kv.live_pages() == kv.live_pages("window") == 0
    assert slot.pages == slot.wpages == []


@pytest.mark.parametrize("chunk", [PAGE, 2 * PAGE, 48])
def test_a_chunk_holds_its_rows_beside_the_window_and_lets_go_behind(chunk):
    """A prompt in chunks of C rows: with the chunk's pages mapped the
    slot holds at most ``(window + C) / page_tokens + 1`` window pages,
    and after the slide behind it a window's again."""
    kv = KVCache(WINDOWED, num_slots=2, max_seq_len=256, page_tokens=PAGE,
                 prefill_chunk=chunk)
    assert kv.num_window_pages == 2 * 5 + 1 + chunk // PAGE
    slot = SlotPages()
    for base in range(0, 240, chunk):
        rows = min(chunk, 240 - base)
        kv.ensure_pages(slot, base + rows, rows=rows)
        assert _held(slot) <= (WINDOW + chunk) // PAGE + 1
        # the chunk's first row admits ``base - window + 1`` onwards
        first = max(0, base + 1 - WINDOW) // PAGE
        assert slot.wpages[:first] == [0] * first
        assert all(slot.wpages[first:])
        # what the next rows no longer admit goes back now
        kv.slide_window_pages(slot, base + rows + 1, 0)
        assert _held(slot) <= WINDOW // PAGE + 1
        assert kv.live_pages("window") == _held(slot)
    feeds = kv.table_feeds(slot)
    assert sorted(feeds) == ["block_table", "block_table_window"]
    assert feeds["block_table_window"].shape == (1, 256 // PAGE)
    assert not kv.table_feeds(None)["block_table_window"].any()


def test_a_window_pool_that_runs_dry_says_which():
    kv = KVCache(WINDOWED, num_slots=1, max_seq_len=256, page_tokens=PAGE,
                 num_window_pages=3)
    slot = SlotPages()
    with pytest.raises(PoolExhausted, match="window page pool"):
        kv.ensure_pages(slot, 3 * PAGE)
    kv.release_pages(slot)
    assert kv.live_pages() == kv.live_pages("window") == 0


def _refcounts(kv):
    return [kv.refcount(p) for p in range(kv.num_pages)]


@pytest.mark.parametrize("pages,dry", [(12, False), (6, True)])
def test_a_draft_rolled_back_leaves_the_table_as_found(pages, dry):
    """Acquire then roll back: the table and every refcount as before the
    draft, also when the pool runs dry half-way through the acquire."""
    kv = KVCache(TOY, num_slots=2, max_seq_len=64, page_tokens=PAGE,
                 num_pages=pages, prefix_reuse=True)
    prompt = np.arange(1, 20, dtype="int64")
    slot, other = SlotPages(), SlotPages()
    kv.ensure_pages(other, 19)
    kv.register_prefix(other, prompt)         # two whole pages shared
    assert kv.prefix_entries == 2
    assert kv.map_prefix(slot, prompt) == 2 * PAGE
    kv.ensure_pages(slot, 19)
    table, refs = list(slot.pages), _refcounts(kv)
    assert refs[table[0]] == 3 and refs[table[2]] == 1
    if dry:
        # 5 usable pages, 4 live, nothing evictable (the index's pages
        # are mapped): the draft's second page is not there
        with pytest.raises(PoolExhausted, match="nothing evictable"):
            kv.acquire_draft_pages(slot, 19 + 16)
    else:
        keep = kv.acquire_draft_pages(slot, 19 + 16)
        assert keep == 3 and len(slot.pages) == 5
        assert kv.rollback_draft_pages(slot, keep) == 2
    assert slot.pages == table and _refcounts(kv) == refs
    kv.release_pages(slot)
    kv.release_pages(other)
    assert kv.live_pages() == 2 and kv.flush_prefix() == 2
    assert kv.live_pages() == 0 and kv.free_pages() == pages - 1


def test_an_idle_index_page_is_evicted_for_a_slot_that_needs_it():
    counted = []
    kv = KVCache(TOY, num_slots=1, max_seq_len=64, page_tokens=PAGE,
                 num_pages=4, prefix_reuse=True,
                 count=lambda key, n=1: counted.append(key))
    prompt = np.arange(1, 18, dtype="int64")
    slot = SlotPages()
    kv.ensure_pages(slot, 17)
    kv.register_prefix(slot, prompt)
    kv.release_pages(slot)                    # the index alone holds 2
    assert kv.live_pages() == 2 and kv.free_pages() == 1
    kv.ensure_pages(slot, 3 * PAGE)           # takes the free one, evicts
    assert counted == ["page_evictions"] * 2 and kv.prefix_entries == 0
    with pytest.raises(PoolExhausted):
        kv.ensure_pages(slot, 4 * PAGE)


def test_release_books_a_slots_page_seconds_to_its_tenant_once():
    usage.reset_ledger()
    try:
        kv = KVCache(TOY, num_slots=1, max_seq_len=64, page_tokens=PAGE)
        slot = SlotPages()
        slot.page_tenant = "acme"
        # (a whole number of seconds, ten ago on the clock release reads)
        t0 = float(int(time.monotonic())) - 10.0
        kv.mark_pages(slot, now=t0)           # the hold starts
        kv.ensure_pages(slot, 2 * PAGE)
        slot.page_t = t0
        kv.mark_pages(slot, now=t0 + 0.5)     # 2 pages x 0.5 s
        assert slot.page_us == 1_000_000
        kv.release_pages(slot)
        booked = usage.ledger().snapshot()["tenants"]["acme"]["page_us"]
        assert booked >= 1_000_000
        assert (slot.page_tenant, slot.page_us, slot.page_t) \
            == (None, 0, 0.0)
        kv.release_pages(slot)                # a second release: nothing
        kv.ensure_pages(slot, PAGE)           # no tenant: no integral
        kv.release_pages(slot)
        assert usage.ledger().snapshot()["tenants"]["acme"]["page_us"] \
            == booked
        assert slot.page_us == 0
    finally:
        usage.reset_ledger()


def test_sizes_come_from_the_spec_before_anything_is_allocated():
    model, _ = _model("gigachat35-432b-a28b")
    kv = _bare(model)
    rows = {e["name"]: e for e in kv.spec}
    assert kv.state_names == [n for n, e in rows.items()
                              if e["kind"] == "slot_state"]
    assert kv.layers_of("latent_pages") and kv.layers_of("slot_state")
    assert not kv.layers_of("pages") and kv.window is None
    assert kv.kv_cache_bytes == sum(
        int(np.prod(e["shape"])) * 4 for e in kv.spec
        if e["kind"] == "latent_pages")
    assert kv.page_bytes * kv.num_pages == kv.kv_cache_bytes
    assert kv.slot_state_bytes == sum(
        int(np.prod(rows[n]["shape"])) * 4 for n in kv.state_names)
    assert kv.kv_live_bytes == 0 and kv.kv_shard_axis is None
