"""What a sequence keeps between steps: page pools, block tables, slot
state, and what each kind of cache refuses.

:class:`~paddle_tpu.serving.generation.GenerationEngine` schedules; this
module owns the cache it schedules over.  :class:`KVCache` is built from
the model's ``cache_spec`` (``models/llama.py``: the one description the
program builders declare their persistable state from) and the page
keywords, and is whole on the host before anything is allocated: a test
drives it bare, with no program built.

* **Paged KV cache** (PagedAttention-style, the engine's only cache): a
  flat per-layer pool ``[num_pages, n_kv, page_tokens, D]`` plus per-slot
  block tables, so concurrency is bounded by LIVE tokens and not by a
  worst-case sequence per slot.  :class:`PagePool` hands out physical
  pages on demand; running out finishes the starved slot ``cache_full``
  after trying to evict idle prefix-index pages.  The pools are
  persistable executor state that the programs update in place.
* **Page kinds follow the spec.**  ``pages`` (K and V of heads) and
  ``latent_pages`` (a latent layer's one pool of ``[c_kv | k_r]`` rows)
  go through the slot's *full* table, a page every ``page_tokens``
  positions for as long as the sequence lives.  ``window_pages``
  (sliding-window layers) have a second pool and a *sliding* table
  (:meth:`KVCache.slide_window_pages`): what the next rows no longer
  admit goes back to the pool, also WHILE the prompt is still coming in,
  so the window pool is ``slots x (window / page_tokens + 1)`` pages and
  ONE chunk's beyond.
* **Slot state** (``slot_state``: a convolution's last rows, the delta
  rule's or a state-space layer's matrix) is per-slot variables that are
  not pages, allocated here; the programs alone read and write them.
* **Shared-prefix reuse**: :class:`PrefixIndex` hashes page-aligned
  prompt-prefix chunks (system prompts, few-shot headers); a hit maps the
  shared pages into the new slot copy-on-write (refcounted,
  mutation-free: decode and tail-prefill writes only ever touch pages
  *past* the shared prefix) and skips their prefill entirely.
* **What a kind refuses** of prefix reuse, chunked prefill, speculation,
  KV-segment handoff and block diffusion is one table, :data:`REFUSALS`.

Gauges: ``serving_kv_cache_bytes`` (allocated capacity: the page pools),
``serving_slot_state_bytes``, ``serving_kv_live_bytes`` (pages referenced
by live sequences or the prefix index), ``serving_kv_pages_free``,
``serving_kv_pages_live`` (with window pages also ``_live_full`` /
``_live_window``, with latent pages ``serving_latent_pages_live``).
Counters (through the ``count`` callable the engine hands in, and
``stat_add``): ``serving_kv_page_evictions``,
``serving_kv_window_pages_released``.  Allocation is the
``startup/pool_alloc`` span of the start-up account.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import telemetry
from ..flags import flag_value
from ..monitor import stat_add
from . import usage

__all__ = ["KVCache", "PagePool", "PrefixIndex", "PoolExhausted",
           "SlotPages", "REFUSALS"]


class PoolExhausted(Exception):
    """The paged KV pool has no free page and nothing evictable."""


class PagePool:
    """Host-side physical-page allocator for the paged KV cache.

    Physical page 0 is the reserved **trash page** (garbage writes —
    idle slots, chunk pad tails — are redirected there in-graph) and is
    never handed out.  Pages are refcounted: a slot holds one ref per
    mapped page, the prefix index holds one per registered page; a page
    returns to the free list when its count hits zero.  Not
    thread-safe on its own — the engine mutates it only from the
    scheduler thread."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"paged KV pool needs >= 2 pages (one is "
                             f"the reserved trash page), got {num_pages}")
        self.num_pages = int(num_pages)
        self._free: collections.deque = collections.deque(
            range(1, num_pages))
        self._ref = [0] * num_pages

    def alloc(self) -> Optional[int]:
        """One free page at refcount 1, or None when exhausted."""
        if not self._free:
            return None
        p = self._free.popleft()
        self._ref[p] = 1
        return p

    def incref(self, pages: Sequence[int]):
        for p in pages:
            self._ref[p] += 1

    def decref(self, pages: Sequence[int]):
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] < 0:
                raise AssertionError(f"page {p} refcount underflow")
            if self._ref[p] == 0:
                self._free.append(p)

    def refcount(self, page: int) -> int:
        return self._ref[page]

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)


class PrefixIndex:
    """Shared-prefix page index: page-aligned prompt-prefix chunk ->
    physical page holding its K/V.

    Keys are the exact token bytes of the prompt's first ``(i+1) *
    page_tokens`` tokens, so a hit is an exact prefix match chained
    from position 0 (no hash collisions, no partial pages).  Lookup is
    capped one token short of the whole prompt — at least one token
    must prefill to produce the first next-token logits.  Entries hold
    one pool ref each; :meth:`evict_one` drops the LRU entry whose page
    only the index still references (pages mapped into live slots are
    never evicted — the no-collateral contract chaos asserts)."""

    def __init__(self, pool: PagePool, page_tokens: int):
        self._pool = pool
        self._pt = int(page_tokens)
        self._entries: "collections.OrderedDict[bytes, int]" = \
            collections.OrderedDict()

    def lookup(self, prompt: np.ndarray) -> List[int]:
        """Longest indexed page chain prefixing ``prompt`` (< its full
        length); hit entries refresh their LRU position."""
        max_pages = max(0, (int(prompt.size) - 1) // self._pt)
        pages = []
        for i in range(max_pages):
            key = prompt[:(i + 1) * self._pt].tobytes()
            p = self._entries.get(key)
            if p is None:
                break
            self._entries.move_to_end(key)
            pages.append(p)
        return pages

    def register(self, prompt: np.ndarray, pages: Sequence[int]):
        """Publish a freshly prefilled prompt's fully-covered pages.
        A key that raced in from another slot keeps its existing page
        (this slot's copy stays private and frees with the slot)."""
        for i, p in enumerate(pages):
            key = prompt[:(i + 1) * self._pt].tobytes()
            if key in self._entries:
                self._entries.move_to_end(key)
                continue
            self._entries[key] = p
            self._pool.incref([p])

    def evict_one(self) -> bool:
        """Free the LRU index-only page; False when every indexed page
        is still mapped into a live slot (nothing safely evictable)."""
        for key, p in list(self._entries.items()):
            if self._pool.refcount(p) == 1:
                del self._entries[key]
                self._pool.decref([p])
                return True
        return False

    def flush(self) -> int:
        """Drop EVERY entry (decref all index-held pages) and return
        how many were dropped — the integrity valve for a mid-step
        executor crash, after which the donated pool buffers (and
        therefore every indexed page's K/V) are unknowable."""
        n = len(self._entries)
        for p in self._entries.values():
            self._pool.decref([p])
        self._entries.clear()
        return n

    def __len__(self) -> int:
        return len(self._entries)


class SlotPages:
    """What one slot holds of the cache, on the host: its two block
    tables and the usage ledger's page-second integral.  The engine's
    slot is one of these with the scheduler's state beside it."""

    __slots__ = ("pages", "wpages", "page_us", "page_t", "page_tenant")

    def __init__(self):
        self.pages: List[int] = []   # the full table, logical order
        # the sliding table, logical order too; a page the window slid
        # past is 0 (the trash page)
        self.wpages: List[int] = []
        # page_us accumulates held-pages-×-wall-time in µs, marked
        # forward at every block-table change and booked at release.
        # page_tenant is the request's tenant as of its claim (every
        # finish path clears the slot's request BEFORE releasing the
        # pages); None (usage off) keeps the integration zero-work
        self.page_us = 0
        self.page_t = 0.0
        self.page_tenant: Optional[str] = None


_HANDOFF = "role={role!r} (KV-segment handoff)"
# What each kind of cache refuses (PERF.md section 7): kind -> (what a
# model of that kind is, {feature refused: as the message names it}, why).
# The kinds are ``cache_spec``'s and "block" (block diffusion: B > 1
# positions a step); ``pages`` has no row.  A model of several kinds is
# told of the first, in this order.
REFUSALS = {
    "block": (
        "a block-diffusion model commits a block of {block} positions a "
        "step",
        {"prefix_reuse": "prefix_reuse", "speculate": "speculate",
         "prefill_chunk": "prefill_chunk > 0", "handoff": _HANDOFF},
        "prefix reuse, chunked prefill, speculation and segment adoption "
        "/ export walk one token a step and a causal prefix"),
    "slot_state": (
        "a model whose layers keep slot state (a convolution's last "
        "rows, the delta rule's matrix) has per-slot state that is not "
        "pages",
        {"prefix_reuse": "prefix_reuse", "speculate": "speculate",
         "prefill_chunk": "prefill_chunk > 0", "handoff": _HANDOFF,
         "block_diffusion": "block_diffusion"},
        "a shared prefix or a chunk would have to start from a state "
        "nobody kept, a rejected draft would have to roll it back, a "
        "segment carries pages only, and the state moves on one token a "
        "step, not a block"),
    "window_pages": (
        "a model with sliding-window layers keeps two page pools (full "
        "and window)",
        {"prefix_reuse": "prefix_reuse", "speculate": "speculate",
         "handoff": "role={role!r} (the disagg segment codec)"},
        "prefix reuse, speculation and KV-segment handoff walk one block "
        "table per slot (chunked prefill walks both)"),
    # refuses nothing: a latent page is mapped and rolled back as a K/V
    # page is, but prefix reuse and speculation over it are untried and
    # a segment's codec would find no K and V pools (ROADMAP S6)
    "latent_pages": ("a model whose attention layers are latent", {}, ""),
}


# bytes an item of the dtypes a cache entry is declared in (``cache_spec``)
ITEMSIZE = {"float32": 4, "bfloat16": 2}


class KVCache:
    """Every layer's cache of one engine: the page configuration, the
    pools and the prefix index, the per-slot page accounting, and (after
    :meth:`allocate`) the device arrays.  ``model``: the engine's model
    dict.  ``page_tokens`` / ``num_pages`` None fall back to their flags.
    ``count(key, n)`` receives ``page_evictions`` and
    ``window_pages_released``.  Methods that take a slot run on the
    scheduler thread."""

    def __init__(self, model: Dict, name: str = "llama", *, num_slots: int,
                 max_seq_len: int, page_tokens=None, num_pages=None,
                 num_window_pages=None, prefill_chunk: int = 0,
                 prefix_reuse: bool = False, count=None,
                 dtype: str = "float32"):
        from ..models.llama import cache_spec, layer_spec, window_layers

        # the page pools' dtype: the engine's programs' (slot state is
        # float32 whatever they are, ``cache_spec``)
        self.dtype = str(dtype)
        self.num_slots, self.max_seq_len = int(num_slots), int(max_seq_len)
        self.prefill_chunk = int(prefill_chunk)
        self.prefix_reuse = bool(prefix_reuse)
        self._count = count if count is not None else lambda key, n=1: None
        pt_ = int(page_tokens if page_tokens is not None
                  else flag_value("FLAGS_serving_kv_page_tokens"))
        if pt_ < 1 or (pt_ & (pt_ - 1)):
            raise ValueError(f"FLAGS_serving_kv_page_tokens must be "
                             f"a power of two, got {pt_}")
        if self.max_seq_len % pt_:
            # the gathered logical view is exactly max_seq_len columns
            # wide (the contraction length of the CPU lowering and of
            # the chunk and verify programs) — no ragged last page
            raise ValueError(
                f"max_seq_len {self.max_seq_len} is not a multiple "
                f"of page_tokens {pt_}")
        self.page_tokens = pt_
        self.pages_per_slot = self.max_seq_len // pt_
        self.num_pages = int(
            num_pages if num_pages is not None
            else (flag_value("FLAGS_serving_kv_pages")
                  or self.num_slots * self.pages_per_slot + 1))
        # a pool a block table: the full one, and the sliding one where
        # the model has window pages
        self._pools = {"full": PagePool(self.num_pages)}
        self._prefix: Optional[PrefixIndex] = (
            PrefixIndex(self._pools["full"], pt_)
            if self.prefix_reuse else None)
        n_layers, pattern = model["num_layers"], model.get("layer_pattern")
        widths = {layer_spec(pattern, i)["window"]
                  for i in window_layers(pattern, n_layers)}
        if len(widths) > 1:
            raise ValueError(f"sliding-window layers of one model share "
                             f"one window, got {sorted(widths)}")
        self.window = widths.pop() if widths else None
        # sliding-window layers keep a second pool: a slot needs at most
        # window / page_tokens + 1 of its pages however long it grows
        self.num_window_pages = self.window_pages_per_slot = 0
        if self.window is not None:
            if self.window % pt_:
                raise ValueError(
                    f"sliding window {self.window} is not a multiple "
                    f"of page_tokens {pt_}")
            self.window_pages_per_slot = min(
                self.pages_per_slot, self.window // pt_ + 1)
            # chunked prefill: the ONE slot whose chunk runs holds the
            # chunk's pages beside its window's; every other slot is
            # back under ``window_pages_per_slot`` before the next
            # chunk is chosen (the engine lets go behind each chunk)
            self.num_window_pages = int(
                num_window_pages if num_window_pages is not None
                else self.num_slots * self.window_pages_per_slot + 1
                + -(-max(self.prefill_chunk, 0) // pt_))
            self._pools["window"] = PagePool(self.num_window_pages)
        heads = model["num_heads"]
        self._n_kv = model.get("num_kv_heads") or heads
        self.spec = cache_spec(
            name, n_layers, pattern, num_slots=self.num_slots,
            num_pages=self.num_pages, page_tokens=pt_,
            num_kv_heads=self._n_kv,
            head_dim=model.get("head_dim") or model["hidden"] // heads,
            hidden=model["hidden"],
            num_window_pages=self.num_window_pages or None, dtype=self.dtype)
        self.kinds = {e["kind"] for e in self.spec}
        self.state_names = [e["name"] for e in self.spec
                            if e["kind"] == "slot_state"]

        def nbytes(*kinds, of=0):
            return sum(int(np.prod(e["shape"][of:])) * ITEMSIZE[e["dtype"]]
                       for e in self.spec if e["kind"] in kinds)

        # capacity the pools take (trash pages included), and the
        # per-slot state that is not pages (trash row included)
        self.kv_cache_bytes = nbytes("pages", "latent_pages",
                                     "window_pages")
        self.slot_state_bytes = nbytes("slot_state")
        # bytes one page costs across every layer's pools of its table (a
        # window page spans the window layers only; a latent layer has
        # one pool of rows, not K and V of heads)
        self.page_bytes = nbytes("pages", "latent_pages", of=1)
        self.window_page_bytes = nbytes("window_pages", of=1)
        self.kv_shard_axis = None
        # window pages let go so far (the engine reads differences)
        self.window_released = 0

    def layers_of(self, kind: str) -> List[int]:
        """The layers with a cache entry of ``kind``, in order."""
        return sorted({e["layer"] for e in self.spec if e["kind"] == kind})

    def check_features(self, *, block=0, speculate=False, role="both"):
        """Raise ``ValueError`` for the first of this model's kinds of
        cache, in the order of :data:`REFUSALS`, that refuses a feature
        that is on, and for a block or a chunk the pages cannot hold."""
        pt_ = self.page_tokens
        if block and self.window is not None:
            raise ValueError(
                "block diffusion over sliding-window layers is not "
                "built: the rows of a block share their columns, a "
                "window gives each row its own")
        if block and pt_ % block:
            raise ValueError(
                f"page_tokens {pt_} is not a multiple of the block "
                f"length {block}: a block lies inside one page")
        kinds = self.kinds | ({"block"} if block else set())
        on = {"prefix_reuse": self.prefix_reuse, "speculate": speculate,
              "prefill_chunk": self.prefill_chunk > 0,
              "handoff": role != "both", "block_diffusion": bool(block)}
        for kind, (model, refuses, why) in REFUSALS.items():
            refused = [label for feature, label in refuses.items()
                       if kind in kinds and on[feature]]
            if refused:
                raise ValueError(
                    f"{model} and does not support {', '.join(refused)}: "
                    f"{why}".format(block=block, role=role))
        if self.window is not None and self.prefill_chunk % pt_:
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} is not a "
                f"multiple of page_tokens {pt_}: window pages are "
                f"let go chunk by chunk, at page boundaries")

    # -- the device arrays --------------------------------------------------
    def allocate(self, scope, mesh=None):
        """The page pools and the slot state, allocated and zero filled
        on the device and set in ``scope``: ``startup/pool_alloc`` of the
        start-up account."""
        import jax
        import jax.numpy as jnp

        with telemetry.startup_span("startup/pool_alloc",
                                    pools=len(self.spec)) as span:
            cache_sh = None
            if mesh is not None:
                # page pools [pages, n_kv, page_tokens, D] shard the
                # kv-head dim over ``mp`` when it divides (each device
                # holds its heads' pages — attention is per-head
                # independent, so the contraction never crosses devices);
                # otherwise replicate
                from jax.sharding import NamedSharding, PartitionSpec as P

                from ..parallel.mesh import MP_AXIS, axis_size

                mp = axis_size(mesh, MP_AXIS)
                heads = next((e["shape"][1] for e in self.spec
                              if e["kind"] != "slot_state"), self._n_kv)
                if mp > 1 and heads % mp == 0:
                    self.kv_shard_axis = MP_AXIS
                cache_sh = NamedSharding(
                    mesh, P(None, MP_AXIS) if self.kv_shard_axis else P())
            for entry in self.spec:
                # one DISTINCT zero buffer per pool: the decode step and
                # the prefill scatter donate all pools in one call, and
                # XLA rejects donating the same buffer twice (device_put
                # also allocates a fresh buffer per call)
                zeros = jnp.zeros(tuple(entry["shape"]), entry["dtype"])
                # (slot state is replicated under a mesh: it is small,
                # and its rows are slots, not heads)
                pool = jax.device_put(zeros, cache_sh) \
                    if cache_sh is not None \
                    and entry["kind"] != "slot_state" else zeros.copy()
                # the host dispatches faster than the device fills:
                # without the wait every pool's ``zeros`` lies beside its
                # copy until the device catches up, a second pool's worth
                # of memory (1.6 GB at 48 slots x 2048 x 4 layers, my
                # chip run, PR 32)
                jax.block_until_ready(pool)
                scope.set_var(entry["name"], pool)
            span.attrs["bytes"] = self.kv_cache_bytes \
                + self.slot_state_bytes
        telemetry.gauge_set("serving_slot_state_bytes",
                            self.slot_state_bytes)
        telemetry.gauge_set("serving_kv_cache_bytes", self.kv_cache_bytes)
        self.publish_gauges()

    # -- what the pools hold ------------------------------------------------
    def live_pages(self, table: str = "full") -> int:
        return self._pools[table].live_pages

    def free_pages(self, table: str = "full") -> int:
        return self._pools[table].free_pages

    def refcount(self, page: int, table: str = "full") -> int:
        return self._pools[table].refcount(page)

    @property
    def kv_live_bytes(self) -> int:
        """Bytes of pool pages referenced by live sequences or the
        prefix index right now."""
        live = self._pools["full"].live_pages * self.page_bytes
        if self.window is not None:
            live += self._pools["window"].live_pages \
                * self.window_page_bytes
        return live

    def publish_gauges(self):
        full = self._pools["full"]
        telemetry.gauge_set("serving_kv_pages_free", full.free_pages)
        telemetry.gauge_set("serving_kv_pages_live", full.live_pages)
        telemetry.gauge_set("serving_kv_live_bytes", self.kv_live_bytes)
        if "latent_pages" in self.kinds:
            telemetry.gauge_set("serving_latent_pages_live",
                                full.live_pages)
        if self.window is not None:
            telemetry.gauge_set("serving_kv_pages_live_full",
                                full.live_pages)
            telemetry.gauge_set("serving_kv_pages_live_window",
                                self._pools["window"].live_pages)

    # -- the prefix index ---------------------------------------------------
    @property
    def prefix_entries(self) -> int:
        return len(self._prefix) if self._prefix else 0

    def map_prefix(self, slot: SlotPages, prompt: np.ndarray) -> int:
        """Map the longest indexed page chain prefixing ``prompt`` into
        ``slot``, whose page hold starts here; how many tokens it
        serves (0: a miss, or no index)."""
        hit = self._prefix.lookup(prompt) if self._prefix is not None \
            else None
        if not hit:
            return 0
        self._pools["full"].incref(hit)
        self.mark_pages(slot)
        slot.pages = list(hit)
        return len(hit) * self.page_tokens

    def register_prefix(self, slot: SlotPages, prompt: np.ndarray):
        """Publish the pages ``prompt``, freshly prefilled into
        ``slot``, covers whole."""
        full = int(prompt.size) // self.page_tokens
        if self._prefix is not None and full:
            self._prefix.register(prompt, slot.pages[:full])
            self.publish_gauges()

    def flush_prefix(self) -> int:
        """Drop every entry of the index (:meth:`PrefixIndex.flush`);
        how many went."""
        if self._prefix is None:
            return 0
        dropped = self._prefix.flush()
        self.publish_gauges()
        return dropped

    # -- a slot's pages -----------------------------------------------------
    def mark_pages(self, slot: SlotPages, now: Optional[float] = None):
        """Advance the slot's KV page-second integral (µs × pages
        held) up to ``now`` — called before EVERY block-table change
        so the integral prices exactly what the pool saw."""
        if slot.page_tenant is None:
            return
        t = time.monotonic() if now is None else now
        if slot.pages and slot.page_t:
            slot.page_us += int((t - slot.page_t) * 1e6) * len(slot.pages)
        slot.page_t = t

    def release_pages(self, slot: SlotPages):
        """Drop the slot's refs on its pages (shared prefix pages fall
        back to the index's ref; private pages free) and refresh the
        pool gauges.  Books the sequence's accumulated KV
        page-seconds to its tenant — this is the single exit every
        hold path (finish, fail, requeue, export, decode crash)
        funnels through."""
        if slot.pages or slot.wpages:
            self.mark_pages(slot)
            self._pools["full"].decref(slot.pages)
            if slot.wpages:
                self._pools["window"].decref(
                    [p for p in slot.wpages if p])
                slot.wpages = []
            self.publish_gauges()
        if slot.page_tenant is not None:
            if slot.page_us:
                usage.ledger().book(slot.page_tenant,
                                    page_us=slot.page_us)
            slot.page_tenant = None
        slot.page_us = 0
        slot.page_t = 0.0
        slot.pages = []

    def ensure_pages(self, slot: SlotPages, n_tokens: int, rows: int = 1):
        """Grow the slot's block table to cover ``n_tokens`` logical
        tokens, evicting idle prefix-index pages when the free list
        runs dry.  ``rows``: how many of them, the last ones, the next
        program attends from (a chunk's; the window kind keeps what the
        earliest of them admits).  Raises :class:`PoolExhausted` when
        nothing is left to evict — the caller turns that into
        ``cache_full`` (decode) or a failed request (prefill)."""
        needed = -(-int(n_tokens) // self.page_tokens)  # ceil
        if len(slot.pages) < needed:
            self.mark_pages(slot)
            pool = self._pools["full"]
        while len(slot.pages) < needed:
            p = pool.alloc()
            if p is None:
                if self._prefix is not None and self._prefix.evict_one():
                    self._count("page_evictions")
                    stat_add("serving_kv_page_evictions")
                    continue
                raise PoolExhausted(
                    f"kv page pool exhausted ({pool.live_pages}"
                    f"/{self.num_pages - 1} pages live, nothing "
                    f"evictable)")
            slot.pages.append(p)
        if self.window is not None:
            self.slide_window_pages(slot, int(n_tokens), needed, rows)
        self.publish_gauges()

    def slide_window_pages(self, slot: SlotPages, n_tokens: int,
                           needed: int, rows: int = 1):
        """The window kind's half of :meth:`ensure_pages`: the
        earliest of the last ``rows`` of ``n_tokens`` positions attends
        ``j >= n_tokens - rows + 1 - window``, so logical pages left of
        that column's page are released (their entries become the trash
        page 0) and pages up to ``needed`` are mapped.  A decoding slot
        so holds at most ``window / page_tokens + 1`` window pages, a
        slot whose chunk of C rows runs at most ``(window + C) /
        page_tokens + 1``, and a single-shot prefill maps only the last
        window of a long prompt."""
        first = max(0, n_tokens - rows + 1 - self.window) \
            // self.page_tokens
        pool, wp = self._pools["window"], slot.wpages
        gone = [p for p in wp[:first] if p]
        if gone:
            pool.decref(gone)
            wp[:first] = [0] * min(first, len(wp))
            self.window_released += len(gone)
            self._count("window_pages_released", len(gone))
            stat_add("serving_kv_window_pages_released", len(gone))
        while len(wp) < needed:
            if len(wp) < first:
                wp.append(0)
                continue
            p = pool.alloc()
            if p is None:
                raise PoolExhausted(
                    f"kv window page pool exhausted ("
                    f"{pool.live_pages}/"
                    f"{self.num_window_pages - 1} pages live)")
            wp.append(p)

    def block_table(self, slot: Optional[SlotPages],
                    window: bool = False) -> np.ndarray:
        """``slot``'s full or sliding table, [pages_per_slot] int32,
        unmapped entries the trash page (None: no slot's, all of it)."""
        bt = np.zeros((self.pages_per_slot,), "int32")
        if slot is not None:
            pages = slot.wpages if window else slot.pages
            bt[:len(pages)] = pages
        return bt

    def table_feeds(self, slot: Optional[SlotPages]) -> Dict[str, np.ndarray]:
        """The block-table feeds [1, pages_per_slot] of a program that
        runs one slot's rows (a prefill, a chunk): ``block_table`` and,
        with window pages, ``block_table_window``."""
        feeds = {"block_table": self.block_table(slot)[None]}
        if self.window is not None:
            feeds["block_table_window"] = \
                self.block_table(slot, window=True)[None]
        return feeds

    def acquire_draft_pages(self, slot: SlotPages, n_tokens: int) -> int:
        """Provisionally grow the slot's block table to hold a draft's
        verify rows.  Returns the page count to KEEP on rollback (the
        pre-draft table length).  On exhaustion the partial growth is
        rolled back HERE and :class:`PoolExhausted` re-raised: the
        caller finds the block table as it left it."""
        keep = len(slot.pages)
        try:
            self.ensure_pages(slot, n_tokens)
        except PoolExhausted:
            self.rollback_draft_pages(slot, keep)
            raise
        return keep

    def rollback_draft_pages(self, slot: SlotPages, keep_pages: int) -> int:
        """Drop the slot's refs on draft pages past ``keep_pages``.
        The rejected rows' K/V needs no device-side undo: rows past the
        committed position are outside every later step's causal
        validity window (``j <= base + t``) and the next real write
        there overwrites them.  Pairs with :meth:`acquire_draft_pages`
        (graftcheck's resource-pairing pass polices the pairing)."""
        dropped = slot.pages[keep_pages:]
        if dropped:
            self.mark_pages(slot)
            self._pools["full"].decref(dropped)
            del slot.pages[keep_pages:]
            self.publish_gauges()
        return len(dropped)
