"""Recommender serving tier: ep-sharded embedding lookups + hot-row cache.

The reference framework's flagship parameter-server workload is Wide&Deep
CTR over sparse lookup tables (PAPER.md: SelectedRows / lookup_table;
``paddle_tpu/models/wide_deep.py``): a vocabulary far larger than any one
device's memory, served at thousands of tiny requests per second.  The PS
answer was server-resident tables behind RPC.  This module recasts that
role as **sharded serving**: the table row-shards across the local device
ring (the ep axis — pure data placement, no contracting dims, so
reassembly is bit-exact vs the unsharded table), each shard owns one
donated gather program, and a refcounted **hot-row cache** fronts the
shards with the same LRU discipline the paged KV cache's
:class:`~paddle_tpu.serving.kv_cache.PrefixIndex` uses for prompt
prefixes — hit rate, evictions and bytes are first-class stats.

Three layers:

* :class:`RowSharding` — the placement rule (``mod`` stripes row ``r``
  onto shard ``r % shards``; ``range`` gives shard ``s`` a contiguous
  block), with the exact inverse mapping used to reassemble gathers in
  logical order.
* :class:`ShardedEmbeddingTable` — the tier: per-shard device-placed
  sub-tables, one AOT-compiled gather executable per (shard, padded-size)
  signature (the output scratch buffer is donated — the gather writes
  straight into it), the :class:`HotRowCache`, and the degradation
  contract: a **dead shard degrades** (ids it owns serve from the hot
  cache when present, else the default row, booked as
  ``serving_embedding_degraded``) instead of failing the lookup — a
  recommender that returns a slightly-stale or default embedding beats
  one that 500s the feed.  ``kill_shard``/``revive_shard`` drive it in
  tests and chaos; the ``embedding_gather`` fault site injects it live.
* :class:`EmbeddingPredictor` — the serving front: implements the
  :class:`~paddle_tpu.inference.Predictor` contract (``run``/``warmup``/
  ``clone``/``cache_info``) over a feed of ``sparse_ids`` (int64
  ``[b, slots]``) + ``dense_x`` (float32 ``[b, dense]``), gathering the
  fused wide+deep rows through the tier and running the dense remainder
  of Wide&Deep (:func:`~paddle_tpu.models.wide_deep.wide_deep_serving_net`)
  through a normal compiled program.  The wide ``[vocab, 1]`` and deep
  ``[vocab, dim]`` tables fuse into ONE ``[vocab, 1+dim]`` table so each
  id costs one gather and one cache row.

A ServingEngine built over an :class:`EmbeddingPredictor` advertises the
``embedding`` capability in ``/healthz`` (the fleet router learns it like
disagg roles and routes ``sparse_ids`` requests to capable replicas) and
carries the tier's stats block in ``/healthz``/``/statusz``.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import fault, telemetry
from ..flags import flag_value
from ..monitor import stat_add
from . import usage

__all__ = ["RowSharding", "HotRowCache", "ShardedEmbeddingTable",
           "EmbeddingPredictor", "build_recsys_predictor"]

PLACEMENTS = ("mod", "range")


class RowSharding:
    """Row-placement rule for a ``[vocab, dim]`` table over ``shards``
    shards — the serving analog of the parallel ShardingRules: a pure
    bijection ``global row -> (shard, local row)`` with no overlap, so
    sharded gathers reassembled through it are bit-identical to an
    unsharded ``jnp.take``.

    * ``mod``: row ``r`` lives on shard ``r % shards`` at local index
      ``r // shards`` — uniform occupancy under ANY id distribution
      (hot ids spread across shards), the default.
    * ``range``: shard ``s`` owns the contiguous block
      ``[s*per, min((s+1)*per, vocab))`` with ``per = ceil(vocab/shards)``
      — locality for range-partitioned id spaces.
    """

    def __init__(self, vocab: int, shards: int, placement: str = "mod"):
        if vocab < 1:
            raise ValueError(f"vocab must be >= 1, got {vocab}")
        if shards < 1 or shards > vocab:
            raise ValueError(f"need 1 <= shards <= vocab, got {shards} "
                             f"shards for vocab {vocab}")
        if placement not in PLACEMENTS:
            raise ValueError(f"unknown placement {placement!r}; want "
                             f"one of {PLACEMENTS}")
        self.vocab = int(vocab)
        self.shards = int(shards)
        self.placement = placement
        self._per = -(-self.vocab // self.shards)  # ceil, for 'range'

    def shard_of(self, ids):
        """Owning shard per id (vectorized; ids must be in-vocab)."""
        ids = np.asarray(ids, dtype=np.int64)
        if self.placement == "mod":
            return ids % self.shards
        return np.minimum(ids // self._per, self.shards - 1)

    def local_of(self, ids):
        """Local row index inside the owning shard (vectorized)."""
        ids = np.asarray(ids, dtype=np.int64)
        if self.placement == "mod":
            return ids // self.shards
        return ids - self.shard_of(ids) * self._per

    def rows_of(self, shard: int) -> np.ndarray:
        """The GLOBAL row ids shard ``shard`` owns, in local order —
        the selector that builds the shard's sub-table."""
        if not 0 <= shard < self.shards:
            raise ValueError(f"shard {shard} out of range "
                             f"[0, {self.shards})")
        if self.placement == "mod":
            return np.arange(shard, self.vocab, self.shards,
                             dtype=np.int64)
        lo = shard * self._per
        return np.arange(lo, min(lo + self._per, self.vocab),
                         dtype=np.int64)

    def spec(self) -> dict:
        return {"vocab": self.vocab, "shards": self.shards,
                "placement": self.placement}


class _HotRow:
    __slots__ = ("row", "refs")

    def __init__(self, row: np.ndarray):
        self.row = row
        self.refs = 0


class HotRowCache:
    """Refcounted LRU cache of embedding rows, modeled on the paged KV
    cache's PrefixIndex/PagePool discipline (``kv_cache.py``): entries a
    live lookup has **pinned** (refcount > 0) are never evicted; eviction
    takes the least-recently-used unpinned entry; ``unpin`` below zero is
    a refcount-discipline bug and asserts.  All mutation is lock-guarded
    (lookups run on every engine worker thread).  ``capacity_rows=0``
    disables the cache (every probe misses, nothing inserts)."""

    def __init__(self, capacity_rows: int, row_nbytes: int):
        if capacity_rows < 0:
            raise ValueError(f"capacity_rows must be >= 0, "
                             f"got {capacity_rows}")
        self.capacity = int(capacity_rows)
        self._row_nbytes = int(row_nbytes)
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[int, _HotRow]" = \
            collections.OrderedDict()
        self._pinned = 0  # outstanding pins across all entries
        self._n = {"hits": 0, "misses": 0, "inserts": 0, "evictions": 0,
                   "insert_skips": 0}

    def get_pinned(self, key: int) -> Optional[np.ndarray]:
        """Probe + pin: a hit refreshes LRU position and takes one ref
        (the caller MUST :meth:`unpin` after consuming the row — the
        pin is what makes a concurrent insert's eviction scan skip
        rows mid-read).  Returns None on miss."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self._n["misses"] += 1
                return None
            self._entries.move_to_end(key)
            e.refs += 1
            self._pinned += 1
            self._n["hits"] += 1
            return e.row

    def unpin(self, key: int):
        with self._lock:
            e = self._entries[key]  # pinned entries are never evicted
            e.refs -= 1
            self._pinned -= 1
            if e.refs < 0 or self._pinned < 0:
                raise AssertionError(
                    f"hot-row {key} refcount underflow "
                    f"(refs={e.refs}, pinned={self._pinned})")

    def put(self, key: int, row: np.ndarray) -> bool:
        """Insert a freshly gathered row, evicting LRU unpinned entries
        to make room.  False when the cache is disabled, or full of
        pinned rows (the insert is skipped — counted, never blocking:
        a lookup must not wait on cache housekeeping)."""
        if self.capacity == 0:
            return False
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return True
            while len(self._entries) >= self.capacity:
                if not self._evict_one_locked():
                    self._n["insert_skips"] += 1
                    return False
            self._entries[key] = _HotRow(row)
            self._n["inserts"] += 1
            return True

    def _evict_one_locked(self) -> bool:
        for key, e in self._entries.items():
            if e.refs == 0:
                del self._entries[key]
                self._n["evictions"] += 1
                return True
        return False

    def flush(self) -> int:
        """Drop every UNPINNED entry; returns how many were dropped
        (pinned rows stay — a flush racing a live lookup must not pull
        rows out from under it)."""
        with self._lock:
            keep = {k: e for k, e in self._entries.items() if e.refs > 0}
            dropped = len(self._entries) - len(keep)
            self._entries = collections.OrderedDict(keep)
            return dropped

    @property
    def pinned(self) -> int:
        with self._lock:
            return self._pinned

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            n = dict(self._n)
            rows = len(self._entries)
            pinned = self._pinned
        probes = n["hits"] + n["misses"]
        return {"rows": rows, "capacity": self.capacity,
                "bytes": rows * self._row_nbytes, "pinned": pinned,
                "hit_rate": round(n["hits"] / probes, 4) if probes
                else None, **n}


class ShardedEmbeddingTable:
    """A ``[vocab, dim]`` float32 embedding table row-sharded across the
    local device ring, served through per-shard donated gather programs
    and fronted by a :class:`HotRowCache`.

    ``lookup(ids)`` returns ``ids.shape + (dim,)`` float32, bit-exact
    vs ``jnp.take(full_table, ids, axis=0)`` (tolerance 0): unique ids
    probe the hot cache, misses group by owning shard, each shard runs
    ONE gather over its local indices, and results scatter back into
    logical order through the :class:`RowSharding` inverse — no
    reductions anywhere, so sharding can never perturb a bit.

    Degradation contract: ids owned by a dead shard (``kill_shard``, or
    an injected ``embedding_gather`` fault) serve from the hot cache
    when present, else ``default_row`` — booked as
    ``serving_embedding_degraded`` (+ ``..._degraded_rows``), never an
    exception.  Out-of-vocab ids likewise serve ``default_row``
    (``serving_embedding_oob_rows``): a corrupt id must not fail the
     200-row batch it rides in.
    """

    def __init__(self, values, *, shards: Optional[int] = None,
                 placement: Optional[str] = None,
                 cache_rows: Optional[int] = None,
                 name: str = "embedding", devices=None,
                 default_row: Optional[np.ndarray] = None):
        import jax

        values = np.ascontiguousarray(np.asarray(values,
                                                 dtype=np.float32))
        if values.ndim != 2:
            raise ValueError(f"embedding table must be 2-D [vocab, dim],"
                             f" got shape {values.shape}")
        self.name = name
        self.vocab, self.dim = int(values.shape[0]), int(values.shape[1])
        devices = list(devices if devices is not None else jax.devices())
        if shards is None:
            shards = int(flag_value("FLAGS_embedding_shards") or 0) \
                or len(devices)
        shards = min(int(shards), self.vocab)
        placement = placement or \
            str(flag_value("FLAGS_embedding_placement") or "mod")
        self.sharding = RowSharding(self.vocab, shards, placement)
        self.num_shards = self.sharding.shards
        # shards cycle the device ring: more shards than devices is the
        # larger-than-HBM case (each device holds several sub-tables,
        # each individually placeable/evictable)
        self._devices = [devices[s % len(devices)]
                         for s in range(self.num_shards)]
        self._shards = [
            jax.device_put(values[self.sharding.rows_of(s)],
                           self._devices[s])
            for s in range(self.num_shards)]
        if default_row is None:
            default_row = np.zeros((self.dim,), np.float32)
        self.default_row = np.asarray(default_row,
                                      dtype=np.float32).reshape(self.dim)
        if cache_rows is None:
            cache_rows = int(flag_value("FLAGS_embedding_cache_rows")
                             or 0)
        self.cache = HotRowCache(cache_rows, row_nbytes=self.dim * 4)
        self._dead: set = set()
        self._state_lock = threading.Lock()    # _dead + counters
        self._compile_lock = threading.RLock()  # gather executable cache
        self._gather_cache: Dict[tuple, tuple] = {}
        self._n = {"lookups": 0, "rows": 0, "degraded": 0,
                   "degraded_rows": 0, "oob_rows": 0}
        self._h_lookup = telemetry.Histogram("serving_embedding_lookup_ms")
        # cached gauge handles (registry round-trip paid once, not per
        # lookup) — mirrors the engine's queue-depth gauge discipline
        self._g_rows = telemetry.metrics.gauge("serving_embedding_hot_rows")
        self._g_bytes = telemetry.metrics.gauge(
            "serving_embedding_hot_bytes")
        self._g_pinned = telemetry.metrics.gauge(
            "serving_embedding_hot_pinned")
        self._g_dead = telemetry.metrics.gauge(
            "serving_embedding_shards_dead")

    # -- gather programs ----------------------------------------------------
    def _gather_compiled(self, shard: int, pad: int):
        """The shard's AOT gather executable at one padded id-count
        signature: ``out[:] = take(sub_table, ids)`` with the ``out``
        scratch DONATED — XLA writes the gathered rows straight into
        the donated buffer instead of allocating a fresh result.
        Compiled under the lock (two racing threads must not both
        build the same signature); the manifest rides the cache entry
        into :meth:`gather_cache_info` (gather-path flops/bytes are
        read off it)."""
        import jax
        import jax.numpy as jnp

        from ..costmodel import executable_manifest

        key = (shard, pad)
        with self._compile_lock:
            entry = self._gather_cache.get(key)
            if entry is None:
                def gather_fn(table, ids, out):
                    return out.at[:, :].set(
                        jnp.take(table, ids, axis=0))

                jitted = jax.jit(gather_fn, donate_argnums=(2,))
                lowered = jitted.lower(
                    self._shards[shard],
                    jax.ShapeDtypeStruct((pad,), jnp.int64),
                    jax.ShapeDtypeStruct((pad, self.dim), jnp.float32))
                compiled = lowered.compile()
                entry = (compiled,
                         executable_manifest(
                             compiled,
                             signature=(f"{self.name}/shard{shard}",
                                        pad)))
                self._gather_cache[key] = entry
            return entry[0]

    def _gather(self, shard: int, local_ids: np.ndarray) -> np.ndarray:
        """One device gather on ``shard``: ids pad up to the next power
        of two (pad slots gather local row 0, sliced off after) so the
        executable count stays logarithmic in batch size."""
        n = int(local_ids.size)
        pad = 1 << max(0, (n - 1).bit_length())
        padded = np.zeros((pad,), np.int64)
        padded[:n] = local_ids
        compiled = self._gather_compiled(shard, pad)
        out = compiled(self._shards[shard], padded,
                       np.empty((pad, self.dim), np.float32))
        return np.asarray(out)[:n]

    def gather_cache_info(self) -> dict:
        """Compiled gather-executable inventory (+ manifests) for
        ``/statusz``.  Non-blocking like Predictor.cache_info: a status
        probe must never stall behind an XLA compile."""
        from ..costmodel import manifest_summary

        if not self._compile_lock.acquire(timeout=0.05):
            return {"compiled": None, "busy": True}
        try:
            entries = list(self._gather_cache.items())
        finally:
            self._compile_lock.release()
        return {"compiled": len(entries),
                "signatures": sorted(f"shard{s}:pad{p}"
                                     for s, p in (k for k, _ in entries)),
                "manifests": {f"shard{k[0]}:pad{k[1]}":
                              manifest_summary(e[1])
                              for k, e in sorted(entries)}}

    # -- the lookup ---------------------------------------------------------
    def lookup(self, ids) -> np.ndarray:
        """Gather ``ids`` (any int shape) -> ``ids.shape + (dim,)``
        float32 rows; see the class docstring for the exactness and
        degradation contracts."""
        t0 = time.perf_counter()
        arr = np.asarray(ids)
        if arr.dtype != np.int64:
            arr = arr.astype(np.int64)
        flat = arr.reshape(-1)
        uniq, inv = np.unique(flat, return_inverse=True)
        rows = np.empty((uniq.size, self.dim), dtype=np.float32)
        oob = (uniq < 0) | (uniq >= self.vocab)
        safe = np.clip(uniq, 0, self.vocab - 1)
        shard_of = self.sharding.shard_of(safe)
        local_of = self.sharding.local_of(safe)
        pinned: List[int] = []
        miss_by_shard: Dict[int, List[int]] = {}
        n_oob = int(oob.sum())
        degraded_shards: List[int] = []
        degraded_rows = 0
        try:
            for j in range(uniq.size):
                if oob[j]:
                    rows[j] = self.default_row
                    continue
                g = int(uniq[j])
                row = self.cache.get_pinned(g)
                if row is not None:
                    rows[j] = row
                    pinned.append(g)
                else:
                    miss_by_shard.setdefault(int(shard_of[j]),
                                             []).append(j)
            for s in sorted(miss_by_shard):
                js = miss_by_shard[s]
                kind = fault.fire("embedding_gather")
                fault.maybe_delay(kind)
                with self._state_lock:
                    dead = s in self._dead
                if dead or kind == "fail":
                    # the degradation contract: a dead shard's rows
                    # serve the default row (cache hits already served
                    # exact above) — booked, never raised
                    for j in js:
                        rows[j] = self.default_row
                    degraded_rows += len(js)
                    degraded_shards.append(s)
                    continue
                got = self._gather(s, local_of[js])
                rows[js] = got
                for j in js:
                    self.cache.put(int(uniq[j]), np.array(rows[j]))
        finally:
            for g in pinned:
                self.cache.unpin(g)
        out = rows[inv].reshape(arr.shape + (self.dim,))
        ms = (time.perf_counter() - t0) * 1e3
        with self._state_lock:
            self._n["lookups"] += 1
            self._n["rows"] += int(flat.size)
            self._n["oob_rows"] += n_oob
            if degraded_rows:
                self._n["degraded"] += 1
                self._n["degraded_rows"] += degraded_rows
        stat_add("serving_embedding_lookups")
        stat_add("serving_embedding_rows", int(flat.size))
        if pinned and usage.enabled():
            # thread-local handoff to the batching engine: lookup runs
            # inside predictor.run on the worker thread, and the batch
            # mixes tenants — the engine takes these hits right after
            # the dispatch and splits them row-weighted per tenant
            usage.note_hot_row_hits(len(pinned))
        if n_oob:
            stat_add("serving_embedding_oob_rows", n_oob)
        if degraded_rows:
            stat_add("serving_embedding_degraded")
            stat_add("serving_embedding_degraded_rows", degraded_rows)
        self._h_lookup.observe(ms)
        if telemetry.enabled():
            hot = self.cache.stats()
            self._g_rows.set(hot["rows"])
            self._g_bytes.set(hot["bytes"])
            self._g_pinned.set(hot["pinned"])
        return out

    # -- degradation control ------------------------------------------------
    def kill_shard(self, shard: int):
        """Mark one shard dead (its ids degrade to cache/default-row
        service).  Idempotent; ``revive_shard`` undoes it."""
        if not 0 <= shard < self.num_shards:
            raise ValueError(f"shard {shard} out of range "
                             f"[0, {self.num_shards})")
        with self._state_lock:
            self._dead.add(int(shard))
            dead = len(self._dead)
        if telemetry.enabled():
            self._g_dead.set(dead)

    def revive_shard(self, shard: int):
        with self._state_lock:
            self._dead.discard(int(shard))
            dead = len(self._dead)
        if telemetry.enabled():
            self._g_dead.set(dead)

    @property
    def dead_shards(self) -> List[int]:
        with self._state_lock:
            return sorted(self._dead)

    # -- introspection ------------------------------------------------------
    def placement(self) -> dict:
        """Same shape the mesh-sharded predictor reports (the engine's
        ``worker_health`` merges it verbatim): mesh axes, device ids,
        and ``missing_shards`` — here the DEAD shard indices, which
        flips the group status to ``missing_shards`` and the replica
        ``/healthz`` status to ``degraded`` without stopping it."""
        return {"mesh": {"ep": self.num_shards},
                "devices": [int(d.id) for d in self._devices],
                "missing_shards": self.dead_shards}

    def device_ids(self) -> List[int]:
        return [int(d.id) for d in self._devices]

    def stats(self) -> dict:
        with self._state_lock:
            n = dict(self._n)
        hot = self.cache.stats()
        return {"name": self.name, "vocab": self.vocab, "dim": self.dim,
                "shards": self.num_shards,
                "placement_rule": self.sharding.placement,
                "devices": self.device_ids(),
                "dead_shards": self.dead_shards,
                "counters": n, "hot_rows": hot,
                "hit_rate": hot["hit_rate"],
                "lookup_ms": self._h_lookup.summary()}


class EmbeddingPredictor:
    """Wide&Deep serving predictor over the sharded embedding tier.

    Duck-types the :class:`~paddle_tpu.inference.Predictor` contract the
    serving engine relies on (``predictor_like`` marks it so the engine
    skips its Program-wrapping path): feed is ``sparse_ids`` (int64
    ``[b, slots]``) + ``dense_x`` (float32 ``[b, dense]``); ``run``
    gathers each id's fused wide+deep row through the tier (hot cache →
    shard gathers), splits the wide column from the deep block, and runs
    the dense remainder through a normal compiled ``inner`` Predictor —
    which keeps AOT bucket compilation, executable manifests, thread
    safety and weight hot-swap (dense weights only; the table tier is
    static) exactly as dense serving has them.  ``clone()`` shares the
    TABLE (one hot cache, one set of shard buffers per process — hit
    rate is a process property) while cloning the inner predictor.
    """

    predictor_like = True

    def __init__(self, inner, table: ShardedEmbeddingTable, *,
                 num_sparse: int, num_dense: int):
        self._inner = inner
        self.table = table
        self.num_sparse = int(num_sparse)
        self.num_dense = int(num_dense)
        self.embed_dim = table.dim - 1  # column 0 is the wide table
        if self.embed_dim < 1:
            raise ValueError("fused table needs dim >= 2 "
                             "(wide column + deep block)")
        self.feed_names = ["sparse_ids", "dense_x"]
        self.fetch_names = list(inner.fetch_names)

    # -- reference-API accessors -------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self.feed_names)

    def get_output_names(self) -> List[str]:
        return list(self.fetch_names)

    def feed_dtypes(self) -> List[np.dtype]:
        """Feed dtypes in ``feed_names`` order — the engine's
        ``coerce_feed`` reads these instead of program block vars
        (there is no block var for ``sparse_ids``; the lookup happens
        outside the graph)."""
        return [np.dtype(np.int64), np.dtype(np.float32)]

    # -- serving ------------------------------------------------------------
    def run(self, feed, return_numpy: bool = True):
        if not isinstance(feed, dict):
            feed = dict(zip(self.feed_names, feed))
        ids = np.asarray(feed["sparse_ids"])
        dense = np.asarray(feed["dense_x"], dtype=np.float32)
        fused = self.table.lookup(ids)          # [b, slots, 1+dim]
        wide_rows = np.ascontiguousarray(fused[..., :1])
        deep_rows = np.ascontiguousarray(fused[..., 1:])
        return self._inner.run({"wide_rows": wide_rows,
                                "deep_rows": deep_rows,
                                "dense_x": dense}, return_numpy)

    def warmup(self, feed_shapes) -> int:
        """Predictor.warmup contract over the PUBLIC feed: runs zeros
        through the full path (tier lookup + dense program), so every
        batch bucket's dense executable is compiled AND primed.
        Returns dense executables compiled now (gather programs compile
        lazily per observed unique-id count — they are a few hundred
        bytes of HLO each)."""
        if isinstance(feed_shapes, dict):
            feed_shapes = [feed_shapes]
        before = len(self._inner._cache)
        for shapes in feed_shapes:
            feed = {n: np.zeros(tuple(shapes[n]), dtype=dt)
                    for n, dt in zip(self.feed_names,
                                     self.feed_dtypes())}
            self.run(feed)
        return max(0, len(self._inner._cache) - before)

    def cache_info(self) -> dict:
        info = self._inner.cache_info()
        info["gather"] = self.table.gather_cache_info()
        return info

    def clone(self) -> "EmbeddingPredictor":
        return EmbeddingPredictor(self._inner.clone(), self.table,
                                  num_sparse=self.num_sparse,
                                  num_dense=self.num_dense)

    # -- tier passthrough (engine health / capability plumbing) -------------
    def placement(self) -> dict:
        return self.table.placement()

    def device_ids(self) -> List[int]:
        return self.table.device_ids()

    def embedding_stats(self) -> dict:
        """The /healthz | /statusz ``embedding`` block; its presence is
        what makes the engine advertise the ``embedding`` capability."""
        return self.table.stats()

    # -- weight hot-swap: dense head delegates to the inner predictor -------
    def weights_doc(self):
        return self._inner.weights_doc()

    def weights_fingerprint(self):
        return self._inner.weights_fingerprint()

    def swap_weights(self, checkpoint, **kw):
        return self._inner.swap_weights(checkpoint, **kw)

    def revert_weights(self):
        return self._inner.revert_weights()

    def rebind_weights(self):
        return self._inner.rebind_weights()


def build_recsys_predictor(num_sparse: int = 26, num_dense: int = 13,
                           vocab: int = 100_000, embed_dim: int = 8,
                           hidden: Sequence[int] = (64, 32),
                           seed: int = 0,
                           shards: Optional[int] = None,
                           placement: Optional[str] = None,
                           cache_rows: Optional[int] = None,
                           devices=None):
    """Synthetic Wide&Deep serving predictor (the recsys analog of the
    loadgen's ``build_synthetic`` MLP — no files needed): a seeded fused
    ``[vocab, 1+embed_dim]`` table sharded over the tier + the dense
    remainder program.  Returns ``(EmbeddingPredictor, per_row_shapes)``
    ready for a ServingEngine (``shapes`` plug straight into
    ``engine.warmup``)."""
    import paddle_tpu as pt
    from ..inference import Predictor
    from ..models.wide_deep import wide_deep_serving_net

    rng = np.random.RandomState(seed)
    # wide column fused ahead of the deep block: one gather serves both
    values = (rng.standard_normal((vocab, 1 + embed_dim))
              .astype(np.float32) * 0.05)
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    startup.random_seed = main.random_seed = seed
    with pt.program_guard(main, startup):
        net = wide_deep_serving_net(num_sparse=num_sparse,
                                    num_dense=num_dense,
                                    embed_dim=embed_dim,
                                    hidden=tuple(hidden))
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    inner = Predictor(main, ["wide_rows", "deep_rows", "dense_x"],
                      [net["prob"]], scope=scope)
    table = ShardedEmbeddingTable(values, shards=shards,
                                  placement=placement,
                                  cache_rows=cache_rows,
                                  name="wide_deep", devices=devices)
    pred = EmbeddingPredictor(inner, table, num_sparse=num_sparse,
                              num_dense=num_dense)
    return pred, {"sparse_ids": (num_sparse,), "dense_x": (num_dense,)}
