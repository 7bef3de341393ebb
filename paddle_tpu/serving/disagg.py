"""Disaggregated prefill/decode serving: the KV-segment handoff.

Prefill is compute-bound and bursty; decode is memory-bandwidth-bound
and steady.  One replica doing both lets a single long prompt wreck
decode p99 for every rider (the DistServe / Splitwise observation).
This module is the handoff layer that lets the fleet split the roles:

* A **prefill-role** :class:`~paddle_tpu.serving.generation.
  GenerationEngine` runs the existing paged prefill (chunked prefill
  and shared-prefix reuse included), then *exports* the populated
  pages of the sequence as a versioned :class:`KVSegment` — per-layer
  page blocks in logical order, lengths, the tokens generated so far
  (the prefill's first token), and a model/config **fingerprint** —
  and frees the slot for the next prompt.  It never occupies a decode
  slot.
* A **decode-role** engine *adopts* a segment: free pages come from
  its own :class:`~paddle_tpu.serving.kv_cache.PagePool` (refcount-
  integrated; pool exhaustion evicts idle prefix pages / requeues
  exactly like a local prefill), the segment's page blocks scatter
  into those physical pages, and the sequence enters the decode grid
  at its recorded position.  Because ``kv_pool_gather`` rebuilds the
  identical dense logical view from *any* physical page placement,
  the adopted sequence's decode is **bit-exact** (tokens AND logits,
  tolerance 0) against a colocated engine that ran prefill+decode
  itself — asserted in ``tests/test_disagg.py``.

**Transports.**  :class:`SegmentTransport` is the seam a cross-host
transport later slots into.  Two implementations ship:

* :class:`DeviceTransport` — single-host handoff: the page blocks
  move device-to-device with ``jax.device_put`` (between sub-meshes
  when the engines own different device subsets).  No host round-trip
  of the K/V bytes.
* :class:`HostBytesTransport` — the serialization path the HTTP
  ``POST /adopt`` hop and a future RDMA/TCP transport share:
  :meth:`KVSegment.to_bytes` / :meth:`KVSegment.from_bytes` frame a
  little-endian payload in the page pools' dtype (float32, or the
  bfloat16 of a program served in it; the header says which) behind a
  JSON header (magic + version + fingerprint), so a decode replica in
  another process adopts exactly what the prefill replica exported.

**Fingerprint contract.**  ``config_fingerprint`` hashes the model
size dict, the page geometry (``page_tokens`` / ``max_seq_len``), the
parameter ``name`` prefix, and the weight seed.  Adoption REJECTS a
mismatched fingerprint (:class:`SegmentMismatch`) — a segment written
by different weights or a different page geometry would decode
garbage silently.  Engines sharing an externally-initialized scope
must be built from the same checkpoint for the seed term to be
honest (the fleet spawns every replica with the same ``--seed`` /
``--model-dir``).

:class:`DisaggPair` is the in-process orchestrator (bench A/B, tests,
and the single-host zero-copy deployment shape): one pump thread
chains ``prefill.submit() → transport.send() → decode.adopt()``
without ever blocking on an individual future, so handoffs overlap
with both engines' scheduling.  The fleet-scale version of the same
pipeline lives in the router (``serving/router.py``): affinity
routing picks prefill capacity for ``/generate``, ships the segment
to a decode replica's ``POST /adopt``, and pins the generation there.

Stats (README catalog): counters ``serving_segments_exported``,
``serving_segments_adopted``, ``serving_segment_export_bytes``,
``serving_segment_adopt_bytes``, ``serving_adopt_rejects``;
histograms ``serving_segment_export_ms``,
``serving_segment_adopt_ms``.
"""
from __future__ import annotations

import hashlib
import json
import struct
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from ..flags import flag_value
from .engine import OverloadedError, RequestFailed, ServingFuture
from .kv_cache import ITEMSIZE

__all__ = ["KVSegment", "SegmentMismatch", "SegmentTransport",
           "DeviceTransport", "HostBytesTransport", "DisaggPair",
           "config_fingerprint", "SEGMENT_VERSION", "SEGMENT_MAGIC"]

SEGMENT_VERSION = 1
SEGMENT_MAGIC = b"PTKVSEG1"
# HTTP content type for a serialized segment (the router recognizes a
# prefill replica's export reply by it)
SEGMENT_CONTENT_TYPE = "application/x-paddletpu-kvsegment"
# a page block's items on the wire, by the pools' dtype: numpy has no
# bfloat16 of its own, so those bytes travel as the uint16 they are
_WIRE = {"float32": "<f4", "bfloat16": "<u2"}


def _pages_dtype(pages) -> str:
    """``"float32"`` or ``"bfloat16"``: what a page block (numpy or jax)
    holds."""
    name = str(pages.dtype)
    if name not in _WIRE:
        raise ValueError(f"a KV segment carries float32 or bfloat16 pages, "
                         f"got {name}")
    return name


def _to_wire(pages, dtype: str) -> bytes:
    """A page block's bytes, little-endian C-order, bit for bit."""
    return np.ascontiguousarray(np.asarray(pages)).view(
        _WIRE[dtype]).tobytes()


def _from_wire(buf, dtype: str, count: int, offset: int, shape):
    """The block :func:`_to_wire` wrote, in its own dtype again."""
    arr = np.frombuffer(buf, _WIRE[dtype], count=count,
                        offset=offset).reshape(shape)
    if dtype == "float32":
        return arr
    import ml_dtypes

    return arr.view(ml_dtypes.bfloat16)


class SegmentMismatch(ValueError):
    """A segment whose fingerprint or page geometry does not match
    the adopting engine — adopting it would decode garbage."""


def config_fingerprint(model: dict, page_tokens: int, max_seq_len: int,
                       name: str, seed: int) -> str:
    """Deterministic fingerprint of everything that must agree between
    the exporting and adopting engines for a segment's K/V to mean
    the same thing: model sizes, page geometry, the parameter name
    prefix (scope identity), and the weight seed."""
    doc = {"model": {k: model[k] for k in sorted(model)},
           "page_tokens": int(page_tokens),
           "max_seq_len": int(max_seq_len),
           "name": str(name), "seed": int(seed),
           "version": SEGMENT_VERSION}
    blob = json.dumps(doc, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:24]


class KVSegment:
    """One sequence's populated KV pages, detached from any pool.

    ``layers`` — one ``(k_pages, v_pages)`` pair per model layer, each
    ``[n_pages, n_kv, page_tokens, D]`` of the exporting engine's pools'
    dtype (:attr:`dtype`) in LOGICAL page order (index j
    holds tokens ``[j*page_tokens, (j+1)*page_tokens)``); the physical
    page ids of the source pool are deliberately NOT part of the
    segment — the adopter scatters into whatever pages its own pool
    hands out.  ``tokens`` — every token generated so far (the
    prefill's first next-token at minimum); ``position`` — the logical
    sequence length already in the pages (== ``prompt_len`` for a
    fresh export).  Arrays may be numpy or jax (a
    :class:`DeviceTransport` keeps them on device)."""

    __slots__ = ("version", "fingerprint", "prompt_len", "position",
                 "tokens", "page_tokens", "layers", "logits",
                 "trace_id")

    def __init__(self, fingerprint: str, prompt_len: int, position: int,
                 tokens: Sequence[int], page_tokens: int,
                 layers: List[Tuple], logits=None,
                 trace_id: Optional[str] = None,
                 version: int = SEGMENT_VERSION):
        self.version = int(version)
        self.fingerprint = str(fingerprint)
        self.prompt_len = int(prompt_len)
        self.position = int(position)
        self.tokens = [int(t) for t in tokens]
        self.page_tokens = int(page_tokens)
        self.layers = layers
        self.logits = logits  # [n_tokens, V] float32, keep_logits only
        self.trace_id = trace_id

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def n_pages(self) -> int:
        return int(self.layers[0][0].shape[0]) if self.layers else 0

    @property
    def dtype(self) -> str:
        """``"float32"`` or ``"bfloat16"``: the page blocks' dtype."""
        return _pages_dtype(self.layers[0][0]) if self.layers else "float32"

    @property
    def nbytes(self) -> int:
        """Payload bytes (K/V page blocks + optional logits) — the
        number a transport actually moves."""
        item = ITEMSIZE[self.dtype]
        total = sum((int(np.prod(k.shape)) + int(np.prod(v.shape))) * item
                    for k, v in self.layers)
        if self.logits is not None:
            total += int(np.prod(np.asarray(self.logits).shape)) * 4
        return total

    # -- serialization (the host-bytes / cross-host path) -------------------
    def to_bytes(self) -> bytes:
        """``MAGIC | u32 header_len | header JSON | payload``: payload
        is every layer's K then V page block, little-endian C-order in
        the pools' dtype (the header's ``dtype``), then the optional
        logits block (float32).  Self-describing — :meth:`from_bytes`
        needs nothing but the buffer."""
        dtype = self.dtype
        n_pages, n_kv, pt, d = self.layers[0][0].shape
        logits = None if self.logits is None \
            else np.ascontiguousarray(np.asarray(self.logits, "<f4"))
        header = {
            "version": self.version, "fingerprint": self.fingerprint,
            "prompt_len": self.prompt_len, "position": self.position,
            "tokens": self.tokens, "page_tokens": self.page_tokens,
            "n_layers": self.n_layers, "n_pages": int(n_pages),
            "n_kv": int(n_kv), "head_dim": int(d), "dtype": dtype,
            "trace_id": self.trace_id,
            "logits_shape": list(logits.shape)
            if logits is not None else None,
        }
        hb = json.dumps(header, sort_keys=True).encode()
        parts = [SEGMENT_MAGIC, struct.pack("<I", len(hb)), hb]
        for k, v in self.layers:
            parts += [_to_wire(k, dtype), _to_wire(v, dtype)]
        if logits is not None:
            parts.append(logits.tobytes())
        return b"".join(parts)

    @classmethod
    def from_bytes(cls, buf: bytes) -> "KVSegment":
        if len(buf) < len(SEGMENT_MAGIC) + 4 \
                or buf[:len(SEGMENT_MAGIC)] != SEGMENT_MAGIC:
            raise ValueError("not a KV segment (bad magic)")
        off = len(SEGMENT_MAGIC)
        (hlen,) = struct.unpack_from("<I", buf, off)
        off += 4
        try:
            header = json.loads(buf[off:off + hlen])
        except ValueError as e:
            raise ValueError(f"corrupt KV segment header: {e}") from e
        off += hlen
        if header.get("version") != SEGMENT_VERSION:
            raise ValueError(f"unsupported KV segment version "
                             f"{header.get('version')} (this build "
                             f"speaks {SEGMENT_VERSION})")
        shape = (header["n_pages"], header["n_kv"],
                 header["page_tokens"], header["head_dim"])
        # (a segment written before the header named it is float32)
        dtype = header.get("dtype", "float32")
        if dtype not in _WIRE:
            raise ValueError(f"KV segment of unknown dtype {dtype!r}")
        items = int(np.prod(shape))
        block = items * ITEMSIZE[dtype]
        expect = off + header["n_layers"] * 2 * block
        if header.get("logits_shape"):
            expect += int(np.prod(header["logits_shape"])) * 4
        if expect != len(buf):
            raise ValueError(f"KV segment length mismatch: header "
                             f"promises {expect} bytes, got "
                             f"{len(buf)}")
        layers = []
        for _ in range(header["n_layers"]):
            k = _from_wire(buf, dtype, items, off, shape)
            off += block
            v = _from_wire(buf, dtype, items, off, shape)
            off += block
            layers.append((k, v))
        logits = None
        if header.get("logits_shape"):
            lshape = tuple(header["logits_shape"])
            n = int(np.prod(lshape))
            logits = np.frombuffer(buf, "<f4", count=n,
                                   offset=off).reshape(lshape)
        return cls(header["fingerprint"], header["prompt_len"],
                   header["position"], header["tokens"],
                   header["page_tokens"], layers, logits=logits,
                   trace_id=header.get("trace_id"),
                   version=header["version"])


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------

class SegmentTransport:
    """The handoff seam: ``send`` delivers a segment to wherever the
    adopting engine will read it from.  Implementations must preserve
    the payload bit-exactly (the pools' dtype in, the same bits out) —
    the round trip is part of the exactness contract the tests pin."""

    def send(self, segment: KVSegment) -> KVSegment:
        raise NotImplementedError


class DeviceTransport(SegmentTransport):
    """Single-host device-to-device handoff: every page block moves
    with ``jax.device_put`` onto ``device`` (a Device, a Sharding, or
    None for the adopter's default placement) — between two engines'
    sub-meshes this is the zero-host-copy path."""

    def __init__(self, device=None):
        self.device = device
        self.segments = 0
        self.bytes_moved = 0

    def send(self, segment: KVSegment) -> KVSegment:
        import jax

        layers = [(jax.device_put(np.asarray(k), self.device),
                   jax.device_put(np.asarray(v), self.device))
                  for k, v in segment.layers]
        self.segments += 1
        self.bytes_moved += segment.nbytes
        return KVSegment(segment.fingerprint, segment.prompt_len,
                         segment.position, segment.tokens,
                         segment.page_tokens, layers,
                         logits=segment.logits,
                         trace_id=segment.trace_id,
                         version=segment.version)


class HostBytesTransport(SegmentTransport):
    """Serialize → deserialize through the wire format — the same
    bytes ``POST /adopt`` carries, so an in-process test of this
    transport covers the cross-host codec end to end."""

    def __init__(self):
        self.segments = 0
        self.bytes_moved = 0

    def send(self, segment: KVSegment) -> KVSegment:
        buf = segment.to_bytes()
        self.segments += 1
        self.bytes_moved += len(buf)
        return KVSegment.from_bytes(buf)


def default_transport() -> SegmentTransport:
    """Transport selected by ``FLAGS_disagg_transport``: ``device``
    (zero-host-copy ``device_put``) or ``bytes`` (the serialization
    path — what a cross-host deployment pays)."""
    kind = str(flag_value("FLAGS_disagg_transport") or "device")
    if kind == "bytes":
        return HostBytesTransport()
    if kind == "device":
        return DeviceTransport()
    raise ValueError(f"FLAGS_disagg_transport={kind!r} (want 'device' "
                     f"or 'bytes')")


# ---------------------------------------------------------------------------
# in-process orchestrator
# ---------------------------------------------------------------------------

class DisaggPair:
    """Chain a prefill-role engine and a decode-role engine into one
    ``submit()`` surface (the single-host disaggregated deployment).

    One pump thread polls outstanding prefill futures; the moment one
    resolves, its segment rides ``transport.send`` into
    ``decode.adopt`` and the pump moves on — no blocking wait on any
    single future, so N handoffs overlap with both engines'
    scheduling.  Failures at any stage resolve the caller's future
    with the stage's error (prefill sheds stay
    :class:`OverloadedError`; adopt sheds likewise)."""

    def __init__(self, prefill, decode,
                 transport: Optional[SegmentTransport] = None):
        if getattr(prefill, "role", "both") != "prefill":
            raise ValueError("DisaggPair needs a prefill-role engine "
                             f"first (got role={prefill.role!r})")
        if getattr(decode, "role", "both") not in ("decode", "both"):
            raise ValueError("DisaggPair needs a decode-capable engine "
                             f"second (got role={decode.role!r})")
        if prefill.fingerprint() != decode.fingerprint():
            raise SegmentMismatch(
                "prefill/decode engine fingerprints differ "
                f"({prefill.fingerprint()} vs {decode.fingerprint()}) "
                "— segments would be rejected at adoption")
        self.prefill = prefill
        self.decode = decode
        self.transport = transport or default_transport()
        self._lock = threading.Lock()
        self._pending_prefill: List[tuple] = []
        self._pending_decode: List[tuple] = []
        self._n = {"handoffs": 0, "failures": 0}
        self._handoff_ms: List[float] = []
        self._closed = threading.Event()
        self._pump = threading.Thread(target=self._pump_loop,
                                      name="disagg-pump", daemon=True)
        self._pump.start()

    # -- API ----------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               trace_id: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               on_token=None, timeline: Optional[bool] = None
               ) -> ServingFuture:
        """Same contract as ``GenerationEngine.submit`` — the result
        is the decode engine's record (full token stream: the
        prefill's first token replayed, then every decoded one) plus
        ``handoff_ms`` / ``segment_bytes`` / the prefill hop's
        timings."""
        out = ServingFuture()
        pf = self.prefill.submit(prompt, max_new_tokens,
                                 trace_id=trace_id,
                                 deadline_ms=deadline_ms,
                                 timeline=timeline)
        with self._lock:
            self._pending_prefill.append(
                (pf, out, {"max_new_tokens": max_new_tokens,
                           "trace_id": trace_id,
                           "deadline_ms": deadline_ms,
                           "on_token": on_token, "timeline": timeline,
                           "t0": time.monotonic()}))
        return out

    def generate(self, prompt, max_new_tokens=None,
                 timeout: Optional[float] = None) -> dict:
        return self.submit(prompt, max_new_tokens).result(timeout)

    def stats(self) -> dict:
        with self._lock:
            n = dict(self._n)
            hand = list(self._handoff_ms)
        hand.sort()
        return {
            "handoffs": n["handoffs"],
            "handoff_failures": n["failures"],
            "handoff_ms_p50": hand[len(hand) // 2] if hand else None,
            "handoff_ms_max": hand[-1] if hand else None,
            "transport": type(self.transport).__name__,
            "transport_bytes": getattr(self.transport, "bytes_moved",
                                       None),
            "prefill": self.prefill.stats(),
            "decode": self.decode.stats(),
        }

    def close(self, drain: bool = True,
              timeout: Optional[float] = None):
        self.prefill.close(drain=drain, timeout=timeout)
        if drain:
            # every prefill future is resolved now; the pump must hand
            # the completed segments to the decode engine BEFORE it
            # starts draining, or the handoff tail would shed as
            # 'draining' despite drain=True
            deadline = None if timeout is None \
                else time.monotonic() + timeout
            while True:
                with self._lock:
                    if not self._pending_prefill:
                        break
                if deadline is not None and time.monotonic() > deadline:
                    break
                time.sleep(0.002)
        self.decode.close(drain=drain, timeout=timeout)
        self._closed.set()
        self._pump.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    # -- pump ---------------------------------------------------------------
    def _pump_loop(self):
        while True:
            moved = self._pump_once()
            with self._lock:
                idle = not (self._pending_prefill
                            or self._pending_decode)
            if self._closed.is_set() and idle:
                return
            if not moved:
                time.sleep(0.002)

    def _pump_once(self) -> bool:
        moved = False
        with self._lock:
            ready_p = [t for t in self._pending_prefill if t[0].done()]
            self._pending_prefill = [
                t for t in self._pending_prefill if not t[0].done()]
        for pf, out, params in ready_p:
            moved = True
            self._handoff(pf, out, params)
        with self._lock:
            ready_d = [t for t in self._pending_decode if t[0].done()]
            self._pending_decode = [
                t for t in self._pending_decode if not t[0].done()]
        for df, out, meta in ready_d:
            moved = True
            try:
                res = dict(df.result(0))
                res.update(meta)
                out._resolve(outputs=res)
            except Exception as e:  # noqa: BLE001 — relay the decode
                # stage's own taxonomy (OverloadedError/RequestFailed)
                with self._lock:
                    self._n["failures"] += 1
                out._resolve(error=e)
        return moved

    def _handoff(self, pf, out, params):
        t_h0 = time.monotonic()
        try:
            pres = pf.result(0)
            seg = pres["segment"]
            seg = self.transport.send(seg)
            df = self.decode.adopt(
                seg, max_new_tokens=params["max_new_tokens"],
                trace_id=pres.get("trace_id") or params["trace_id"],
                deadline_ms=self._remaining_ms(params),
                on_token=params["on_token"],
                timeline=params["timeline"])
        except Exception as e:  # noqa: BLE001 — prefill shed/failure or
            # adopt-time rejection: the caller gets the stage's error
            with self._lock:
                self._n["failures"] += 1
            out._resolve(error=e)
            return
        ms = (time.monotonic() - t_h0) * 1e3
        with self._lock:
            self._n["handoffs"] += 1
            self._handoff_ms.append(ms)
            if len(self._handoff_ms) > 4096:
                del self._handoff_ms[:2048]
        telemetry.histogram_observe("serving_segment_handoff_ms", ms,
                                    trace_id=pres.get("trace_id"))
        meta = {"handoff_ms": round(ms, 3),
                "segment_bytes": seg.nbytes,
                "prefill_ms": pres.get("prefill_ms"),
                "prefill_queue_wait_ms": pres.get("queue_wait_ms")}
        with self._lock:
            self._pending_decode.append((df, out, meta))

    @staticmethod
    def _remaining_ms(params) -> Optional[float]:
        if params["deadline_ms"] is None:
            return None
        spent = (time.monotonic() - params["t0"]) * 1e3
        return max(1.0, params["deadline_ms"] - spent)
