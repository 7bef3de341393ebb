"""Slot-based continuous batching for autoregressive decode.

The serving engine's FIFO head-run batching (``engine.py``) cannot
express generation: one request is not one forward but a *prefill*
(one causal pass over the prompt, O(P²)) followed by N *decode* steps
(one token each, O(1) with a KV cache).  Static batching strands a
finished sequence's batch slot until the whole batch drains — the two
dominant throughput losses Orca's iteration-level scheduling (Yu et
al., OSDI '22) and vLLM's KV-cache management (Kwon et al., SOSP '23)
identified.  This module is the repo's answer:

* **Fixed slot grid** — ``num_slots`` decode slots over one cache,
  :class:`~paddle_tpu.serving.kv_cache.KVCache` (``kv_cache.py``: page
  pools, block tables, slot state, the prefix index, and the one table
  of what each cache kind refuses).  The engine asks it for pages and
  block tables and names no pool; the decode program writes each slot's
  fresh K/V into the page its block table names and the executor
  *donates* the pool buffers, so every step updates the pools in place
  in HBM — no per-token cache copy, one compiled executable for the
  whole grid.
* **Prefill/decode split** — prompts compile against shape buckets
  (powers of two, like the one-shot batcher); decode steps run the
  whole slot grid every iteration.  Idle slots compute garbage rows
  that are row-independent from live ones (asserted bit-exact in
  ``tests/test_generation.py``).
* **Continuous batching** — a finished sequence (EOS / max tokens /
  max_seq_len) frees its slot *immediately*; the scheduler claims the
  next queued request into it between decode steps while the other
  slots keep generating.  ``continuous=False`` restores FIFO head-run
  static batching (claim only when every slot is idle, i.e. batch
  drain) — the baseline ``tests/test_generation.py`` compares against.
* **Paged attention** — on a TPU the decode step attends the live
  pages in place (``paged_decode_attention``); on the CPU
  ``kv_pool_gather`` rebuilds the slot's logical ``[n_kv, max_seq_len,
  D]`` view and ``cached_attention`` contracts over it.  The plain engine
  answers to the uncached forward (``tests/test_generation.py``,
  ``tests/test_paged_generation.py``).  A prompt whose page-aligned
  prefix the index holds skips that part of its prefill.
* **Chunked prefill** (``FLAGS_serving_prefill_chunk``) — long prompts
  feed in fixed-size slices, ONE slice per scheduler iteration
  interleaved with decode steps (SarathiServe-style), so a long prompt
  no longer stalls the whole grid's inter-token latency.  Slices go
  round-robin over the prefilling slots.  A prefix-hit
  tail prefill rides the same chunk program with ``base`` set past the
  shared pages.  A model with sliding-window layers (two page kinds)
  prefills in chunks too (PR 51): the chunk program takes both block
  tables, a window layer attends only the pages still inside its window,
  and behind each chunk the engine lets go of the window pages the next
  rows no longer admit (``kv_cache.py``).
  With chunking on a prompt may be as long as the cache; only the chunk
  needs a prefill rung.  A model whose attention layers are latent
  (MLA) prefills in chunks over its latent pages (PR 56): a chunk writes
  its ``[c_kv | k_r]`` rows as whole pages and attends the slot's cached
  rows expanded block by block (``latent_chunk_attention``); at 128 heads
  a single-shot rung wide enough for a long prompt would expand gigabytes
  of keys and values a layer, so chunks are the only way in.
* **Speculative decoding** (``FLAGS_serving_speculate``) — self-
  speculation over the slot's pages: a prompt-lookup drafter
  (:func:`ngram_draft` — longest n-gram suffix match over the
  sequence's OWN prompt+generated history, no second model) proposes
  up to ``FLAGS_serving_spec_tokens`` tokens per slot per scheduler
  iteration; one chunk-shaped verify program
  (``build_llama_verify``) scores ``[pending, draft...]`` against the
  slot's pages in a single prefill-shaped call, and the longest
  argmax-agreeing prefix plus the one bonus token is accepted —
  **bit-exact vs plain greedy decode** (tokens AND logits, tolerance
  0; the verify rows ARE the decode-step forward, batched).  Rejected
  draft tokens roll their provisionally-grown KV pages back
  (``KVCache.rollback_draft_pages``).  Slots
  with no usable draft, or ``submit(speculate=False)``, take the
  unchanged one-token grid step — mixed grids per iteration.
* **One decode step in flight** — the scheduler hands grid step n+1
  to the device before it fetches and books step n, so building feeds,
  booking tokens and publishing run while the device works.  Step n+1
  takes step n's tokens as the device holds them; its positions and
  pages are the booked ones plus the step in flight, and a sequence
  the host knows will end at step n (budget or cache one token from
  full) stays out of it.  A sequence that ends on EOS at the settle of
  step n has already ridden n+1: that row is discarded (a row is
  booked only if its slot still serves the request that rode; its K/V
  write lies past every committed position and a later owner of the
  page writes before it reads).  After a joiner (a finished prefill,
  an adopted segment), before a speculative round or a weight swap,
  and with no page left for the position ahead, the settle comes first
  and the step is built from the host's tokens.  Token streams are the
  same either way.
* **Admission control** — bounded queue reusing the serving
  :class:`~paddle_tpu.serving.engine.OverloadedError` semantics:
  ``queue_full`` at submit, ``deadline`` when a request outlives
  ``FLAGS_serving_deadline_ms`` before claiming a slot, ``draining``
  during shutdown.

Fault containment: a *prefill* failure (poisoned prompt —
``FLAGS_serving_poison_value`` sentinel token — injected ``prefill``
fault, or a real crash) fails exactly that request while the grid
keeps decoding; a *decode-step* failure fails the requests ACTIVE in
the grid (their cache state is unknowable after a mid-step crash) but
never the scheduler — the next queued request prefills into a clean
slot and serving continues (``decode_step`` fault-matrix tested).
``submit(deadline_ms=...)`` adopts the router-propagated remaining
budget like the one-shot engine: a spent budget sheds at the queue.

**Per-sequence timelines** — every request carries a trace-linked
timeline record (admit → claim → prefix-hit → prefill/chunk slices →
first token → each decode token → finish), returned on the result as
``timeline`` (relative-ms offsets) and kept in a bounded recent/slowest
store surfaced by :meth:`GenerationEngine.tracez` (the ``/tracez``
``generation`` block).  Two latency histograms derive from it, both
with trace-id exemplars: ``serving_ttft_ms`` (time to first token,
admission to the first generated token — queue wait, prefix mapping,
and every chunked-prefill slice *including the decode steps
interleaved between slices* all count, because that is what the user
waits) and ``serving_inter_token_ms`` (the gap between consecutive
generated tokens of one sequence — chunk-induced stalls on OTHER
sequences land here, which is exactly the SarathiServe trade the
chunk flag tunes).  A ``generation/sequence`` span brackets each
request under its trace id with the prefill/chunk/decode spans as
children, and per-slot occupancy transitions emit a Perfetto counter
track (``generation_slots`` via ``telemetry.counter_sample``).
``submit(on_token=...)`` registers a per-token callback ((token_id,
monotonic_ts), called on the scheduler thread, exceptions contained)
— the HTTP ``stream`` mode and the loadgen's client-side TTFT/ITL
measurement hang off it.  The HTTP streams' callback only appends to
the process's stream writer (``serving/streams.py``); where a booking
batch ends (a settled step, a prefill's first token, a speculative
round, an adoption's replay) the scheduler calls
``stream_writer.flush()``, which wakes the writer once if anything was
pushed.  All of it is admission-time gated: with
``FLAGS_telemetry=0`` and no callback, the per-token cost is zero
extra work.

Stats (README catalog): counters ``serving_generate_requests``,
``serving_generate_shed``, ``requests_shed_deadline``,
``serving_prefills``, ``serving_decode_steps``,
``serving_decode_steps_ahead`` (of those, dispatched before the step
before them was fetched), ``serving_decode_rows_discarded`` (rows of
such a step whose sequence had ended at the settle it overtook),
``serving_decode_failures`` (decode-grid iterations that raised —
each fails only the then-active requests),
``serving_generated_tokens``,
``serving_prefill_tokens``, ``serving_slot_reclaims``,
``serving_prefix_hits``, ``serving_prefix_tokens_saved``,
``serving_prefill_chunks``,
``serving_kv_pool_stalls``, ``serving_spec_drafts``,
``serving_spec_tokens_proposed``, ``serving_spec_tokens_accepted``,
``serving_spec_rollbacks``,
``serving_kv_window_pages_released_in_prefill`` (of the window pages let
go, those whose prompt was still coming in), ``moe_tokens_routed``,
``moe_tokens_dropped`` (must read 0), ``moe_pad_pairs_left_out`` (the
pairs of the rows a program holds beyond the tokens it was fed, a rung's
pad tail and a step's idle slots, which no expert multiplied), and for a
model whose expert
layers hold one chip's share of the router's experts (the layer
pattern's ``held``) ``moe_pairs_routed`` / ``moe_pairs_held`` (the
token-expert pairs the router placed over all its experts, and those
whose expert is held here and went through the matmuls; under
group-limited selection ``moe_rows_group_held``, the row-layers whose kept
groups include the one held here; where the router's last outputs are
identity experts, the pattern's ``zero_experts``, ``moe_pairs_zero``, the
pairs they took, which no chip multiplies), with a shared
expert ``moe_shared_expert_rows`` (row-layers it ran on),
``serving_block_passes_denoise`` / ``serving_block_passes_commit``
(block diffusion: slot-passes that decided positions / that only
committed a block's K/V), ``serving_block_tokens_committed``,
``serving_slot_state_writes`` (prefills that wrote a slot's state),
``serving_prefill_rows_run`` / ``serving_prefill_rows_skipped`` (a
whole-prompt prefill's rung rows that its dense products multiplied / that
lay in whole segments behind the prompt's end and were not:
``models/llama.py`` ``dense_rows_run``), ``serving_delta_state_steps``
(slot-layers whose delta state a decode step moved on),
``serving_ssm_state_steps`` (the same of a state-space layer's matrix),
``programs_built_float32`` / ``programs_built_bfloat16`` (the programs the
engine built, by the dtype they are declared in: ``dtype``, below);
gauges (the cache's are listed in ``kv_cache.py``)
``serving_spec_acceptance_rate``, ``serving_slot_occupancy``,
``moe_experts_touched``, ``moe_expert_load_max_over_mean``; histograms
``serving_generate_ms``, ``serving_prefill_ms``,
``serving_decode_step_ms``, ``serving_spec_verify_ms``,
``serving_ttft_ms``, ``serving_inter_token_ms``.
"""
from __future__ import annotations

import collections
import contextlib
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import blackbox, costmodel, fault, telemetry
from ..flags import flag_value
from ..models.llama import dense_rows_run
from ..monitor import stat_add
from ..ops.gated_delta_ops import CHUNK as DELTA_CHUNK
from ..ops.ssd_ops import CHUNK as SSD_CHUNK
from ..ops.latent_attention_ops import CHUNK_BLOCK_K
from . import batcher
from . import usage
from .engine import (OverloadedError, PoisonedInput, RequestFailed,
                     ServingFuture, poison_sentinel_matches)
from .kv_cache import KVCache, PoolExhausted, SlotPages
from .streams import stream_meter, stream_writer
from .sharded import describe_mesh as _describe_mesh

__all__ = ["GenerationEngine", "GenRequest", "ngram_draft"]

logger = logging.getLogger("paddle_tpu.serving.generation")

class GenRequest:
    """One queued generation request."""

    __slots__ = ("prompt", "max_new_tokens", "future", "t_submit",
                 "t_claimed", "t_deadline", "trace_id", "prefill_ms",
                 "on_token", "record_timeline", "events", "t_tokens",
                 "t_first", "t_last", "segment", "speculate", "bb",
                 "tenant", "keep_logits")

    def __init__(self, prompt: np.ndarray, max_new_tokens: int):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.segment = None  # adopted KVSegment (decode-role handoff)
        self.speculate = None  # per-request override (None = engine)
        # on an engine that keeps logits: False leaves this request's
        # (one row a token) off its record
        self.keep_logits = True
        self.future = ServingFuture()
        self.t_submit = time.monotonic()
        self.t_claimed: Optional[float] = None
        self.t_deadline: float = float("inf")  # set at admission
        self.trace_id: Optional[str] = None
        self.prefill_ms: float = 0.0
        # timeline machinery (admission-gated: record_timeline=False
        # and on_token=None keep the per-token path append-free)
        self.on_token = None          # callable(token_id, monotonic_ts)
        self.record_timeline = False
        self.events: List[tuple] = []  # (label, monotonic_ts, extra)
        self.t_tokens: List[float] = []  # per generated token
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        # flight-recorder last-words token (None when blackbox is off
        # or the in-flight cap is reached)
        self.bb: Optional[int] = None
        # usage-ledger tenant key (None with FLAGS_usage=0: the ledger
        # does zero per-request work, including this attribution)
        self.tenant: Optional[str] = None

    def note(self, label: str, ts: float, extra=None):
        if self.record_timeline:
            self.events.append((label, ts, extra))


def ngram_draft(history: np.ndarray, k: int, max_ngram: int) -> List[int]:
    """Prompt-lookup drafter: propose up to ``k`` tokens by matching
    the longest suffix n-gram of ``history`` (``max_ngram`` down to 1)
    against an earlier occurrence in ``history`` itself, and reading
    off the tokens that followed it — self-speculation, no second
    model (Saxena's prompt-lookup decoding / LLMA).  The LAST earlier
    occurrence wins (recent context predicts repetitive continuations
    best).  Returns ``[]`` on a miss; the caller falls back to the
    plain one-token grid step, so a bad draft costs a verify, never
    correctness — acceptance is gated on the verifier's argmax."""
    h = np.asarray(history).ravel()
    n = int(h.size)
    k = int(k)
    if k < 1 or n < 2:
        return []
    for g in range(min(int(max_ngram), n - 1), 0, -1):
        suffix = h[n - g:]
        # candidate start positions of earlier occurrences: the match
        # must END before the history's last token so at least one
        # follow-on token exists to propose
        for start in range(n - g - 1, -1, -1):
            if np.array_equal(h[start:start + g], suffix):
                follow = h[start + g:start + g + k]
                if follow.size:
                    return [int(t) for t in follow]
    return []


class _Slot(SlotPages):
    """Per-slot decode state: cache offset, step count, deadline (and,
    as :class:`SlotPages`, the pages it holds)."""

    __slots__ = ("idx", "req", "position", "steps", "tokens", "t_start",
                 "logits", "router_logits", "prefill_pos", "hit_tokens",
                 "chunk_counts", "decoding", "span", "blk_tokens",
                 "blk_masked", "blk_left", "blk_done", "blk_head", "passes")

    def __init__(self, idx: int):
        super().__init__()
        self.idx = idx
        self.req: Optional[GenRequest] = None
        self.span = None  # generation/sequence root (telemetry on)
        self.position = 0     # pre-step sequence length = cache offset
        self.steps = 0        # decode steps taken for this request
        self.tokens: List[int] = []
        self.t_start = 0.0
        self.logits: List[np.ndarray] = []  # keep_logits only
        self.router_logits: List[np.ndarray] = []  # keep_logits, experts
        self.prefill_pos = 0         # next position to prefill
        self.hit_tokens = 0          # tokens served by the index
        # the expert counts of the prompt's chunks before its last, as
        # the device holds them: [(handle, real rows)]
        self.chunk_counts: List[tuple] = []
        self.decoding = False        # prefill complete, in the grid
        # block diffusion: the block at ``position`` as the last booked
        # pass left it (the host's mirror of what the device carries),
        # how many of its positions are still undecided, how many
        # denoising passes it has had, and how many prompt tokens sit,
        # fixed, at its head (the first generated block only)
        self.blk_tokens: Optional[np.ndarray] = None
        self.blk_masked: Optional[np.ndarray] = None
        self.blk_left = 0
        self.blk_done = 0
        self.blk_head = 0
        self.passes: List[dict] = []     # keep_logits: every pass's record

    @property
    def active(self) -> bool:
        return self.req is not None


class DeviceAccount:
    """What the scheduler thread knows of the device without a profiler:
    a floor and a ceiling for the time the chip sat idle.

    The device runs what it is given in order, and every program the
    scheduler launches passes :meth:`launched`, every wait for one
    :meth:`fetch_begin` / :meth:`fetch_end`.  So a fetch that waited
    ends at the instant its program finished; a probe of the last
    program's output (``FetchHandle.ready``) that reads not ready says
    the chip is busy now; one that reads ready says it has been idle
    since some instant after the last at which it was seen busy.  At a
    launch the gap since the chip ran dry is therefore at least
    ``now - _t_done`` (``idle_known``: idle for certain since the last
    program was known to have finished) and at most ``now - _t_busy``
    (``idle_slack``: possibly idle since it was last seen busy); a
    launch behind a program still running books a gap of zero.  Fed on
    the scheduler thread only, and only while telemetry is on."""

    __slots__ = ("_clock", "_last", "_t_done", "_t_busy", "_t_probe",
                 "_fetching", "dispatches", "drained", "idle_known_s",
                 "idle_slack_s", "wait_s")

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        # first output of the last program launched while the device is
        # not known to have finished it; None: nothing outstanding
        self._last = None
        self._t_done: Optional[float] = None   # idle for certain since
        self._t_busy: Optional[float] = None   # last seen busy
        self._t_probe = 0.0                    # when it was last asked
        self._fetching = None     # (handle, ready) of the wait under way
        self.dispatches = 0
        self.drained = 0
        self.idle_known_s = 0.0
        self.idle_slack_s = 0.0
        self.wait_s = 0.0         # seconds blocked in fetches, in all

    def probe(self):
        """Ask, without waiting, whether the last program launched is
        still running, and note the answer with its time."""
        now = self._t_probe = self._clock()
        if self._last is None:
            return
        if self._last.ready():
            self._last, self._t_done = None, now
        else:
            self._t_busy = now

    def launched(self, handle) -> dict:
        """A program went to the device the moment after the last
        :meth:`probe` (the executor calls it as it hands the step
        over); ``handle`` is the program's first output.  Returns what
        the launch's span carries: ``drained`` and, when the chip had
        run dry, the gap's floor and ceiling in ms."""
        at = self._t_probe
        drained = self._last is None
        known = slack = 0.0
        if drained and self._t_done is not None:
            known = max(0.0, at - self._t_done)
            slack = max(known, at - self._t_busy)
        self._last, self._t_busy = handle, at
        self.dispatches += 1
        stat_add("serving_device_dispatches")
        if not drained:
            return {"drained": 0}
        self.drained += 1
        self.idle_known_s += known
        self.idle_slack_s += slack
        attrs = {"drained": 1, "idle_known_ms": round(known * 1e3, 3),
                 "idle_slack_ms": round(slack * 1e3, 3)}
        stat_add("serving_device_dispatches_drained")
        stat_add("serving_device_idle_known_ms", attrs["idle_known_ms"])
        stat_add("serving_device_idle_slack_ms", attrs["idle_slack_ms"])
        return attrs

    def fetch_begin(self, handle, at: float) -> int:
        """On entry of a wait for the program whose first output is
        ``handle``: 1 when it had finished already."""
        ready = int(handle.ready())
        self._fetching = (handle, ready)
        if ready and handle is self._last:
            self._last, self._t_done = None, at
        elif ready:
            self.probe()        # of the program launched behind it
        return ready

    def fetch_end(self, start: float, end: float):
        """The wait that :meth:`fetch_begin` opened is over.  One that
        found its program still running ended when the program did: the
        chip was busy until ``end``, with this program or, from then on,
        with the one behind it."""
        handle, ready = self._fetching
        self._fetching = None
        self.wait_s += end - start
        if ready:
            return
        self._t_busy = end
        if handle is self._last:
            self._last, self._t_done = None, end


class _StepInFlight:
    """A decode grid step the device has been handed and the host has
    not read yet.  ``riders`` are the ``(slot, request)`` pairs of its
    live rows: a row is booked at the settle only if its slot still
    serves that request.  A joiner (a sequence whose prefill was
    launched behind the step before and is read only after this step's
    dispatch, :class:`_Joiner`) is a rider like the others: its row is
    discarded if its first token ended it or its prefill failed.
    ``outs`` are the step's fetch handles by name, ``t0`` the moment
    the device could begin it (its feeds' start, or the settle of the
    step before when it was dispatched ahead of that), ``released`` the
    window pages its feeds let go."""

    __slots__ = ("riders", "outs", "links", "t0", "ahead", "released")

    def __init__(self, riders, outs, links, t0, ahead, released):
        self.riders = riders
        self.outs = outs
        self.links = links
        self.t0 = t0
        self.ahead = ahead
        self.released = released


class _Joiner:
    """A finished prefill the device has been handed and the host has
    not read yet, launched with a decode step in flight.  Its slot is
    in the grid already, at the position the prefill leaves it, with no
    token booked: the next step takes the slot's first input as the
    device holds it (``outs["next_token"]``; block diffusion: the first
    block as the host made it) and goes out before the scheduler blocks
    here.  ``n_rows``: real rows of the program that returned
    ``outs``, of the ``bucket`` it was built for."""

    __slots__ = ("slot", "req", "outs", "n_rows", "bucket")

    def __init__(self, slot, req, outs, n_rows, bucket):
        self.slot = slot
        self.req = req
        self.outs = outs
        self.n_rows = n_rows
        self.bucket = bucket


_JOIN_ROW = None


def _join_row(tokens, rows, idx):
    """``tokens``, what one grid step yields for the next on the device
    ([slots] greedy tokens; block diffusion: [slots, B] blocks), as the
    next step's feed [slots, W] with row ``idx`` taken from ``rows``
    instead: [slots, W] from the host (block diffusion: a joiner's
    first block), or the one token a prefill yielded, [1], as the
    device holds it.  One small jitted select, compiled at warm-up
    (``idx`` -1 takes no row); its result has the array type and width
    of the carried feed.  It does :meth:`_carried_tokens`' reshape
    itself, so a joiner's step costs one program and not two, and the
    reshape alone runs once a step WITHOUT a joiner: less often than
    the decode program, by which whoever reads a device trace (the
    benchmark's ``readers/module_time.py``) tells that program from the
    microsecond ones around it."""
    global _JOIN_ROW
    if _JOIN_ROW is None:
        import jax
        import jax.numpy as jnp

        def select(tokens, rows, idx):
            carried = tokens.reshape(tokens.shape[0], -1)
            rows = rows.astype(carried.dtype).reshape(
                -1, carried.shape[1])
            row = jnp.arange(carried.shape[0], dtype=idx.dtype)[:, None]
            return jnp.where(row == idx, rows, carried)

        _JOIN_ROW = jax.jit(select)
    return _JOIN_ROW(tokens, rows, np.int32(idx))


class GenerationEngine:
    """KV-cached generation over a fixed decode-slot grid.

    ``model``: dict of llama size kwargs (``vocab_size``, ``hidden``,
    ``num_layers``, ``num_heads``, ``num_kv_heads``, ``intermediate``;
    optionally ``head_dim``, ``rms_norm_eps``, ``rope_base``,
    ``layer_pattern``, ``qk_norm``).  ``block_diffusion={"block": B,
    "passes": T, "mask_id": id}`` in it makes a slot's unit of work a
    block of B positions: the prompt's whole blocks prefill under the
    block-causal mask, each later block takes up to T denoising passes
    (the static schedule: ``ceil(undecided / passes_left)`` positions
    fixed a pass, by confidence) and one commit pass, and its tokens
    are booked and streamed together at the commit.
    A ``layer_pattern`` with layers that keep slot state (``mixer``,
    ``models/llama.py``) gives those layers no pages but per-slot state
    (``kv_cache.py``): the prefill program writes the whole of its slot's
    state from the prompt's true last positions, so a reused slot needs
    no reset; the decode program moves the state of the rows that ride a
    step on by one, on the device, and leaves a dead row's alone.  What
    a block engine, slot state and window pages each refuse of
    ``prefix_reuse``, ``prefill_chunk``, ``speculate`` and the
    disaggregated roles is ``kv_cache.REFUSALS``.
    ``scope``: optional pre-initialized :class:`~paddle_tpu.framework.
    executor.Scope` whose weights use the same ``name`` prefix (the
    engine then shares them zero-copy); omitted, the engine seeds its
    own random weights (bench / loadgen).
    ``dtype``: what every program of the engine is declared in (the
    embedding table, every matrix, the residual stream, the page pools;
    ``models/llama.py`` ``build_llama_prefill``).  None: the engine's own
    rule on the model it is handed (``models/llama.py``
    ``serving_dtype``: bfloat16 where every layer is of a kind whose
    bfloat16 form exists, float32 otherwise); ``"float32"`` /
    ``"bfloat16"`` state it.  ``stats()["counters"]`` counts the programs
    built as ``programs_built_float32`` / ``programs_built_bfloat16``.

    In-process API: :meth:`submit` (future) / :meth:`generate`
    (blocking).  The HTTP front end exposes ``POST /generate`` over the
    same calls (:mod:`paddle_tpu.serving.server`).
    """

    def __init__(self, model: Dict, scope=None, *, num_slots=None,
                 max_seq_len=None, prefill_buckets=None, eos_id=-1,
                 max_new_tokens=None, queue_cap=None, deadline_ms=None,
                 continuous=True, autostart=True, name="llama",
                 attn_impl="auto", seed=0, keep_logits=False,
                 mesh=None, shard_rules=None, paged=None,
                 page_tokens=None, num_pages=None, prefill_chunk=None,
                 prefix_reuse=None, role=None, speculate=None,
                 spec_tokens=None, spec_ngram=None, num_window_pages=None,
                 dtype=None):
        import paddle_tpu as pt
        from ..compile_cache import ensure_compile_cache
        from ..models.llama import (build_llama_prefill, expert_layers,
                                    layer_spec, routed_ffn, serving_dtype)

        ensure_compile_cache()
        self.model = dict(model)
        self.dtype = str(dtype) if dtype is not None \
            else serving_dtype(self.model)
        if self.dtype not in ("float32", "bfloat16"):
            raise ValueError(f"dtype is None, 'float32' or 'bfloat16', got "
                             f"{dtype!r}")
        # what a builder is handed beyond the model: nothing where the
        # program is the float32 one the builders always built
        self._dtype_args = {} if self.dtype == "float32" \
            else {"dtype": self.dtype}
        # block diffusion (the class docstring): B positions a step, T
        # denoising passes a block; 0 = one token a step
        bd = self.model.pop("block_diffusion", None) or {}
        self._blk = int(bd.get("block", 0))
        self._blk_passes = int(bd.get("passes", 0))
        self._blk_mask_id = int(bd.get("mask_id", 0))
        self._rows = self._blk or 1        # positions a slot's step covers
        if bd and (self._blk < 2 or not 1 <= self._blk_passes <= self._blk
                   or not 0 <= self._blk_mask_id
                   < self.model["vocab_size"]):
            raise ValueError(
                f"block_diffusion needs block >= 2, 1 <= passes <= block "
                f"and a mask_id inside the vocabulary, got {bd}")
        self.name = name
        self.attn_impl = attn_impl
        self.continuous = bool(continuous)
        # keep_logits: fetch and retain every step's next-token logits
        # on the result record — the bit-exactness tests compare them
        # against the uncached full forward; costs one extra [slots, V]
        # fetch per step, so serve-path default is off.  A model with
        # routed experts also gets ``router_logits`` on the record, one
        # [L_moe, E] array per generated token: routing is discrete, and
        # a reference check needs them to tell a near tie from a fault
        self.keep_logits = bool(keep_logits)
        self.eos_id = int(eos_id)
        self.num_slots = int(num_slots if num_slots is not None
                             else flag_value("FLAGS_serving_decode_slots"))
        self.max_seq_len = int(
            max_seq_len if max_seq_len is not None
            else flag_value("FLAGS_serving_max_seq_len"))
        self.max_new_tokens = int(
            max_new_tokens if max_new_tokens is not None
            else flag_value("FLAGS_serving_max_new_tokens"))
        self.queue_cap = int(queue_cap if queue_cap is not None
                             else flag_value("FLAGS_serving_queue_cap"))
        dl = (deadline_ms if deadline_ms is not None
              else flag_value("FLAGS_serving_deadline_ms"))
        self._deadline_s = float(dl) / 1e3
        if prefill_buckets is None:
            spec = str(flag_value("FLAGS_serving_prefill_buckets") or "")
            prefill_buckets = [int(b) for b in spec.split(",") if b] \
                if spec else None
        self.prefill_buckets = batcher.prompt_buckets(
            self.max_seq_len, buckets=prefill_buckets)
        if self.num_slots < 1:
            raise ValueError("GenerationEngine needs at least one slot")
        if paged not in (None, True):
            raise ValueError("the dense KV cache was removed at PR 30")
        self.prefill_chunk = int(
            prefill_chunk if prefill_chunk is not None
            else flag_value("FLAGS_serving_prefill_chunk"))
        self.prefix_reuse = bool(
            prefix_reuse if prefix_reuse is not None
            else flag_value("FLAGS_serving_prefix_reuse"))
        # every layer's cache, by kind (``kv_cache.py``): the page
        # configuration (None keywords fall back to flags) and its
        # checks, the pools, the block tables, the slot state
        self.kv = kv = KVCache(
            self.model, name, num_slots=self.num_slots,
            max_seq_len=self.max_seq_len, page_tokens=page_tokens,
            num_pages=num_pages, num_window_pages=num_window_pages,
            prefill_chunk=self.prefill_chunk,
            prefix_reuse=self.prefix_reuse, count=self._count,
            dtype=self.dtype)
        # what the programs are built from and others read of it, set
        # once (``kv_live_bytes`` moves: a property)
        for attr in ("page_tokens", "pages_per_slot", "num_pages",
                     "window", "num_window_pages", "window_pages_per_slot",
                     "state_names", "kv_cache_bytes", "slot_state_bytes",
                     "page_bytes"):
            setattr(self, attr, getattr(kv, attr))

        # the per-layer pattern (models/llama.py DEFAULT_LAYER) beside
        # the cache's kinds: layers that keep slot state (a
        # convolution's rows, the delta rule's matrix), not pages
        specs = [layer_spec(self.model.get("layer_pattern"), i)
                 for i in range(self.model["num_layers"])]
        self._state_layers = kv.layers_of("slot_state")
        # ... those of them whose state is the delta rule's matrix, which
        # a prefill scans the prompt for in chunks
        self._delta_layers = [i for i in self._state_layers
                              if specs[i]["mixer"]["kind"] == "gated_delta"]
        # ... and those whose state is a state-space layer's matrix,
        # scanned likewise in chunks of their own length
        self._ssd_layers = [i for i in self._state_layers
                            if specs[i]["mixer"]["kind"] == "ssd"]
        if self._delta_layers and self._ssd_layers:
            raise ValueError("delta-rule and state-space layers in one "
                             "model are not built: a prefill's span "
                             "counts one scan's chunks")
        self._scan_chunk = DELTA_CHUNK if self._delta_layers \
            else SSD_CHUNK if self._ssd_layers else None
        self._window_layers = kv.layers_of("window_pages")
        # attention layers whose pages hold one latent row a token
        self._latent_layers = kv.layers_of("latent_pages")
        routed = [routed_ffn(specs[i]) for i in expert_layers(
            self.model.get("layer_pattern"), self.model["num_layers"])]
        self._moe_top_k = routed[0]["top_k"] if routed else 0
        # one chip's share of an expert-parallel group: the range of the
        # router's experts held here (None: all), and a shared expert
        self._moe_held = routed[0].get("held") if routed else None
        self._moe_shared = bool(routed and routed[0].get("shared_width"))
        # the router's last outputs that are identity experts (0: none)
        self._moe_zero = int(routed[0].get("zero_experts", 0)) if routed \
            else 0
        # group-limited selection: the router's groups (1: none)
        self._moe_groups = int(routed[0].get("n_group", 1)) if routed else 1
        self._build_fn_prefill = build_llama_prefill
        self._seed = seed
        # a prompt goes in whole, so the largest rung bounds it; in
        # chunks it goes in slices of at most the chunk, and only the
        # chunk needs a rung
        self.max_prompt_len = self.max_seq_len - 1 \
            if 0 < self.prefill_chunk <= self.prefill_buckets[-1] \
            else min(self.prefill_buckets[-1], self.max_seq_len - 1)
        # disaggregated serving role: "both" (colocated, the default)
        # runs prefill AND the decode grid; "prefill" exports each
        # prompt's populated pages as a KVSegment instead of decoding;
        # "decode" accepts segments via adopt() and never prefills
        self.role = str(role if role is not None
                        else flag_value("FLAGS_serving_role") or "both")
        if self.role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be both|prefill|decode, got "
                             f"{self.role!r}")
        # speculative decoding (self-speculation: the verify program
        # scores the draft against the slot's pages and the rollback
        # discipline IS page accounting)
        self.speculate = bool(flag_value("FLAGS_serving_speculate")
                              if speculate is None else speculate)
        self.spec_tokens = int(
            spec_tokens if spec_tokens is not None
            else flag_value("FLAGS_serving_spec_tokens"))
        self.spec_ngram = int(
            spec_ngram if spec_ngram is not None
            else flag_value("FLAGS_serving_spec_ngram"))
        if self.speculate:
            if self.spec_tokens < 1:
                raise ValueError(f"spec_tokens must be >= 1, got "
                                 f"{self.spec_tokens}")
            if self.spec_ngram < 1:
                raise ValueError(f"spec_ngram must be >= 1, got "
                                 f"{self.spec_ngram}")
        kv.check_features(block=self._blk, speculate=self.speculate,
                          role=self.role)
        self._fingerprint: Optional[str] = None
        self._chunk_progs: Dict[int, tuple] = {}
        self._verify_progs: Dict[int, tuple] = {}
        self._adopt_scatter = None  # donated jit, built on first adopt
        self._prefill_rr = 0  # chunked-prefill round-robin cursor

        # (before the first program is built: a build is counted)
        self._n = {"requests": 0, "shed": 0, "served": 0, "prefills": 0,
                   "decode_steps": 0, "decode_steps_ahead": 0,
                   "decode_joiners_ahead": 0,
                   "decode_rows_discarded": 0, "generated_tokens": 0,
                   "prefill_tokens": 0, "slot_reclaims": 0,
                   "failed": 0, "prefix_hits": 0,
                   "prefix_tokens_saved": 0, "prefill_chunks": 0,
                   "page_evictions": 0, "pool_stalls": 0,
                   "segments_exported": 0, "segments_adopted": 0,
                   "adopt_rejects": 0, "spec_drafts": 0,
                   "spec_tokens_proposed": 0,
                   "spec_tokens_accepted": 0, "spec_rollbacks": 0,
                   "window_pages_released": 0,
                   "window_pages_released_in_prefill": 0,
                   "moe_tokens_routed": 0, "moe_pad_pairs_left_out": 0,
                   "moe_tokens_dropped": 0, "block_passes_denoise": 0,
                   "block_passes_commit": 0, "block_tokens_committed": 0,
                   "prefill_rows_run": 0, "prefill_rows_skipped": 0,
                   "slot_state_writes": 0, "delta_state_steps": 0,
                   "ssm_state_steps": 0,
                   "moe_pairs_routed": 0, "moe_pairs_held": 0,
                   "moe_pairs_zero": 0,
                   "moe_rows_group_held": 0, "moe_shared_expert_rows": 0,
                   "programs_built_float32": 0, "programs_built_bfloat16": 0}
        self._n_lock = threading.Lock()

        # programs + executors: decode gets its own executor so its
        # compile-cache entry (and cost/memory manifest) is isolated —
        # cache_info()["entries"][0] IS the decode step
        self._prefill_exe = pt.Executor()
        self._decode_exe = pt.Executor()
        self._prefill_progs: Dict[int, tuple] = {}  # bucket -> (prog, fetches)
        self.scope = scope if scope is not None else pt.Scope()
        # mesh-partitioned decode: weights shard per `shard_rules`
        # (default serving_shard_rules — mp/ep last-dim splits) and the
        # KV page pools shard over mp on the kv-head dim.  The
        # executor needs no mesh plumbing: committed NamedSharding
        # placements on the scope arrays drive GSPMD at jit time, and
        # the donated cache buffers stay sharded in place across steps.
        self.mesh = mesh
        self._warm = False        # no :meth:`warmup` has finished yet
        self._build_decode(scope_ready=scope is not None)
        if mesh is not None:
            self._place_on_mesh(shard_rules)
        kv.allocate(self.scope, mesh)

        # scheduler state
        self._queue: collections.deque = collections.deque()
        self._cv = threading.Condition()
        self._slots = [_Slot(i) for i in range(self.num_slots)]
        self._draining = False
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        # in-place weight hot-swap: a validated swap is handed to the
        # scheduler thread here and commits at the next decode-grid-
        # step boundary (executors re-read the scope per call and the
        # cache vars are untouched, so in-flight KV pages and token
        # streams ride through the flip).  (arrays, Event, result box).
        self._pending_swap = None
        self.weights_version = 1

        # per-bucket manifest-flops cache for usage attribution: the
        # executor cache walk is paid once per bucket, not per dispatch
        self._usage_flops: Dict[int, int] = {}
        self._h_gen = telemetry.Histogram("serving_generate_ms")
        self._h_prefill = telemetry.Histogram("serving_prefill_ms")
        self._h_step = telemetry.Histogram("serving_decode_step_ms")
        self._h_verify = telemetry.Histogram("serving_spec_verify_ms")
        self._h_ttft = telemetry.Histogram("serving_ttft_ms")
        self._h_itl = telemetry.Histogram("serving_inter_token_ms")
        self._decode_rate_ema: Optional[float] = None
        # finished-sequence timeline store (the /tracez generation
        # block): recent ring + always-kept slowest-N tail, like the
        # one-shot engine's trace store
        self._timeline_lock = threading.Lock()
        self._timelines_recent: collections.deque = collections.deque(
            maxlen=max(1, int(flag_value("FLAGS_tracez_recent") or 32)))
        self._timelines_slow: List[dict] = []
        self._tail_keep = max(0, int(
            flag_value("FLAGS_trace_tail_keep") or 8))
        self._occ_vec: Optional[tuple] = None  # last slot-track sample
        # the scheduler thread's account of the device: gaps at every
        # launch, seconds blocked in fetches (_launch,
        # _begin_device_wait, _end_device_wait)
        self._account = DeviceAccount()
        self._on_launch = None    # the account's probe, during a launch
        # the stream handlers' totals as the last pass read them
        self._stream_seen = stream_meter.totals()
        self._released_in_feeds = 0  # window pages the last feeds freed
        # the one decode grid step dispatched and not yet fetched
        self._inflight: Optional[_StepInFlight] = None
        # the one finished prefill launched behind it and not yet read
        self._joining: Optional[_Joiner] = None

        if autostart:
            self.start()

    # -- build --------------------------------------------------------------
    def _build_decode(self, scope_ready: bool):
        import paddle_tpu as pt
        from ..models.llama import build_llama_decode

        main, startup = pt.Program(), pt.Program()
        startup._is_startup = True
        startup.random_seed = main.random_seed = self._seed
        with pt.program_guard(main, startup):
            feeds, fetches, cache_names = build_llama_decode(
                self.num_slots, self.max_seq_len, name=self.name,
                num_pages=self.num_pages, page_tokens=self.page_tokens,
                num_window_pages=self.num_window_pages or None,
                keep_router_logits=self.keep_logits, **self._blk_args(),
                **self._dtype_args, **self.model)
        self._built()
        self._decode_prog = main
        self._decode_feeds = feeds
        self._decode_fetches = fetches
        self.cache_names = cache_names
        if not scope_ready:
            # engine-owned weights: the decode program references every
            # parameter, so one startup run initializes the full set
            self._prefill_exe.run(startup, scope=self.scope)

    def _built(self):
        """One more program built in the engine's dtype."""
        self._count(f"programs_built_{self.dtype}")

    def _blk_args(self, prefill: bool = False) -> dict:
        """What block diffusion adds to a program builder's arguments;
        nothing for a model of one token a step."""
        if not self._blk:
            return {}
        if prefill:
            return {"mask_block": self._blk}
        return {"block": self._blk, "mask_id": self._blk_mask_id}

    def _place_on_mesh(self, shard_rules):
        """Shard every decode-program weight onto the mesh — once,
        before the caches exist (the caches get their own kv-head
        placement in ``KVCache.allocate``).  The prefill programs read
        the same scope, so one placement covers both paths
        (:func:`~paddle_tpu.serving.sharded.place_block_state`)."""
        from .sharded import place_block_state, serving_shard_rules

        self._shard_rules = shard_rules or serving_shard_rules(self.mesh)
        place_block_state(self._decode_prog.global_block(),
                          self._decode_feeds, self.scope, self.mesh,
                          self._shard_rules, skip=self.cache_names)

    @property
    def kv_live_bytes(self) -> int:
        return self.kv.kv_live_bytes

    def _fetch_names(self, fetches) -> List[str]:
        """The fetches every run of a program takes, warm-up included
        (another list would be another compilation): the greedy token,
        what the expert layers counted, and with ``keep_logits`` the
        logits and the router's."""
        # a block-diffusion pass yields the block as it stands; its
        # prefill yields nothing but K/V
        names = ["next_token"] if not self._blk \
            else [n for n in ("tokens", "masked", "rows_written")
                  if n in fetches]
        names += [n for n in ("expert_counts", "expert_group_rows")
                  if n in fetches]
        if self.keep_logits:
            # (a block-diffusion prefill has router logits and no row's)
            names += [n for n in ("logits", "router_logits")
                      if n in fetches]
        return names

    def _run_fetching(self, exe, prog, fetches, feed) -> dict:
        names = self._fetch_names(fetches)
        outs = exe.run(prog, feed=feed,
                       fetch_list=[fetches[n] for n in names],
                       scope=self.scope, return_numpy=False,
                       on_launch=self._on_launch)
        return dict(zip(names, outs))

    def _launch(self, span_name: str, run, parent=None, **attrs) -> dict:
        """Hand one program to the device under a span named
        ``span_name``: ``run()`` makes the executor call (with
        ``on_launch=self._on_launch``) and returns the program's fetch
        handles by name, unread.  Every program the scheduler launches
        goes through here, so the span also says whether the chip had
        run dry by then (:class:`DeviceAccount`); warm-up runs the same
        programs from its caller's thread and books nothing."""
        span = telemetry.span_begin(span_name, parent=parent, **attrs)
        acct = self._account if self._on_scheduler(span) else None
        try:
            if acct is not None:
                acct.probe()
                self._on_launch = acct.probe
            outs = run()
            if acct is not None:
                span.attrs.update(acct.launched(next(iter(outs.values()))))
        finally:
            self._on_launch = None
            telemetry.span_end(span)
        return outs

    def _prefill_prog_for(self, bucket: int):
        """Whole-prompt prefill: the causal forward over the prompt with
        each layer's K/V scattered into the slot's pages."""
        import paddle_tpu as pt

        entry = self._prefill_progs.get(bucket)
        if entry is None:
            main, startup = pt.Program(), pt.Program()
            startup._is_startup = True
            startup.random_seed = main.random_seed = self._seed
            with pt.program_guard(main, startup):
                _feeds, fetches = self._build_fn_prefill(
                    1, bucket, name=self.name, attn_impl=self.attn_impl,
                    cache_slots=self.num_slots,
                    max_seq_len=self.max_seq_len,
                    num_pages=self.num_pages,
                    page_tokens=self.page_tokens,
                    num_window_pages=self.num_window_pages or None,
                    keep_router_logits=self.keep_logits,
                    **self._blk_args(prefill=True), **self._dtype_args,
                    **self.model)
            self._built()
            entry = self._prefill_progs[bucket] = (main, fetches)
        return entry

    def _chunk_prog_for(self, bucket: int):
        """Prefill-continuation program (chunked prefill / prefix-hit
        tail): ``bucket`` new tokens attend the slot's pages plus
        themselves causally."""
        import paddle_tpu as pt
        from ..models.llama import build_llama_prefill_chunk

        entry = self._chunk_progs.get(bucket)
        if entry is None:
            main, startup = pt.Program(), pt.Program()
            startup._is_startup = True
            startup.random_seed = main.random_seed = self._seed
            # every chunk the engine sends starts at a page boundary (a
            # multiple of the chunk, or a prefix hit's whole pages):
            # where chunk and rung are whole pages the K/V go in page by
            # page
            aligned = bucket % self.page_tokens == 0 \
                and self.prefill_chunk % self.page_tokens == 0
            with pt.program_guard(main, startup):
                _feeds, fetches, _names = build_llama_prefill_chunk(
                    bucket, self.max_seq_len, self.num_pages,
                    self.page_tokens, name=self.name,
                    num_window_pages=self.num_window_pages or None,
                    page_aligned=aligned,
                    keep_router_logits=self.keep_logits,
                    **self._dtype_args, **self.model)
            self._built()
            entry = self._chunk_progs[bucket] = (main, fetches)
        return entry

    def _chunk_feed(self, ids: np.ndarray, base: int, n: int,
                    slot: Optional[_Slot]) -> dict:
        """The chunk program's feeds for ``n`` real rows ``ids`` at
        ``base`` of ``slot``'s pages (None: a warm-up's, every write to
        the trash page)."""
        return {"chunk_ids": ids[None],
                "base": np.asarray([base], "int32"),
                "chunk_len": np.asarray([n], "int32"),
                "last_off": np.asarray([max(n - 1, 0)], "int64"),
                **self.kv.table_feeds(slot)}

    def _chunk_buckets(self) -> List[int]:
        """Prefill-bucket lengths the chunk program can be asked for:
        with chunking on, every slice (prefix-hit tails included) is at
        most the chunk size, so only buckets up to its own are needed;
        chunking off, a prefix-hit tail can be any prefill bucket."""
        if self.prefill_chunk > 0:
            cap = batcher.prompt_bucket_for(
                min(self.prefill_chunk, self.max_prompt_len),
                self.prefill_buckets)
            return [b for b in self.prefill_buckets if b <= cap]
        return list(self.prefill_buckets)

    def _verify_prog_for(self, bucket: int):
        """Speculative-verify program: the chunk forward fetching
        EVERY row's argmax + logits (``build_llama_verify``) — one
        call scores a whole draft against the slot's pages."""
        import paddle_tpu as pt
        from ..models.llama import build_llama_verify

        entry = self._verify_progs.get(bucket)
        if entry is None:
            main, startup = pt.Program(), pt.Program()
            startup._is_startup = True
            startup.random_seed = main.random_seed = self._seed
            with pt.program_guard(main, startup):
                _feeds, fetches, _names = build_llama_verify(
                    bucket, self.max_seq_len, self.num_pages,
                    self.page_tokens, name=self.name, **self._dtype_args,
                    **self.model)
            self._built()
            entry = self._verify_progs[bucket] = (main, fetches)
        return entry

    def _verify_buckets(self) -> List[int]:
        """Bucket lengths the verify program can be asked for: the
        chunk is ``[pending, draft...]`` — at most ``spec_tokens + 1``
        rows — so only buckets up to that length's own bucket compile
        (with the default K=4, exactly one: bucket 8)."""
        cap = batcher.prompt_bucket_for(
            min(self.spec_tokens + 1, self.max_prompt_len),
            self.prefill_buckets)
        return [b for b in self.prefill_buckets if b <= cap]

    def warmup(self) -> int:
        """Compile every prefill bucket + the decode step now (off the
        request path).  Returns the number of programs compiled.
        Warmup dispatches run with all-zero block tables and zero
        valid lengths, so every write lands on the trash page (and a
        prefill's slot state on the trash row)."""
        if self._warm:
            # every program is warm: nothing of start-up is left to time
            return self._warm_programs()
        with telemetry.startup_span("startup/warmup") as span:
            compiled = span.attrs["programs"] = self._warm_programs()
        self._warm = True
        return compiled

    def _warming(self, kind: str, bucket=None):
        """One program of :meth:`warmup`, from its Python construction to
        its first run: a ``startup/warm_program`` span whose self time is
        that first run and its fetch.  What the program compiles under it
        copies ``kind`` and ``bucket`` (``compile_cache.py``)."""
        if self._warm:
            return contextlib.nullcontext()
        return telemetry.startup_span("startup/warm_program", kind=kind,
                                      bucket=bucket)

    def _warm_programs(self) -> int:
        compiled = 0
        first = None    # the last prefill's first token, on the device
        if self.role == "decode":
            # a decode-role engine never prefills: the decode step
            # (plus the verify program when speculating) is all it runs
            compiled = 0
            if self.speculate:
                compiled += self._warm_verify()
            with self._warming("decode"):
                self._warm_decode()
            return compiled + 1
        if self.prefill_chunk <= 0:
            for b in self.prefill_buckets:
                if b in self._prefill_progs:
                    continue
                with self._warming("prefill", b):
                    prog, fetches = self._prefill_prog_for(b)
                    feed = {"input_ids": np.zeros((1, b), "int64"),
                            "prompt_len": np.zeros((1,), "int32"),
                            **self.kv.table_feeds(None)}
                    if not self._blk:      # (a block prefill yields no row)
                        feed["last_pos"] = np.zeros((1,), "int64")
                    if self.state_names:   # the trash row, no slot's state
                        feed["slot"] = np.asarray([self.num_slots], "int32")
                    first = self._run_fetching(
                        self._prefill_exe, prog, fetches,
                        feed).get("next_token")
                compiled += 1
        if self.prefill_chunk > 0 or self.prefix_reuse:
            for b in self._chunk_buckets():
                if b in self._chunk_progs:
                    continue
                with self._warming("chunk", b):
                    prog, fetches = self._chunk_prog_for(b)
                    # (the fetches every run takes: another list would
                    # be another compilation, inside the window)
                    first = self._run_fetching(
                        self._prefill_exe, prog, fetches, self._chunk_feed(
                            np.zeros((b,), "int64"), 0, 0,
                            None))["next_token"]
                compiled += 1
        if self.role == "prefill":
            # a prefill-role engine never runs the decode grid
            return compiled
        if self.speculate:
            compiled += self._warm_verify()
        with self._warming("decode"):
            self._warm_decode(first.value if first is not None else None)
        return compiled + 1

    def _warm_verify(self) -> int:
        """Run every verify rung not yet built, once; how many."""
        compiled = 0
        for b in self._verify_buckets():
            if b in self._verify_progs:
                continue
            with self._warming("verify", b):
                prog, fetches = self._verify_prog_for(b)
                self._prefill_exe.run(
                    prog,
                    feed={"chunk_ids": np.zeros((1, b), "int64"),
                          "base": np.zeros((1,), "int32"),
                          "block_table": np.zeros(
                              (1, self.pages_per_slot), "int32"),
                          "chunk_len": np.zeros((1,), "int32")},
                    fetch_list=[fetches["tokens"]],
                    scope=self.scope, return_numpy=False)
            compiled += 1
        return compiled

    def _warm_decode(self, first=None):
        """Three grid steps over idle rows: the decode program, then the
        same program fed the first one's tokens as the device holds
        them, which compiles the reshape that carries a step's tokens
        into the step dispatched ahead of its settle, then the same
        again through :func:`_join_row`, which compiles
        the select that puts a joiner's row among them.  ``first``: a
        prefill's ``next_token`` as the device holds it (the warm-up's
        last; a decode-role engine has none and no such joiner)."""
        positions = np.zeros((self.num_slots,), "int32")
        host = np.zeros((self.num_slots, self._rows), "int32")
        idle = self._host_tokens(host)
        zeros = np.zeros((self.num_slots,), "int32")
        block = {"masked": idle, "quota": zeros, "fresh": zeros} \
            if self._blk else None

        def ahead(outs, rows):
            # (row -1: no slot's)
            tokens = self._carried_tokens(outs, rows)
            if self._blk:
                block["masked"] = outs["masked"].value if rows is None \
                    else _join_row(outs["masked"].value, rows, -1)
            return self._dispatch_decode(tokens, positions, block=block)

        outs = ahead(self._dispatch_decode(idle, positions, block=block),
                     None)
        rows = host if self._blk else first
        if rows is not None:
            outs = ahead(outs, rows)
        self._fetch_decode(outs)

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop_guarded,
                                            name="generation-scheduler",
                                            daemon=True)
            self._thread.start()

    def drain(self, timeout: Optional[float] = None):
        with self._cv:
            self._draining = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)

    def close(self, drain: bool = True, timeout: Optional[float] = None):
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._draining = True
            shed = []
            if not drain:
                shed, self._queue = list(self._queue), collections.deque()
            self._cv.notify_all()
        for req in shed:
            self._shed(req, "draining")
        if self._thread is not None:
            self._thread.join(timeout)
        with self._n_lock:
            served, shed_n = self._n["served"], self._n["shed"]
        telemetry.log_event("generation_drained",
                            served=served, shed=shed_n)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    # -- admission ----------------------------------------------------------
    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               trace_id: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               on_token=None,
               timeline: Optional[bool] = None,
               speculate: Optional[bool] = None,
               tenant: Optional[str] = None,
               keep_logits: bool = True) -> ServingFuture:
        """Admit one generation request.  ``prompt``: 1-D int token ids
        (1 ≤ len ≤ the largest prefill bucket).  Returns a future whose
        ``result()`` is ``{"tokens", "prompt_len", "steps", "finish",
        "trace_id", "queue_wait_ms", "prefill_ms", "ttft_ms",
        "total_ms", "timeline"?}``.
        A budget larger than the cache capacity left after the prompt
        is honored until the slot's cache fills, finishing
        ``"cache_full"`` (vs ``"length"`` for a genuinely met budget).
        Sheds with :class:`OverloadedError` (``queue_full`` /
        ``draining`` / ``deadline`` — ``deadline_ms`` is the request's
        REMAINING end-to-end budget, router-propagated; a spent budget
        sheds right here instead of claiming a decode slot).

        ``on_token`` — optional per-token callback ``(token_id,
        monotonic_ts)`` invoked on the scheduler thread the moment
        each token is booked (the streaming/TTFT hook); it must be
        fast and never raise (exceptions are contained and logged, the
        sequence keeps generating).  ``timeline`` — force the
        per-sequence timeline record on/off; default follows
        ``FLAGS_telemetry`` (off ⇒ zero per-token bookkeeping).
        ``speculate`` — per-request speculative-decoding override:
        ``False`` opts this sequence out of drafting (it rides the
        plain grid step even on a speculating engine — bit-exact
        either way, this knob only trades verify compute); ``True``
        or ``None`` follow the engine's ``speculate`` setting.
        ``keep_logits`` — on an engine built with ``keep_logits``,
        ``False`` keeps this request's logits and router logits off its
        record (a check that compares three slots of a full grid would
        otherwise hold every slot's rows on the host)."""
        if self.role == "decode":
            raise ValueError("decode-role engine accepts KV segments "
                             "via adopt(), not prompts (role=decode)")
        ids = np.asarray(prompt)
        if ids.ndim != 1 or ids.size < 1:
            raise ValueError(f"prompt must be a non-empty 1-D token id "
                             f"sequence, got shape {ids.shape}")
        if not np.issubdtype(ids.dtype, np.integer):
            raise ValueError(f"prompt must be integer token ids, got "
                             f"dtype {ids.dtype}")
        if ids.size > self.max_prompt_len:
            raise ValueError(
                f"prompt of {ids.size} tokens exceeds max prompt length "
                f"{self.max_prompt_len} (largest prefill bucket, with "
                f"one decode slot of max_seq_len={self.max_seq_len} "
                f"reserved)")
        mnt = max(1, int(max_new_tokens if max_new_tokens is not None
                         else self.max_new_tokens))
        req = GenRequest(ids.astype("int64"), mnt)
        req.speculate = speculate
        req.keep_logits = bool(keep_logits)
        budget_s = self._deadline_s
        if deadline_ms is not None:
            budget_s = min(budget_s, float(deadline_ms) / 1e3)
        req.t_deadline = req.t_submit + budget_s
        if telemetry.enabled():
            # an externally-minted id (the router hop's trace header)
            # wins: one generated sequence is one trace across tiers
            req.trace_id = trace_id or telemetry.new_trace_id()
        req.on_token = on_token
        req.record_timeline = bool(telemetry.enabled()
                                   if timeline is None else timeline)
        req.note("admit", req.t_submit)
        if usage.enabled():
            req.tenant = usage.normalize_tenant(tenant)
            # last words carry the tenant: a crash names its victim
            # traffic in the flight recorder
            req.bb = blackbox.request_begin(req.trace_id, "generate",
                                            prompt_len=int(ids.size),
                                            tenant=req.tenant)
        else:
            req.bb = blackbox.request_begin(req.trace_id, "generate",
                                            prompt_len=int(ids.size))
        self._count("requests")
        stat_add("serving_generate_requests")
        if req.tenant is not None:
            # booked at the SAME site as the global counters above:
            # per-tenant sums stay equal to them at tolerance 0
            usage.ledger().book(req.tenant, requests=1,
                                tokens_in=int(ids.size))
        with self._cv:
            if self._draining:
                raise self._shed_err(req, "draining")
            if budget_s <= 0:
                raise self._shed_err(req, "deadline",
                                     "budget exhausted upstream")
            if len(self._queue) >= self.queue_cap:
                raise self._shed_err(
                    req, "queue_full",
                    f"{len(self._queue)}/{self.queue_cap} queued")
            self._queue.append(req)
            self._cv.notify_all()
        return req.future

    def generate(self, prompt, max_new_tokens=None,
                 timeout: Optional[float] = None) -> dict:
        """Blocking one-shot: ``submit(...).result(timeout)``."""
        return self.submit(prompt, max_new_tokens).result(timeout)

    # -- in-place weight hot-swap -------------------------------------------
    def _weight_names(self) -> List[str]:
        """The swap surface: every scope array that is NOT a KV cache
        (the cache/pool vars carry live sequence state and must ride
        through a swap untouched)."""
        caches = set(self.cache_names) | set(self.state_names)
        return [n for n in self.scope.local_var_names()
                if n not in caches]

    def swap_weights(self, checkpoint, *,
                     timeout_s: Optional[float] = None) -> dict:
        """Hot-swap the decode/prefill weights in place at a
        decode-grid-step boundary.

        Validates the checkpoint (dir or ``{name: array}`` dict)
        against the live weight structure on THIS thread — shape /
        dtype / missing-name drift raises
        :class:`~paddle_tpu.inference.SwapMismatch` before anything
        flips — then hands the commit to the scheduler thread, which
        applies it between grid steps: the executors re-read the scope
        every call and the cache vars are untouched, so in-flight
        sequences keep their KV pages and token streams and simply
        decode the next token under the new weights.  A failed commit
        rolls back to the old arrays.  Bounded by
        ``FLAGS_swap_timeout_s``."""
        from ..inference import (SwapMismatch, _weight_doc,
                                 weights_structure_fingerprint)
        if timeout_s is None:
            timeout_s = float(flag_value("FLAGS_swap_timeout_s") or 30.0)
        if isinstance(checkpoint, dict):
            new = dict(checkpoint)
        else:
            path = os.path.join(str(checkpoint), "__params__")
            if not os.path.exists(path):
                raise SwapMismatch(
                    f"swap checkpoint {str(checkpoint)!r} has no "
                    f"__params__")
            from .. import io
            new = io._read(path)
        names = self._weight_names()
        live_doc = _weight_doc(
            (n, self.scope.find_var(n)) for n in names)
        new_doc = _weight_doc(
            (n, new[n]) for n in names if n in new)
        problems = []
        for n in names:
            if n not in new:
                problems.append(f"{n}: missing from checkpoint")
            elif new_doc[n] != live_doc[n]:
                problems.append(f"{n}: checkpoint {new_doc[n]} != "
                                f"live {live_doc[n]}")
        if problems:
            raise SwapMismatch(
                f"checkpoint structure "
                f"{weights_structure_fingerprint(new_doc)} != live "
                f"{weights_structure_fingerprint(live_doc)}: "
                + "; ".join(problems[:4]))
        arrays = {n: new[n] for n in names}
        if self._thread is None:
            # no scheduler running (tests, pre-start): commit inline —
            # every instant is a grid-step boundary
            return self._commit_swap(arrays)
        ev = threading.Event()
        box: Dict[str, object] = {}
        with self._cv:
            if self._draining or self._closed:
                raise SwapMismatch("no weight swap during drain")
            if self._pending_swap is not None:
                raise SwapMismatch("another weight swap is mid-flight")
            self._pending_swap = (arrays, ev, box)
            self._cv.notify_all()
        if not ev.wait(timeout_s):
            raise SwapMismatch(
                f"swap not committed within {timeout_s}s "
                f"(scheduler never reached a grid-step boundary)")
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _apply_pending_swap(self):
        """Scheduler-thread half: commit the handed-off swap at the
        grid-step boundary and wake the caller."""
        with self._cv:
            pending = self._pending_swap
        if pending is None:
            return
        arrays, ev, box = pending
        try:
            # the step in flight ran under the old weights: book it
            # before anything flips
            self._settle_inflight()
        except Exception as e:  # noqa: BLE001 — a decode-grid crash,
            # contained as in _iteration; the swap itself goes ahead
            self._decode_failed(e)
        try:
            box["result"] = self._commit_swap(arrays)
        except BaseException as e:  # noqa: BLE001 — hand the caller
            # the failure; the scheduler itself must keep decoding
            box["error"] = e
        finally:
            with self._cv:
                self._pending_swap = None
            ev.set()

    def _commit_swap(self, arrays: Dict[str, np.ndarray]) -> dict:
        """Flip every weight array in the scope (validated upstream),
        re-placing per the mesh sharding rules when mesh-partitioned.
        Atomic: any failure — including an injected ``weight_swap``
        fault — restores every already-flipped array before
        re-raising."""
        import jax

        t0 = time.monotonic()
        old_vals: Dict[str, object] = {}
        try:
            for n in sorted(arrays):
                kind = fault.fire("weight_swap")
                fault.maybe_delay(kind)
                if kind == "fail":
                    raise fault.InjectedFault(
                        "injected weight_swap failure")
                old_vals[n] = self.scope.find_var(n)
                v = arrays[n]
                if self.mesh is not None:
                    from jax.sharding import NamedSharding
                    sh = NamedSharding(
                        self.mesh,
                        self._shard_rules.spec(n, np.shape(v)))
                    self.scope.set_var(n, jax.device_put(v, sh))
                else:
                    self.scope.set_var(n, jax.device_put(v))
        except BaseException:
            for n, v in old_vals.items():
                self.scope.set_var(n, v)
            stat_add("serving_weight_swap_failures")
            raise
        self._prev_weights = old_vals
        self.weights_version += 1
        stat_add("serving_weight_swaps")
        ms = round((time.monotonic() - t0) * 1e3, 3)
        telemetry.log_event("generation_weight_swap",
                            version=self.weights_version, swap_ms=ms,
                            replaced=len(arrays))
        return {"weights_version": self.weights_version,
                "swap_ms": ms, "replaced": len(arrays)}

    def revert_weights(self) -> dict:
        """Restore the weights replaced by the last successful swap
        (retained device arrays — no checkpoint round-trip)."""
        from ..inference import SwapMismatch
        prev = getattr(self, "_prev_weights", None)
        if not prev:
            raise SwapMismatch("no previous weights retained "
                               "(nothing swapped yet)")
        return self.swap_weights(prev)

    # -- disaggregated handoff (KV segments) --------------------------------
    def fingerprint(self) -> str:
        """The segment-compatibility fingerprint (model sizes, page
        geometry, name prefix, weight seed) — equal fingerprints mean
        a segment exported here adopts bit-exactly there."""
        if self._fingerprint is None:
            from .disagg import config_fingerprint
            # (the programs' dtype with the model's sizes, where it is
            # not float32: pages of another dtype mean something else)
            self._fingerprint = config_fingerprint(
                dict(self.model, **self._dtype_args), self.page_tokens,
                self.max_seq_len, self.name, self._seed)
        return self._fingerprint

    def _check_segment(self, seg):
        """Structural + fingerprint admission check for adopt(); a
        reject here means decoding the segment could only produce
        garbage (wrong weights, wrong page geometry, truncated
        payload)."""
        from .disagg import SegmentMismatch
        if seg.fingerprint != self.fingerprint():
            self._count("adopt_rejects")
            stat_add("serving_adopt_rejects")
            raise SegmentMismatch(
                f"segment fingerprint {seg.fingerprint} != engine "
                f"{self.fingerprint()} (model/page-geometry/seed "
                f"drift)")
        n_layers = len(self.cache_names) // 2
        needed = -(-seg.position // self.page_tokens)
        if (seg.page_tokens != self.page_tokens
                or seg.n_layers != n_layers
                or seg.n_pages != needed
                or not seg.tokens
                or seg.position < 1
                or seg.position > self.max_seq_len
                # prompt_len feeds a host allocation and the result
                # record — a crafted header must not OOM the replica
                or seg.prompt_len < 1
                or seg.prompt_len > seg.position):
            self._count("adopt_rejects")
            stat_add("serving_adopt_rejects")
            raise SegmentMismatch(
                f"segment structure invalid: page_tokens="
                f"{seg.page_tokens}/{self.page_tokens}, layers="
                f"{seg.n_layers}/{n_layers}, pages={seg.n_pages} "
                f"(need {needed} for position {seg.position}), "
                f"tokens={len(seg.tokens)}")

    def adopt(self, segment, max_new_tokens: Optional[int] = None,
              trace_id: Optional[str] = None,
              deadline_ms: Optional[float] = None,
              on_token=None,
              timeline: Optional[bool] = None,
              tenant: Optional[str] = None) -> ServingFuture:
        """Adopt an exported :class:`~paddle_tpu.serving.disagg.
        KVSegment` into this engine's page pool and decode it to
        completion — the decode half of the disaggregated pipeline.

        Admission mirrors :meth:`submit` (queue cap / draining /
        deadline shedding with the same taxonomy); page allocation at
        claim time is refcount-integrated with the local pool and, on
        exhaustion, evicts idle prefix pages or requeues exactly like
        a local prefill.  A fingerprint or structure mismatch raises
        :class:`~paddle_tpu.serving.disagg.SegmentMismatch`
        immediately (never queued).  The result record's ``tokens``
        is the FULL stream — the segment's already-generated tokens
        (replayed through ``on_token`` so a streaming client sees one
        uninterrupted sequence) followed by everything decoded
        here."""
        if self.role == "prefill":
            raise ValueError("prefill-role engine cannot adopt "
                             "segments (it has no decode grid)")
        if self._blk:
            raise ValueError("a block-diffusion engine cannot adopt "
                             "segments: a segment is a causal prefix and "
                             "one pending token")
        self._check_segment(segment)
        mnt = max(1, int(max_new_tokens if max_new_tokens is not None
                         else self.max_new_tokens))
        # the dummy prompt only carries the length for result
        # accounting — the segment's pages already hold the K/V
        req = GenRequest(np.zeros((segment.prompt_len,), "int64"), mnt)
        req.segment = segment
        budget_s = self._deadline_s
        if deadline_ms is not None:
            budget_s = min(budget_s, float(deadline_ms) / 1e3)
        req.t_deadline = req.t_submit + budget_s
        if telemetry.enabled():
            req.trace_id = (trace_id or segment.trace_id
                            or telemetry.new_trace_id())
        req.on_token = on_token
        req.record_timeline = bool(telemetry.enabled()
                                   if timeline is None else timeline)
        req.note("admit", req.t_submit, {"adopted": True})
        if usage.enabled():
            req.tenant = usage.normalize_tenant(tenant)
            req.bb = blackbox.request_begin(
                req.trace_id, "adopt",
                prompt_len=int(segment.prompt_len), tenant=req.tenant)
        else:
            req.bb = blackbox.request_begin(
                req.trace_id, "adopt",
                prompt_len=int(segment.prompt_len))
        self._count("requests")
        stat_add("serving_generate_requests")
        if req.tenant is not None:
            # tokens_in stays on the prefill tier (it already booked
            # the prompt); the decode tier books the request + its
            # decode-side cost under the SAME propagated tenant
            usage.ledger().book(req.tenant, requests=1)
        with self._cv:
            if self._draining:
                raise self._shed_err(req, "draining")
            if budget_s <= 0:
                raise self._shed_err(req, "deadline",
                                     "budget exhausted upstream")
            if len(self._queue) >= self.queue_cap:
                raise self._shed_err(
                    req, "queue_full",
                    f"{len(self._queue)}/{self.queue_cap} queued")
            self._queue.append(req)
            self._cv.notify_all()
        return req.future

    def _shed_err(self, req: GenRequest, reason: str,
                  detail: str = "") -> OverloadedError:
        blackbox.request_end(req.bb)
        self._count("shed")
        stat_add("serving_generate_shed")
        if req.tenant is not None:
            usage.ledger().book(req.tenant, sheds=1)
        if reason == "deadline":
            stat_add("requests_shed_deadline")
        err = OverloadedError(reason, detail)
        err.trace_id = req.trace_id
        return err

    def _shed(self, req: GenRequest, reason: str):
        req.future._resolve(error=self._shed_err(req, reason))

    # -- scheduler ----------------------------------------------------------
    def _count(self, key: str, n: int = 1):
        with self._n_lock:
            self._n[key] += n

    def _active(self) -> List[_Slot]:
        return [s for s in self._slots if s.active]

    def _can_claim_locked(self) -> bool:
        """Continuous batching claims a free slot the moment one
        exists; static (FIFO head-run) batching only claims into a
        fully drained grid — the Orca-motivated difference under
        test."""
        if self.continuous:
            return any(not s.active for s in self._slots)
        return all(not s.active for s in self._slots)

    def _claim_locked(self) -> List[tuple]:
        claimed = []
        if not self._can_claim_locked():
            return claimed
        now = time.monotonic()
        busy_before = sum(1 for s in self._slots if s.active)
        for slot in self._slots:
            if slot.active or not self._queue:
                continue
            req = None
            while self._queue:
                cand = self._queue.popleft()
                if now > cand.t_deadline:
                    self._shed(cand, "deadline")
                    continue
                req = cand
                break
            if req is None:
                break
            req.t_claimed = now
            req.note("claim", now, {"slot": slot.idx})
            if req.bb is not None:
                blackbox.request_phase(req.bb, "prefill",
                                       slot=slot.idx)
            slot.req = req
            slot.position = 0
            slot.steps = 0
            slot.tokens = []
            slot.t_start = now
            slot.pages = []
            slot.wpages = []
            slot.prefill_pos = 0
            slot.hit_tokens = 0
            slot.chunk_counts = []
            slot.decoding = False
            slot.span = None
            claimed.append((slot, req))
            if busy_before:
                # the continuous-batching event: a new sequence enters
                # a grid other sequences are still decoding in
                self._count("slot_reclaims")
                stat_add("serving_slot_reclaims")
        return claimed

    def _decoding_slots(self) -> List[_Slot]:
        return [s for s in self._slots if s.active and s.decoding]

    def _prefilling_slots(self) -> List[_Slot]:
        return [s for s in self._slots if s.active and not s.decoding]

    def _loop_guarded(self):
        # per-request failures resolve futures inside _loop; an
        # exception escaping the scheduler loop itself kills every
        # in-flight sequence at once — dump the flight recorder
        # before the thread dies (then re-raise into excepthook)
        try:
            self._loop()
        except BaseException as e:
            blackbox.dump_exception("generation_scheduler", e)
            raise

    def _loop(self):
        while True:
            # decode-grid-step boundary: the previous iteration's
            # decode step fully committed, the next has not started —
            # the one safe instant to flip weights under live slots
            # (the apply reads the handoff box under _cv and returns
            # immediately when no swap is pending)
            self._apply_pending_swap()
            with self._cv:
                waited = None
                while True:
                    if self._queue and self._can_claim_locked():
                        break
                    if self._active():
                        break
                    if self._pending_swap is not None:
                        break  # an idle grid must still commit swaps
                    if self._draining and not self._queue:
                        telemetry.span_end(waited)
                        return
                    if waited is None:
                        waited = telemetry.span_begin(
                            "generation/wait_work",
                            queued=len(self._queue))
                    self._cv.wait(0.02)
                telemetry.span_end(waited)
                # one iteration = one trace of its own (the stack is
                # empty here); every phase below hangs under it on
                # this thread's stack
                it = telemetry.span_begin("generation/iteration", cpu=True)
                claim = telemetry.span_begin("generation/claim")
                claimed = self._claim_locked()
                queued = len(self._queue)
            active = None
            if it is not None:
                waited0 = self._account.wait_s
            try:
                active = self._iteration(claimed, claim)
            finally:
                if it is not None:
                    # what the stream writer took of the interpreter
                    # since the last pass ended
                    seen, self._stream_seen = \
                        self._stream_seen, stream_meter.totals()
                    it.attrs.update(
                        active=active, claimed=len(claimed), queued=queued,
                        stream_write_ms=round(
                            (self._stream_seen[0] - seen[0]) * 1e3, 3),
                        stream_cpu_ms=round(
                            (self._stream_seen[1] - seen[1]) * 1e3, 3))
                    # unwinds whatever phase a raise left open
                    telemetry.span_end(it)
                    telemetry.histogram_observe(
                        "serving_iteration_host_ms",
                        (it.end - it.start
                         - (self._account.wait_s - waited0)) * 1e3)

    def _on_scheduler(self, span) -> bool:
        """Whether ``span`` (None with telemetry off) was opened by the
        scheduler thread: only its launches and waits feed the device
        account; warm-up and a set-up check run the same programs from
        their caller's thread."""
        return span is not None \
            and threading.current_thread() is self._thread

    def _begin_device_wait(self, name: str, outs: dict, **attrs):
        """Open a span that blocks on the device for the program that
        returned ``outs``; ``ready`` on it says whether the program had
        already finished."""
        span = telemetry.span_begin(name, **attrs)
        if self._on_scheduler(span):
            span.attrs["ready"] = self._account.fetch_begin(
                next(iter(outs.values())), span.start)
        return span

    def _end_device_wait(self, span):
        """Close a span that blocked on the device: its time stays out
        of the iteration's host time (``serving_iteration_host_ms``),
        and where it waited its end is the instant the program
        finished."""
        telemetry.span_end(span)
        if self._on_scheduler(span):
            self._account.fetch_end(span.start, span.end)

    def _iteration(self, claimed: List[tuple], claim) -> int:
        """One scheduler pass after the claim: admit the claimed
        requests, advance one prefill slice, speculate, step the decode
        grid, publish.  ``claim`` is the open ``generation/claim``
        span (None with telemetry off).  Returns the slots still
        active at its end."""
        for slot, req in claimed:
            try:
                self._begin(slot, req)
            except PoolExhausted as e:
                # segment adoption allocates its pages at claim
                # time: exhaustion is the SAME transient the
                # prefill path sees — evictions already ran, so
                # requeue behind live sequences (or fail when the
                # pool can never hold it)
                self._requeue_or_fail(slot, e)
            except Exception as e:  # noqa: BLE001 — a prefill/adopt
                # failure must not kill the scheduler: exactly this
                # request errors, the grid keeps decoding
                self._fail_request(slot, req,
                                   "adopt" if req.segment is not None
                                   else "prefill", e)
        if claim is not None:
            claim.attrs["claimed"] = len(claimed)
            telemetry.span_end(claim)
        if claimed:
            with telemetry.trace_span("generation/publish"):
                self._sample_slot_track()
        # advance ONE pending slice per iteration, so a long prompt
        # pays out between decode steps instead of stalling the grid.
        # Chunked: round-robin over the prefilling slots.  Unchunked a
        # slice is a whole prompt, so slots claimed together prefill
        # first come, first served
        pending = self._prefilling_slots()
        if pending:
            if self.prefill_chunk > 0:
                slot = pending[self._prefill_rr % len(pending)]
                self._prefill_rr += 1
            else:
                slot = min(pending, key=lambda s: s.req.t_submit)
            try:
                self._prefill_advance(slot)
            except PoolExhausted as e:
                # transient saturation, not a broken request: live
                # sequences will free pages as they finish, so put
                # the request back at the queue head (its own
                # deadline still bounds the wait).  Only a pool
                # that cannot serve the prompt even with every
                # other slot idle is a hard failure
                self._requeue_or_fail(slot, e)
            except Exception as e:  # noqa: BLE001 — a prefill
                # failure fails this request only
                self._fail_request(slot, slot.req, "prefill", e)
        # speculative round first: slots whose draft verified this
        # iteration already advanced (often several tokens) and are
        # skipped by the grid step; the rest ride it unchanged —
        # mixed grids per iteration
        served = frozenset()
        if self.speculate and self._decoding_slots():
            try:
                # a draft continues the booked history
                self._settle_inflight()
                served = self._speculate_round()
            except Exception as e:  # noqa: BLE001 — a verify crash
                # is a decode-grid crash: it donated the same pool
                # buffers, so the active slots' cache state is
                # unknowable (same containment as the grid step)
                self._decode_failed(e)
        if self._decoding_slots():
            try:
                self._decode_step(skip=served)
            except Exception as e:  # noqa: BLE001 — a decode-step
                # failure fails the ACTIVE requests (after a
                # mid-step crash their cache state is unknowable)
                # but never the scheduler: the next queued request
                # prefills into a clean slot and serving continues
                self._decode_failed(e)
        with telemetry.trace_span("generation/publish"):
            return self._publish_gauges()

    def _begin(self, slot: _Slot, req: GenRequest):
        """Post-claim admission work: poison/fault checks + the
        prefix-index mapping only — the prompt itself pays out via
        :meth:`_prefill_advance` (one slice per scheduler iteration)."""
        # the per-sequence timeline span: trace-linked root bracketing
        # claim→finish under the request's trace id, the prefill /
        # chunk / decode spans hang under it
        queue_wait_ms = (req.t_claimed - req.t_submit) * 1e3
        # (a root of the REQUEST's trace, not a child of the scheduler's
        # generation/claim that happens to be open on this thread: a
        # parent context with no span says exactly that)
        slot.span = telemetry.span_begin(
            "generation/sequence", detached=True,
            parent=telemetry.SpanContext(req.trace_id, None),
            slot=slot.idx,
            prompt_len=int(req.prompt.size),
            adopted=req.segment is not None,
            queue_wait_ms=round(queue_wait_ms, 3))
        telemetry.histogram_observe("serving_generate_queue_wait_ms",
                                    queue_wait_ms, trace_id=req.trace_id)
        # page-second attribution arms here (None keeps every mark a
        # single attribute check — the FLAGS_usage=0 zero-work path)
        slot.page_tenant = req.tenant
        if req.segment is not None:
            self._adopt_begin(slot, req)
            return
        kind = fault.fire("prefill")
        fault.maybe_delay(kind)
        if kind == "fail":
            raise fault.InjectedFault("injected prefill failure")
        # poison fails the request BEFORE any page is mapped or
        # registered: a poisoned prompt sharing a cached prefix never
        # touches (or evicts) the pages other slots still reference
        self._poison_check(req.prompt)
        slot.hit_tokens = self.kv.map_prefix(slot, req.prompt)
        if slot.hit_tokens:
            req.note("prefix_hit", time.monotonic(),
                     {"tokens": slot.hit_tokens})
            self._count("prefix_hits")
            stat_add("serving_prefix_hits")
            if req.tenant is not None:
                usage.ledger().book(req.tenant, prefix_hits=1)
            self._count("prefix_tokens_saved", slot.hit_tokens)
            stat_add("serving_prefix_tokens_saved", slot.hit_tokens)
        slot.prefill_pos = slot.hit_tokens

    def _adopt_begin(self, slot: _Slot, req: GenRequest):
        """Materialize an adopted segment into this pool: allocate the
        pages (refcounted; eviction/requeue semantics identical to a
        local prefill via ``KVCache.ensure_pages``), scatter the
        segment's page blocks into them, replay the already-generated
        tokens, and enter the decode grid at the recorded position.
        Raises :class:`PoolExhausted` for the scheduler's requeue
        path."""
        import jax.numpy as jnp

        seg = req.segment
        t0 = time.monotonic()
        kind = fault.fire("adopt")
        fault.maybe_delay(kind)
        if kind == "fail":
            raise fault.InjectedFault("injected adopt failure")
        if self._adopt_scatter is None:
            import jax
            # donated scatter: the pool buffer is consumed and updated
            # IN PLACE (same contract as the decode step's donation) —
            # adoption cost scales with the segment, not the pool.
            # One compile per distinct segment page count, bounded by
            # pages_per_slot
            self._adopt_scatter = jax.jit(
                lambda pool, idx, rows: pool.at[idx].set(rows),
                donate_argnums=(0,))
        with telemetry.trace_span("generation/segment_adopt",
                                  parent=slot.span.context()
                                  if slot.span is not None else None,
                                  position=seg.position,
                                  pages=seg.n_pages,
                                  bytes=seg.nbytes, slot=slot.idx):
            self.kv.ensure_pages(slot, seg.position)  # may raise
            phys = jnp.asarray(
                np.asarray(slot.pages[:seg.n_pages], "int32"))
            for i, (k_pages, v_pages) in enumerate(seg.layers):
                for kind_, arr in (("k", k_pages), ("v", v_pages)):
                    name = f"{self.name}.pool_{kind_}_{i}"
                    pool = self.scope.find_var(name)
                    pool = self._adopt_scatter(
                        pool, phys,
                        jnp.asarray(np.asarray(arr), pool.dtype))
                    self.scope.set_var(name, pool)
        slot.position = seg.position
        slot.prefill_pos = seg.position
        slot.tokens = list(seg.tokens)
        slot.steps = 0
        slot.logits = [np.asarray(r) for r in np.asarray(seg.logits)] \
            if (self.keep_logits and seg.logits is not None) else []
        slot.decoding = True
        if req.bb is not None:
            blackbox.request_phase(req.bb, "decoding")
        now = time.monotonic()
        ms = (now - t0) * 1e3
        self._count("segments_adopted")
        stat_add("serving_segments_adopted")
        stat_add("serving_segment_adopt_bytes", seg.nbytes)
        telemetry.histogram_observe("serving_segment_adopt_ms", ms,
                                    trace_id=req.trace_id)
        req.note("adopt", now, {"tokens": len(seg.tokens),
                                "position": seg.position,
                                "bytes": seg.nbytes,
                                "ms": round(ms, 3)})
        # replay the remotely generated tokens: the stream consumer
        # sees one uninterrupted sequence, and TTFT here honestly
        # measures adopt-admission to first token availability
        tele = telemetry.enabled()
        for tok in seg.tokens:
            if req.record_timeline:
                req.t_tokens.append(now)
            if req.t_first is None:
                req.t_first = now
                if tele:
                    ttft = (now - req.t_submit) * 1e3
                    self._h_ttft.observe(ttft, trace_id=req.trace_id)
                    telemetry.histogram_observe(
                        "serving_ttft_ms", ttft, trace_id=req.trace_id)
            if req.on_token is not None:
                try:
                    req.on_token(tok, now)
                except Exception as e:  # noqa: BLE001 — same containment
                    # contract as _book_token's replay
                    logger.warning("on_token callback failed (token "
                                   "dropped from stream): %s", e)
                    req.on_token = None
        stream_writer.flush()
        req.t_last = now
        self.kv.publish_gauges()
        # a segment can arrive already finished (EOS at prefill, or a
        # budget the replay alone meets) — same precedence as
        # _book_token: eos > length > cache_full
        last = slot.tokens[-1]
        if last == self.eos_id:
            self._finish(slot, "eos")
        elif len(slot.tokens) >= req.max_new_tokens:
            self._finish(slot, "length")
        elif slot.position >= self.max_seq_len:
            self._finish(slot, "cache_full")

    def _end_seq_span(self, slot: _Slot, outcome: str):
        """Close the slot's generation/sequence span (safe when none —
        telemetry off or pre-claim failure)."""
        if slot.span is not None:
            slot.span.attrs["outcome"] = outcome
            if slot.req is not None:
                slot.span.attrs["steps"] = slot.steps
            telemetry.span_end(slot.span)
            slot.span = None

    def _requeue_or_fail(self, slot: _Slot, e: Exception):
        """Pool exhausted mid-prefill.  With other sequences live the
        condition is transient — release this slot's pages and put the
        request back at the QUEUE HEAD (fairness preserved; its
        deadline still sheds it if starvation persists).  With the
        grid otherwise empty the pool simply cannot hold the prompt:
        fail it, a retry can never succeed."""
        req = slot.req
        others = [s for s in self._slots if s.active and s is not slot]
        if not others:
            self._fail_request(slot, req,
                               "adopt" if req.segment is not None
                               else "prefill", e)
            return
        self._count("pool_stalls")
        stat_add("serving_kv_pool_stalls")
        logger.debug("kv pool exhausted mid-prefill; requeueing "
                     "request (%d live slots hold the pages)",
                     len(others))
        self._end_seq_span(slot, "requeued")
        req.note("requeue", time.monotonic())
        self.kv.release_pages(slot)
        slot.req = None
        slot.decoding = False
        slot.logits = []
        self._sample_slot_track()
        with self._cv:
            self._queue.appendleft(req)
            self._cv.notify_all()

    def _fail_request(self, slot: _Slot, req: GenRequest, phase: str,
                      e: Exception):
        self._count("failed")
        if req.tenant is not None:
            usage.ledger().book(req.tenant, failures=1)
        logger.warning("%s failed: %s", phase, e)
        self._end_seq_span(slot, f"failed:{phase}")
        self.kv.release_pages(slot)
        blackbox.request_end(req.bb)
        req.future._resolve(error=RequestFailed(
            f"{phase} failed: {type(e).__name__}: {e}"))
        slot.req = None
        slot.decoding = False
        slot.logits = []
        self._sample_slot_track()

    def _decode_failed(self, e: Exception):
        # fail EVERY active slot, mid-prefill ones included: the step
        # donated the same cache (or page-pool) buffers a concurrent
        # chunked prefill writes into, so after a mid-step crash no
        # slot's cache state is knowable
        active = self._active()
        # its rows die with their requests, the unread prefill's too
        self._inflight = self._joining = None
        self._count("failed", len(active))
        stat_add("serving_decode_failures")
        logger.warning("decode step failed; failing %d active "
                       "request(s): %s", len(active), e)
        telemetry.log_event("serving_decode_failure",
                            active=len(active),
                            error=f"{type(e).__name__}: {e}")
        err = RequestFailed(f"decode step failed: "
                            f"{type(e).__name__}: {e}")
        for s in active:
            self._end_seq_span(s, "failed:decode_step")
            req, s.req, s.logits = s.req, None, []
            s.decoding = False
            if req.tenant is not None:
                usage.ledger().book(req.tenant, failures=1)
            self.kv.release_pages(s)
            blackbox.request_end(req.bb)
            req.future._resolve(error=err)
        self._sample_slot_track()
        # the crashed step donated the pool buffers, so every indexed
        # page's K/V is as unknowable as the slots' — a later prefix hit
        # must not serve possibly-corrupt rows
        dropped = self.kv.flush_prefix()
        if dropped:
            logger.warning("flushed %d prefix-index entries after "
                           "decode-step failure", dropped)

    # -- prefill ------------------------------------------------------------
    def _poison_check(self, prompt: np.ndarray):
        """The generation half of the poison-input model: a prompt
        carrying the ``FLAGS_serving_poison_value`` sentinel token
        crashes its prefill — exactly that request fails (prefill
        isolation), the grid keeps decoding."""
        pv = flag_value("FLAGS_serving_poison_value")
        if not pv:
            return
        if poison_sentinel_matches(prompt, float(pv)):
            raise PoisonedInput(
                f"prompt contains poisoned token (sentinel {pv})")

    # -- usage flops pricing ------------------------------------------------
    def _exe_flops(self, bucket: int) -> int:
        """Manifest flops of the prefill-side executable at ``bucket``
        (the padded prompt/chunk/verify feed is ``(1, bucket)``) — 0
        when the backend exposes no cost analysis (CPU test backends).
        Memoized per bucket: the executor cache walk is paid once."""
        fl = self._usage_flops.get(bucket)
        if fl is not None:
            return fl
        fl = 0
        try:
            probe = f"(1, {int(bucket)})"
            for e in self._prefill_exe.cache_info()["entries"]:
                man = e.get("manifest")
                if man and probe in str(e.get("signature") or ""):
                    fl = int(man.get("flops") or 0)
                    break
        except Exception:  # noqa: BLE001 — attribution must never
            # fail a dispatch; an unpriceable executable books 0 flops
            return 0
        self._usage_flops[bucket] = fl
        return fl

    def _decode_flops(self) -> int:
        """Manifest flops of one decode grid step (0 when absent)."""
        fl = self._usage_flops.get(-1)
        if fl is not None:
            return fl
        man = self.decode_manifest()
        if not man:
            return 0
        fl = int(man.get("flops") or 0)
        self._usage_flops[-1] = fl
        return fl

    # -- prefill slices -----------------------------------------------------
    def _prefill_advance(self, slot: _Slot):
        """One prefill slice for one slot: either the whole prompt
        through the full-prefill program (chunking off, no prefix
        hit), or the next ``prefill_chunk`` tokens (or the whole
        prefix-hit tail) through the chunk program.  The final slice
        yields the first generated token and flips the slot into the
        decode grid."""
        req = slot.req
        prompt = req.prompt
        t0 = time.monotonic()
        n_prompt = int(prompt.size)
        parent = slot.span.context() if slot.span is not None else None
        if slot.prefill_pos == 0 and self.prefill_chunk <= 0:
            # block diffusion prefills (commits) the prompt's whole
            # blocks; its tail rides at the head of the first block
            n_rows = n_prompt - n_prompt % self._rows
            bucket = batcher.prompt_bucket_for(max(n_rows, 1),
                                               self.prefill_buckets)
            with telemetry.trace_span("generation/prefill_prepare",
                                      parent=parent, slot=slot.idx,
                                      bucket=bucket):
                self.kv.ensure_pages(slot, n_rows)
                prog, fetches = self._prefill_prog_for(bucket)
                feed = {"input_ids":
                        batcher.pad_prompt(prompt[:max(n_rows, 1)],
                                           bucket)[None],
                        "prompt_len": np.asarray([n_rows], "int32"),
                        **self.kv.table_feeds(slot)}
                if not self._blk:
                    feed["last_pos"] = np.asarray([n_prompt - 1], "int64")
                state = {}
                if self.state_names:
                    # the program overwrites the whole of this slot's
                    # state: whatever the slot's last sequence left goes
                    feed["slot"] = np.asarray([slot.idx], "int32")
                    state = {"state_written": 1}
                    if self._scan_chunk:
                        # what a delta or a state-space layer's scan
                        # covered: the prompt's tokens, the rung's chunks,
                        # and those of them wholly behind the prompt's end
                        chunks = -(-bucket // self._scan_chunk)
                        state.update(
                            scan_tokens=n_rows, scan_chunks=chunks,
                            scan_pad_chunks=chunks
                            - -(-n_rows // self._scan_chunk))
                # the rows [c_kv | k_r] a latent layer's pool took
                latent = {"latent_rows_written": n_rows} \
                    if self._latent_layers else {}
                # the rung's rows its dense products multiply: on a long
                # rung they stop at the prompt's last segment
                rows_run = dense_rows_run(bucket, n_rows, self.dtype)
            outs = self._launch(
                "generation/prefill", lambda: self._run_fetching(
                    self._prefill_exe, prog, fetches, feed),
                parent=parent, tokens=n_rows, bucket=bucket,
                rows_run=rows_run, slot=slot.idx, **state, **latent)
            self._count("prefill_rows_run", rows_run)
            self._count("prefill_rows_skipped", bucket - rows_run)
            stat_add("serving_prefill_rows_run", rows_run)
            stat_add("serving_prefill_rows_skipped", bucket - rows_run)
            if state:
                self._count("slot_state_writes")
                stat_add("serving_slot_state_writes")
            req.prefill_ms += (time.monotonic() - t0) * 1e3
            if req.tenant is not None:
                usage.ledger().book(req.tenant,
                                    flops=self._exe_flops(bucket))
            self._complete_prefill(slot, req, outs, n_rows, bucket)
            return
        # chunk continuation (chunked prefill and/or prefix-hit tail):
        # this iteration runs the FIRST remaining span; later spans
        # run on later iterations, decode steps in between
        start, end = batcher.chunk_spans(
            slot.prefill_pos, n_prompt, self.prefill_chunk)[0]
        n = end - start
        bucket = batcher.prompt_bucket_for(n, self.prefill_buckets)
        window = {}
        with telemetry.trace_span("generation/prefill_prepare",
                                  parent=parent, slot=slot.idx,
                                  bucket=bucket):
            windowed = self.window is not None
            released = self.kv.window_released
            had = self.kv.live_pages("window") if windowed else 0
            # the window kind keeps what the chunk's FIRST row admits
            self.kv.ensure_pages(slot, start + n, rows=n)
            prog, fetches = self._chunk_prog_for(bucket)
            chunk = np.zeros((bucket,), "int64")
            chunk[:n] = prompt[start:start + n]
            feed = self._chunk_feed(chunk, start, n, slot)
            if windowed:
                held = sum(1 for p in slot.wpages if p)
                mapped = self.kv.live_pages("window") - had \
                    + self.kv.window_released - released
                # programs run in the order sent, and the feed names the
                # pages this chunk reads: what the NEXT rows (the next
                # chunk's, or the first decode step's, at ``start + n``)
                # no longer admit goes back to the pool now, so that
                # only the slot whose chunk runs holds more than a
                # window's pages
                self.kv.slide_window_pages(slot, start + n + 1, 0)
                gone = self.kv.window_released - released
                self._count("window_pages_released_in_prefill", gone)
                stat_add("serving_kv_window_pages_released_in_prefill",
                         gone)
                self.kv.publish_gauges()
                window = {"window_pages_held": held,
                          "window_pages_mapped": mapped,
                          "window_pages_released": gone}
        last = start + n >= n_prompt
        latent = {}
        if self._latent_layers:
            # a latent layer's pool took the chunk's rows [c_kv | k_r];
            # its rows attended the slot's cached rows and themselves,
            # which the chunk kernel expanded in whole key blocks up to
            # the rung's last row (a layer's figures, not their sum)
            block = min(CHUNK_BLOCK_K, self.max_seq_len)
            latent = {"latent_rows_written": n,
                      "latent_rows_attended": start + n,
                      "latent_rows_expanded": min(
                          -(-(start + bucket) // block) * block,
                          self.max_seq_len)}
        outs = self._launch(
            "generation/prefill_chunk", lambda: self._run_fetching(
                self._prefill_exe, prog, fetches, feed),
            parent=parent, tokens=n, base=start, bucket=bucket,
            pad_rows=bucket - n, slot=slot.idx,
            attended_pairs=self._chunk_pairs(start, n), **window, **latent)
        self._count("prefill_chunks")
        stat_add("serving_prefill_chunks")
        if req.tenant is not None:
            usage.ledger().book(req.tenant,
                                flops=self._exe_flops(bucket))
        now = time.monotonic()
        req.prefill_ms += (now - t0) * 1e3
        req.note("chunk", now, {"base": start, "tokens": n})
        slot.prefill_pos = start + n
        if last:
            self._complete_prefill(slot, req, outs, n, bucket)
        elif "expert_counts" in outs:
            # booked with the prompt's last chunk, when all have run
            slot.chunk_counts.append((outs["expert_counts"], n, bucket,
                                      outs.get("expert_group_rows")))

    def _chunk_pairs(self, base: int, n: int) -> int:
        """The (row, column) pairs the ``n`` rows of a chunk at ``base``
        admit, summed over the attention layers: row ``t`` of a full
        layer every ``j <= base + t``, of a window layer the last
        ``window`` of them."""
        ends = np.arange(base + 1, base + n + 1, dtype=np.int64)
        n_window = len(self._window_layers)
        # (a layer that is an FFN alone attends nothing)
        n_full = len(self.kv.layers_of("pages")) + len(self._latent_layers)
        pairs = n_full * int(ends.sum())
        if n_window:
            pairs += n_window * int(np.minimum(ends, self.window).sum())
        return pairs

    def _fetch_first_token(self, slot: _Slot, outs, parent,
                           n_tokens: int, bucket: int) -> int:
        """Block on a prefill's outputs: the first generated token (the
        logits row when kept, and the expert layers' counts over the
        ``n_tokens`` real rows of the program's ``bucket``), under
        ``generation/prefill_fetch``."""
        span = self._begin_device_wait("generation/prefill_fetch", outs,
                                       parent=parent, slot=slot.idx)
        try:
            # (a block-diffusion prefill yields K/V alone: its first
            # tokens come from the first block's passes)
            first = int(np.asarray(outs["rows_written" if self._blk else
                                        "next_token"].numpy())[0])
            keep = slot.req.keep_logits
            slot.logits = [np.asarray(outs["logits"].numpy())[0]] \
                if keep and self.keep_logits and "logits" in outs else []
            slot.router_logits = \
                [np.asarray(outs["router_logits"].numpy())[0]] \
                if keep and "router_logits" in outs else []
            if "expert_counts" in outs:
                # (the prompt's earlier chunks ran before this one)
                chunks, slot.chunk_counts = slot.chunk_counts \
                    + [(outs["expert_counts"], n_tokens, bucket,
                        outs.get("expert_group_rows"))], []
                booked = [self._book_experts(
                    np.asarray(c.numpy()), n, rows,
                    None if g is None else np.asarray(g.numpy()))
                    for c, n, rows, g in chunks]
                if span is not None:
                    # (the counts come back with this fetch, after the
                    # ``generation/prefill`` span that launched them;
                    # ``experts_held_touched`` summed over the prompt's
                    # programs: each read the held experts it touched)
                    span.attrs.update({
                        k: sum(b[k] for b in booked) for k in (
                            "pairs_routed", "pairs_held", "pairs_absent",
                            "pairs_zero", "rows_group_held",
                            "pad_pairs_left_out", "experts_held_touched")
                        if k in booked[0]})
        finally:
            self._end_device_wait(span)
        return first

    def _book_experts(self, counts: np.ndarray, n_tokens: int,
                      built_for: int, group_rows=None) -> dict:
        """Book what a program's expert layers counted: ``counts``
        [L_moe, E] tokens per expert over the ``n_tokens`` valid rows of
        the ``built_for`` rows the program holds.  Routing is dropless, so
        every layer must have placed ``n_tokens * top_k`` pairs; the
        shortfall is ``moe_tokens_dropped`` and must read 0.  The other
        rows' pairs (a rung's pad tail, a step's idle slots) went
        through no expert: ``moe_pad_pairs_left_out``.  ``group_rows``
        [L_moe, n_group] (group-limited selection): the valid rows that
        kept each group; where the held experts lie inside one group, its
        rows are ``moe_rows_group_held``.  Returns the load figures of the
        step."""
        routed = int(counts.sum())
        dropped = counts.shape[0] * n_tokens * self._moe_top_k - routed
        left_out = counts.shape[0] * (built_for - n_tokens) \
            * self._moe_top_k
        for what, k in (("moe_tokens_routed", routed),
                        ("moe_pad_pairs_left_out", left_out)):
            self._count(what, k)
            stat_add(what, k)
        if dropped:
            self._count("moe_tokens_dropped", dropped)
            stat_add("moe_tokens_dropped", dropped)
            logger.error("expert routing lost %d token-expert pairs",
                         dropped)
        # (an identity expert has no weights to touch or to load)
        real = counts[:, :counts.shape[1] - self._moe_zero]
        touched = float((real > 0).sum(axis=1).mean())
        mean = real.mean(axis=1)
        load = float((real.max(axis=1) / np.maximum(mean, 1e-9)).mean())
        if telemetry.enabled():
            telemetry.gauge_set("moe_experts_touched", touched)
            telemetry.gauge_set("moe_expert_load_max_over_mean", load)
        attrs = {"experts_touched": round(touched, 3),
                 "expert_load_max_over_mean": round(load, 4),
                 "pad_pairs_left_out": left_out}
        if self._moe_shared:
            rows = counts.shape[0] * n_tokens
            self._count("moe_shared_expert_rows", rows)
            stat_add("moe_shared_expert_rows", rows)
        if self._moe_held is not None:
            # the router scored every expert of the group; the matmuls
            # ran over the pairs whose expert this chip holds
            first, n = self._moe_held
            here = counts[:, first:first + n]
            held = int(here.sum())
            for what, k in (("moe_pairs_routed", routed),
                            ("moe_pairs_held", held)):
                self._count(what, k)
                stat_add(what, k)
            attrs.update(pairs_routed=routed, pairs_held=held,
                         experts_held_touched=round(
                             float((here > 0).sum(axis=1).mean()), 3))
            if self._moe_zero:
                # the routed pairs three ways: multiplied here, an expert
                # of another chip's, an identity expert's (no product)
                zero = routed - int(real.sum())
                self._count("moe_pairs_zero", zero)
                stat_add("moe_pairs_zero", zero)
                attrs.update(pairs_zero=zero,
                             pairs_absent=routed - held - zero)
            per = counts.shape[1] // self._moe_groups
            if group_rows is not None and first // per == (first + n - 1) \
                    // per:
                # the rows whose kept groups include the one held here
                rows = int(group_rows[:, first // per].sum())
                self._count("moe_rows_group_held", rows)
                stat_add("moe_rows_group_held", rows)
                attrs["rows_group_held"] = rows
        return attrs

    def _complete_prefill(self, slot: _Slot, req: GenRequest, outs,
                          n_rows: int, bucket: int):
        """Shared tail of every prefill path: the sequence enters the
        decode grid at the position its prefill leaves it, with nothing
        booked, and the prefill is left to :meth:`_join` to read: at
        once, or, with a decode step in flight, after
        :meth:`_decode_step` has sent the next step out ahead with this
        sequence in it.  ``n_rows``: real rows of the program that
        produced ``outs`` (the whole prompt, or its last chunk: only
        that one's expert counts are fetched), built for ``bucket``."""
        n_prompt = int(req.prompt.size)
        slot.prefill_pos = n_prompt
        self._joining = _Joiner(slot, req, outs, n_rows, bucket)
        if self._blk:
            self._enter_blocks(slot, req, n_rows)
        else:
            slot.position, slot.tokens = n_prompt, []
            # (a prefill-role engine exports the pages instead)
            slot.decoding = self.role != "prefill"
        if self._inflight is None:
            self._join()

    def _join(self):
        """Read the prefill that :meth:`_complete_prefill` left unread,
        if there is one: block on its first generated token, publish
        the prompt's fully-covered pages to the prefix index and book
        the token.  A prefill that fails here fails its request only:
        its row of a step dispatched ahead is discarded at that step's
        settle, as a row whose sequence ended is."""
        j, self._joining = self._joining, None
        if j is None:
            return
        slot, req = j.slot, j.req
        try:
            self._read_prefill(slot, req, j.outs, j.n_rows, j.bucket)
        except Exception as e:  # noqa: BLE001 — a prefill failure
            # fails this request only
            self._fail_request(slot, req, "prefill", e)

    def _read_prefill(self, slot: _Slot, req: GenRequest, outs,
                      n_rows: int, bucket: int):
        """:meth:`_join`'s body: whatever raises here fails ``req``."""
        first = self._fetch_first_token(
            slot, outs, slot.span.context() if slot.span is not None
            else None, n_rows, bucket)
        n_prompt = int(req.prompt.size)
        self._h_prefill.observe(req.prefill_ms, trace_id=req.trace_id)
        telemetry.histogram_observe("serving_prefill_ms",
                                    req.prefill_ms,
                                    trace_id=req.trace_id)
        self._count("prefills")
        # prefix-hit tokens never ran a prefill pass — count only the
        # tokens this engine actually computed
        self._count("prefill_tokens", n_prompt - slot.hit_tokens)
        stat_add("serving_prefills")
        stat_add("serving_prefill_tokens", n_prompt - slot.hit_tokens)
        if req.tenant is not None:
            usage.ledger().book(req.tenant, prefill_steps=1)
        self.kv.register_prefix(slot, req.prompt)
        if self._blk:
            # (its first tokens come with the first block's commit pass)
            return
        slot.tokens = [first]
        if self.role == "prefill":
            # disaggregated prefill: export the populated pages as a
            # KVSegment instead of entering the decode grid — the
            # slot (and its pages) free for the next prompt now
            self._export_segment(slot, req)
            return
        if req.bb is not None:
            blackbox.request_phase(req.bb, "decoding")
        self._book_token(slot, first, time.monotonic())
        stream_writer.flush()

    def _enter_blocks(self, slot: _Slot, req: GenRequest, n_rows: int):
        """A block-diffusion sequence enters the grid after its prefill
        committed ``n_rows`` positions (the prompt's whole blocks): its
        first block starts there, with the prompt's tail fixed at its
        head and every other position undecided.  Nothing is booked
        yet: the first tokens come with that block's commit pass."""
        slot.position = n_rows
        slot.tokens = []
        self._new_block(slot, req.prompt[n_rows:])
        slot.passes = []
        slot.decoding = True
        if req.bb is not None:
            blackbox.request_phase(req.bb, "decoding")

    def _export_segment(self, slot: _Slot, req: GenRequest):
        """Gather the slot's populated pages into a detached
        :class:`~paddle_tpu.serving.disagg.KVSegment` and resolve the
        request with it (``finish="exported"``).  The gather copies
        page content, so the slot's pages release immediately —
        shared prefix pages fall back to the index's ref and keep
        serving later hits on THIS replica."""
        import jax.numpy as jnp

        from .disagg import KVSegment

        t0 = time.monotonic()
        n_prompt = slot.position
        needed = -(-n_prompt // self.page_tokens)
        idx = jnp.asarray(np.asarray(slot.pages[:needed], "int32"))
        with telemetry.trace_span("generation/segment_export",
                                  parent=slot.span.context()
                                  if slot.span is not None else None,
                                  tokens=n_prompt, pages=int(needed),
                                  slot=slot.idx):
            layers = []
            for i in range(len(self.cache_names) // 2):
                k_pool = self.scope.find_var(
                    f"{self.name}.pool_k_{i}")
                v_pool = self.scope.find_var(
                    f"{self.name}.pool_v_{i}")
                layers.append((jnp.take(k_pool, idx, axis=0),
                               jnp.take(v_pool, idx, axis=0)))
            seg = KVSegment(
                self.fingerprint(), n_prompt, n_prompt,
                list(slot.tokens), self.page_tokens, layers,
                logits=np.stack(slot.logits)
                if self.keep_logits and slot.logits else None,
                trace_id=req.trace_id)
        now = time.monotonic()
        ms = (now - t0) * 1e3
        # the prefill's first next-token was generated HERE (the
        # adopter only replays it)
        self._count("generated_tokens")
        stat_add("serving_generated_tokens")
        if req.tenant is not None:
            usage.ledger().book(req.tenant, tokens_out=1)
        self._count("segments_exported")
        stat_add("serving_segments_exported")
        stat_add("serving_segment_export_bytes", seg.nbytes)
        telemetry.histogram_observe("serving_segment_export_ms", ms,
                                    trace_id=req.trace_id)
        req.note("export", now, {"bytes": seg.nbytes, "pages": needed,
                                 "ms": round(ms, 3)})
        total_ms = (now - req.t_submit) * 1e3
        self._count("served")
        self._h_gen.observe(total_ms, trace_id=req.trace_id)
        telemetry.histogram_observe("serving_generate_ms", total_ms,
                                    trace_id=req.trace_id)
        if req.tenant is not None:
            led = usage.ledger()
            led.book(req.tenant, served=1)
            led.observe_latency(req.tenant, total_ms)
        result = {
            "tokens": [int(t) for t in slot.tokens],
            "prompt_len": n_prompt,
            "steps": 0,
            "finish": "exported",
            "trace_id": req.trace_id,
            "queue_wait_ms": round(
                ((req.t_claimed or now) - req.t_submit) * 1e3, 3),
            "prefill_ms": round(req.prefill_ms, 3),
            "ttft_ms": None,
            "total_ms": round(total_ms, 3),
            "segment": seg,
            "segment_bytes": seg.nbytes,
        }
        if slot.hit_tokens:
            result["prefix_hit_tokens"] = slot.hit_tokens
        if req.record_timeline:
            result["timeline"] = self._timeline_record(req, result)
            self._store_timeline(
                {k: v for k, v in result.items() if k != "segment"})
        self._end_seq_span(slot, "exported")
        slot.req = None
        slot.decoding = False
        slot.logits = []
        self.kv.release_pages(slot)
        self._sample_slot_track()
        blackbox.request_end(req.bb)
        req.future._resolve(outputs=result)

    # -- decode -------------------------------------------------------------
    def _host_tokens(self, tokens: np.ndarray):
        """``tokens`` [slots] int32 as the decode program's ``tokens`` feed,
        on the device: the same array type and width as the tokens one
        step carries into the next (:meth:`_carried_tokens`), so both
        bind the one compiled step."""
        import jax

        return jax.device_put(tokens.reshape(self.num_slots, -1))

    def _carried_tokens(self, outs: dict, rows=None, idx: int = -1):
        """The ``tokens`` feed of the step after the one that returned
        ``outs``: its greedy tokens (block diffusion: the blocks as its
        decisions left them) as the device holds them, never brought to
        the host.  A joiner did not ride that step: its row ``idx`` is
        taken from ``rows`` (:func:`_join_row`), its unread prefill's
        first token as the device holds that one (block diffusion: its
        first block from the host)."""
        tokens = outs["tokens" if self._blk else "next_token"].value
        if rows is not None:
            return _join_row(tokens, rows, idx)
        return tokens if self._blk else tokens.reshape(self.num_slots, 1)

    def _dispatch_decode(self, tokens, positions: np.ndarray,
                         block_tables: Optional[np.ndarray] = None,
                         live: Optional[np.ndarray] = None,
                         block_tables_window:
                         Optional[np.ndarray] = None,
                         block: Optional[dict] = None) -> dict:
        """Hand one grid step to the device.  Returns its fetch handles
        by name, unread: ``next_token`` and, where the program has
        them, ``logits``, ``expert_counts``, ``router_logits`` (the
        counts ride the token fetch: one wait for the one program).
        ``block``: a block-diffusion pass's further feeds (``masked``,
        ``quota``, ``fresh``); its fetches are ``tokens`` and
        ``masked``."""
        empty = (self.num_slots, self.pages_per_slot)
        feed = {"tokens": tokens, "positions": positions,
                "block_tables": block_tables if block_tables is not None
                else np.zeros(empty, "int32"),
                "live": live if live is not None
                else np.zeros((self.num_slots,), "int32")}
        if self._blk:
            feed.update(block)
        if self.window is not None:
            feed["block_tables_window"] = block_tables_window \
                if block_tables_window is not None \
                else np.zeros(empty, "int32")
        return self._launch(
            "generation/decode_dispatch", lambda: self._run_fetching(
                self._decode_exe, self._decode_prog, self._decode_fetches,
                feed))

    def _fetch_decode(self, outs: dict) -> dict:
        """Block on a dispatched grid step: its fetches as arrays."""
        span = self._begin_device_wait("generation/token_fetch", outs)
        try:
            return {n: np.asarray(o.numpy()) for n, o in outs.items()}
        finally:
            self._end_device_wait(span)

    def _speculate_round(self) -> frozenset:
        """One speculative draft/verify per eligible decoding slot.
        Returns the slot indices that advanced (>= 1 token each) —
        this iteration's grid step skips them; ineligible slots (per-
        request opt-out, no n-gram match, budget/capacity leaves no
        draft room, pool exhausted) fall through to it unchanged.

        Per slot: the prompt-lookup drafter proposes up to K tokens
        from the sequence's own history; the verify chunk
        ``[pending, draft...]`` runs at ``base = position`` (row 0
        writes the pending token's K/V exactly where the plain step
        would); ``a`` = longest prefix with ``draft[i] == argmax(row
        i)`` and rows ``0..a`` commit — ``a + 1`` tokens booked
        through :meth:`_book_token` in order, never fewer than the
        plain step's one.  Draft pages past the new position roll
        back through the pool."""
        served = set()
        for slot in list(self._decoding_slots()):
            req = slot.req
            if req.speculate is False:
                continue
            cap = min(self.spec_tokens,
                      req.max_new_tokens - len(slot.tokens) - 1,
                      self.max_seq_len - slot.position - 1)
            if cap < 1:
                continue
            history = np.concatenate(
                [req.prompt, np.asarray(slot.tokens, "int64")])
            draft = ngram_draft(history, cap, self.spec_ngram)
            if not draft:
                continue
            t0 = time.monotonic()
            # the verify IS a decode-grid dispatch: it donates the
            # same pool buffers, so it shares the decode_step fault
            # site (chaos's mid-verify faults land here)
            kind = fault.fire("decode_step")
            fault.maybe_delay(kind)
            if kind == "fail":
                raise fault.InjectedFault(
                    "injected decode_step failure (spec verify)")
            c = len(draft) + 1  # [pending, draft...]
            try:
                keep = self.kv.acquire_draft_pages(
                    slot, slot.position + c)
            except PoolExhausted:
                # transient: live sequences will free pages; the slot
                # rides the plain step (whose own ensure/cache_full
                # path still governs hard exhaustion)
                continue
            self._count("spec_drafts")
            stat_add("serving_spec_drafts")
            self._count("spec_tokens_proposed", len(draft))
            stat_add("serving_spec_tokens_proposed", len(draft))
            bucket = batcher.prompt_bucket_for(c, self.prefill_buckets)
            prog, fetches = self._verify_prog_for(bucket)
            chunk = np.zeros((bucket,), "int64")
            chunk[0] = slot.tokens[-1]
            chunk[1:c] = draft
            names = ["tokens", "logits"] if self.keep_logits else ["tokens"]
            feed = {"chunk_ids": chunk[None],
                    "base": np.asarray([slot.position], "int32"),
                    "block_table": self.kv.block_table(slot)[None],
                    "chunk_len": np.asarray([c], "int32")}
            outs = self._launch(
                "generation/spec_verify", lambda: dict(zip(
                    names, self._prefill_exe.run(
                        prog, feed=feed,
                        fetch_list=[fetches[n] for n in names],
                        scope=self.scope, return_numpy=False,
                        on_launch=self._on_launch))),
                parent=slot.span.context() if slot.span is not None
                else None, draft=len(draft), bucket=bucket, slot=slot.idx)
            m = np.asarray(outs["tokens"].numpy())[0]
            logits_arr = np.asarray(outs["logits"].numpy())[0] \
                if self.keep_logits else None
            a = 0
            while a < len(draft) and int(draft[a]) == int(m[a]):
                a += 1
            t1 = time.monotonic()
            ms = (t1 - t0) * 1e3
            self._h_verify.observe(ms, trace_id=req.trace_id)
            telemetry.histogram_observe("serving_spec_verify_ms", ms,
                                        trace_id=req.trace_id)
            self._count("spec_tokens_accepted", a)
            stat_add("serving_spec_tokens_accepted", a)
            if req.tenant is not None:
                usage.ledger().book(req.tenant,
                                    flops=self._exe_flops(bucket))
            if a < len(draft):
                self._count("spec_rollbacks")
                stat_add("serving_spec_rollbacks")
            # book rows 0..a in order: row j's argmax is the token a
            # plain step would emit after committing the chunk's first
            # j+1 tokens — the stream (and logits) are the plain
            # stream, several steps at once.  One clock read for the
            # burst: the tokens genuinely became available together
            for j in range(a + 1):
                tok = int(m[j])
                slot.position += 1
                slot.steps += 1
                slot.tokens.append(tok)
                if logits_arr is not None:
                    slot.logits.append(logits_arr[j])
                self._book_token(slot, tok, t1)
                if slot.req is None:
                    break  # finished mid-burst (_finish freed pages)
            stream_writer.flush()
            if slot.req is not None:
                self.kv.rollback_draft_pages(
                    slot, max(keep,
                              -(-slot.position // self.page_tokens)))
            served.add(slot.idx)
        return frozenset(served)

    def _decode_step(self, skip: frozenset = frozenset()):
        """One pass of the decode grid with one step kept in flight:
        dispatch the next step, then settle (fetch and book) the one
        dispatched a pass earlier, so the host's half of a step runs
        while the device works on the next.  That order needs the next
        step built without the last one's tokens on the host: every
        sequence due to ride it rode the step in flight, whose tokens
        it takes as the device holds them, or is the joiner, the
        sequence whose finished prefill this pass launched behind that
        step and has not read (:class:`_Joiner`): its token is the
        prefill's, as the device holds it.  So a pass that carries a
        prefill runs: launch the prefill, dispatch the step ahead with
        the joiner in it, settle the step in flight, and only then
        block on the prefill and book its first token; the device goes
        from step to prefill to step without a gap.  With an adopted
        segment in the grid or no page left for a position ahead, the
        settle (and the joiner's first token) comes first and the step
        goes out from the host's tokens: the same pass, the settle
        placed before the dispatch."""
        kind = fault.fire("decode_step")
        fault.maybe_delay(kind)
        if kind == "fail":
            raise fault.InjectedFault("injected decode_step failure")
        lead = self._inflight if self._rides_on() else None
        built = None
        if lead is not None:
            try:
                built = self._decode_feeds_for(skip, lead)
            except PoolExhausted:
                # no page for a rider's position ahead: it may end at
                # the settle, so that comes first
                lead = None
        if built is None:
            self._settle_inflight()
            built = self._decode_feeds_for(skip, None)
        riders, feeds, links, t0 = built
        if not riders:
            # nothing rides on: all that is left is the step in flight
            self._settle_inflight()
            return
        step = telemetry.span_begin("generation/decode_step", links=links,
                                    active=len(riders),
                                    ahead=int(lead is not None))
        try:
            self._inflight = _StepInFlight(
                riders, self._dispatch_decode(*feeds), links, t0,
                lead is not None, self._released_in_feeds)
            if lead is not None:
                outs = self._fetch_inflight(lead, step)
        finally:
            telemetry.span_end(step)
        if lead is None:
            return
        joining = self._joining
        if joining is not None and any(s is joining.slot for s, _ in riders):
            self._count("decode_joiners_ahead")
            stat_add("serving_decode_joiners_ahead")
        self._book_inflight(lead, outs)
        self._release_step(lead)
        self._join()
        if not any(s.req is r for s, r in riders):
            # every sequence of the step ahead ended at this settle: no
            # row of it will be booked, and nothing waits for it
            self._inflight = None
            self._discard_rows(len(riders))

    def _discard_rows(self, n: int):
        """Count ``n`` rows of a step dispatched ahead that no sequence
        is left to take."""
        if n:
            self._count("decode_rows_discarded", n)
            stat_add("serving_decode_rows_discarded", n)

    def _rides_on(self) -> bool:
        """Whether the next grid step can go out ahead of the settle of
        the one in flight: there is one, and every sequence decoding
        now rode it or is the joiner, whose first input the device
        holds as it holds the riders' (an adopted segment's tokens are
        the host's: after one, the settle comes first)."""
        fl = self._inflight
        if fl is None:
            return False
        rode = {s.idx: r for s, r in fl.riders}
        joiner = self._joining.slot if self._joining is not None else None
        return all(rode.get(s.idx) is s.req or s is joiner
                   for s in self._decoding_slots())

    def _settle_inflight(self):
        """Fetch and book the step in flight, if there is one, and then
        the first token of the prefill launched behind it
        (:meth:`_join`): what comes before anything that reads or ends
        the sequences' booked state (a step built from the host's
        tokens, a speculative draft, a weight swap)."""
        fl, self._inflight = self._inflight, None
        if fl is None:
            return
        step = telemetry.span_begin("generation/decode_step",
                                    links=fl.links, active=len(fl.riders))
        try:
            outs = self._fetch_inflight(fl, step)
        finally:
            telemetry.span_end(step)
        self._book_inflight(fl, outs)
        self._release_step(fl)
        self._join()

    def _fetch_inflight(self, fl: _StepInFlight, step) -> dict:
        """Wait for ``fl``'s fetches and write what the step did onto
        the open ``generation/decode_step`` span."""
        outs = self._fetch_decode(fl.outs)
        attrs = {}
        if "expert_counts" in outs:
            # the counts cover every live row of the step, the rows the
            # settle discards too
            attrs.update(self._book_experts(
                outs["expert_counts"], len(fl.riders) * self._rows,
                self.num_slots * self._rows, outs.get("expert_group_rows")))
        if self.window is not None:
            # the pages this step's feeds let go, what both kinds hold
            # now, the positions its rows attended
            rows = [s for s, r in fl.riders if s.req is r]
            attrs.update(
                window_pages_released=fl.released,
                pages_live_full=self.kv.live_pages(),
                pages_live_window=self.kv.live_pages("window"),
                live_positions=int(sum(s.position + 1 for s in rows)),
                live_positions_window=int(sum(
                    min(s.position + 1, self.window) for s in rows)))
        if self.state_names or self._latent_layers:
            live = int(sum(s.position + 1
                           for s, r in fl.riders if s.req is r))
        if self.state_names:
            # the slots whose state this step moved on (every row that
            # rode it), and the positions its attention layers read
            attrs.update(state_slots=len(fl.riders), live_positions=live)
            for key, scanned in (("delta_state_steps", self._delta_layers),
                                 ("ssm_state_steps", self._ssd_layers)):
                if scanned:
                    moved = len(fl.riders) * len(scanned)
                    self._count(key, moved)
                    stat_add("serving_" + key, moved)
        if self._latent_layers:
            # the cached rows a latent layer's decode kernel read
            attrs["latent_positions"] = live
        if self._blk:
            # what this pass was, slot by slot: the booked state is the
            # state it was dispatched from
            rows = [s for s, r in fl.riders if s.req is r]
            commit = [s for s in rows if not s.blk_left]
            attrs.update(
                passes_denoise=len(rows) - len(commit),
                passes_commit=len(commit),
                rows=len(rows) * self._blk,
                live_positions=int(sum(s.position + self._blk
                                       for s in rows)),
                tokens_committed=int(sum(
                    self._block_yield(s) for s in commit)))
        if step is not None:
            step.attrs.update(attrs)
        return outs

    def _book_inflight(self, fl: _StepInFlight, outs: dict):
        """Book ``fl``'s fetched tokens under ``generation/book_tokens``
        to the riders whose slot still serves the request that rode."""
        t1 = time.monotonic()
        rows = [s for s, r in fl.riders if s.req is r]
        span = telemetry.span_begin("generation/book_tokens", cpu=True,
                                    links=fl.links, tokens=len(rows))
        try:
            self._book_step(rows, outs, fl.t0, t1)
            if fl.ahead:
                self._count("decode_steps_ahead")
                stat_add("serving_decode_steps_ahead")
            self._discard_rows(len(fl.riders) - len(rows))
            if self._inflight is not None:
                # dispatched ahead of this settle: the device begins it
                # now
                self._inflight.t0 = t1
            if span is not None:
                span.attrs["finished"] = sum(s.req is None for s in rows)
        finally:
            telemetry.span_end(span)
            # one wake-up for the step's lines, however many streams
            stream_writer.flush()

    def _release_step(self, fl: _StepInFlight):
        """Let go of a settled step's device arrays, here and not
        wherever a caller's frame ends: the runtime gives up the
        interpreter to free them, and the stream writer that the booking
        just woke may run before this thread has it back (until PR 44 a
        handler thread a stream did: 6 ms a pass with 64 streams,
        PERF.md section 6, PR 36), so the wait has a span,
        ``generation/release``."""
        span = telemetry.span_begin("generation/release", cpu=True)
        fl.outs = None
        telemetry.span_end(span)
        if self._on_scheduler(span):
            # the longest stretch of a pass with no word from the device
            # ends here
            self._account.probe()

    def _decode_feeds_for(self, skip: frozenset,
                          lead: Optional[_StepInFlight]):
        """:meth:`_build_decode_feeds` under ``generation/decode_feeds``.
        Returns ``(riders, feeds, links, t0)``.  Raises
        :class:`PoolExhausted` when the step was to go out ahead of
        ``lead``'s settle and a rider has no page for the position
        ahead."""
        t0 = time.monotonic()
        span = telemetry.span_begin("generation/decode_feeds", cpu=True)
        try:
            active, feeds = self._build_decode_feeds(skip, lead)
            # the grid step serves N sequences at once: link their
            # sequence-span contexts, the fan-in convention batch spans
            # use
            links = tuple(s.span.context() for s in active
                          if s.span is not None)
            if span is not None:
                span.attrs["active"] = len(active)
                span.links = links
        finally:
            telemetry.span_end(span)
        return [(s, s.req) for s in active], feeds, links, t0

    def _joiner_ahead(self, lead: Optional[_StepInFlight]) -> Optional[_Slot]:
        """The slot that enters the step going out ahead of ``lead``'s
        settle without having ridden ``lead``: the joiner's, if there
        is one.  It stands no step past its booked state but AT it: the
        host knows the position its unread prefill leaves it at."""
        if lead is None or self._joining is None:
            return None
        return self._joining.slot

    def _build_decode_feeds(self, skip: frozenset,
                            lead: Optional[_StepInFlight] = None):
        """The host half of a grid step before its dispatch: the page
        guard, then the slots that ride the step and the program's
        feeds ``(tokens, positions, block_tables, live)``.  With
        ``lead`` the step goes out ahead of ``lead``'s settle: each
        rider sits one position past its booked one, its token is
        ``lead``'s on the device (the joiner's: its prefill's), and a
        sequence the host knows will end at ``lead`` or at its first
        token (its budget or its cache is one token from full) stays
        out."""
        if self._blk:
            return self._build_block_feeds(lead)
        ahead = int(lead is not None)
        joiner = self._joiner_ahead(lead)
        riding = [s for s in self._decoding_slots()
                  if s.idx not in skip
                  and not (ahead and (
                      len(s.tokens) + 1 >= s.req.max_new_tokens
                      or s.position + (s is not joiner)
                      >= self.max_seq_len))]
        released = self.kv.window_released
        # pool-exhaustion guard: a slot about to cross into an
        # unmapped page must get one BEFORE the step (the write
        # would land on the trash page and corrupt nothing, but
        # the token would be attention-blind to itself); a slot
        # the pool cannot serve even after eviction finishes
        # cache_full with everything it generated so far (ahead of
        # a settle it has more to come: the caller settles first)
        for s in riding:
            try:
                self.kv.ensure_pages(
                    s, s.position + ahead * (s is not joiner) + 1)
            except PoolExhausted:
                if ahead:
                    raise
                self._finish(s, "cache_full")
        self._released_in_feeds = self.kv.window_released - released
        active = [s for s in riding if s.req is not None]
        if not active:
            return active, None
        positions = np.zeros((self.num_slots,), "int32")
        for s in active:
            positions[s.idx] = s.position + ahead * (s is not joiner)
        if ahead and joiner in active:
            tokens = self._carried_tokens(
                lead.outs, self._joining.outs["next_token"].value,
                joiner.idx)
        elif ahead:
            tokens = self._carried_tokens(lead.outs)
        else:
            last = np.zeros((self.num_slots,), "int32")
            for s in active:
                last[s.idx] = s.tokens[-1]
            tokens = self._host_tokens(last)
        bt = np.zeros((self.num_slots, self.pages_per_slot), "int32")
        live = np.zeros((self.num_slots,), "int32")
        btw = np.zeros_like(bt) if self.window is not None else None
        for s in active:
            bt[s.idx] = self.kv.block_table(s)
            live[s.idx] = 1
            if btw is not None:
                btw[s.idx] = self.kv.block_table(s, window=True)
        return active, (tokens, positions, bt, live, btw)

    # -- block diffusion: a slot's step is a pass over a block -------------
    def _block_quota(self, left: int, done: int) -> int:
        """Positions a denoising pass decides, ``left`` undecided after
        ``done`` passes: the static schedule, ``ceil(left /
        passes_left)``.  0 is the commit pass."""
        return -(-left // (self._blk_passes - done)) if left else 0

    def _block_yield(self, s: _Slot) -> int:
        """Tokens the commit of ``s``'s current block books: the block
        less the prompt's tail at its head, cut by the budget."""
        return min(self._blk - s.blk_head,
                   s.req.max_new_tokens - len(s.tokens))

    def _new_block(self, s: _Slot, head=()):
        """The host's mirror of a block no pass has touched: ``head``
        (the prompt's tail, for the first block) fixed at its start,
        the mask token, undecided, everywhere else."""
        B, n = self._blk, len(head)
        s.blk_tokens = np.full((B,), self._blk_mask_id, "int32")
        s.blk_tokens[:n] = head
        s.blk_masked = (np.arange(B) >= n).astype("int32")
        s.blk_left, s.blk_done, s.blk_head = B - n, 0, n

    def _block_phase(self, s: _Slot, ahead: int):
        """``(base, undecided, denoising passes done, fresh)`` of the
        block ``s`` works on ``ahead`` passes past its booked state.
        The schedule is static, so the host knows every slot's phase
        and position ahead without reading the device."""
        base, left, done, fresh = s.position, s.blk_left, s.blk_done, 0
        for _ in range(ahead):
            if left:
                left -= self._block_quota(left, done)
                done += 1
            else:                    # that pass commits: the next block
                base, left, done, fresh = base + self._blk, self._blk, 0, 1
        return base, left, done, fresh

    def _build_block_feeds(self, lead: Optional[_StepInFlight]):
        """:meth:`_build_decode_feeds` for block diffusion: each rider's
        row is its block's next pass (``quota`` 0: the commit pass).
        With ``lead`` the blocks are ``lead``'s on the device, a slot
        whose block ``lead`` commits starts the next one (``fresh``),
        and a sequence whose last block ``lead`` commits stays out; the
        joiner's row is its first block as the host made it, behind the
        positions its unread prefill commits."""
        B, ahead = self._blk, int(lead is not None)
        joiner = self._joiner_ahead(lead)
        riding = [s for s in self._decoding_slots()
                  if not (ahead and s is not joiner and not s.blk_left and (
                      len(s.tokens) + self._block_yield(s)
                      >= s.req.max_new_tokens
                      or s.position + 2 * B > self.max_seq_len))]
        self._released_in_feeds = 0
        n = self.num_slots
        positions, quota, fresh, live = (np.zeros((n,), "int32")
                                         for _ in range(4))
        bt = np.zeros((n, self.pages_per_slot), "int32")
        for s in riding:
            base, left, done, new = self._block_phase(
                s, ahead * (s is not joiner))
            try:
                self.kv.ensure_pages(s, base + B)
            except PoolExhausted:
                if ahead:
                    raise
                self._finish(s, "cache_full")
                continue
            positions[s.idx], fresh[s.idx], live[s.idx] = base, new, 1
            quota[s.idx] = self._block_quota(left, done)
            bt[s.idx] = self.kv.block_table(s)
        active = [s for s in riding if s.req is not None]
        if not active:
            return active, None
        # the blocks the host holds: every rider's, or ahead of a settle
        # the joiner's alone
        host = np.zeros((2, n, B), "int32")
        for s in (active if not ahead else [joiner] if joiner else []):
            host[0, s.idx], host[1, s.idx] = s.blk_tokens, s.blk_masked
        if not ahead:
            tokens, masked = (self._host_tokens(h) for h in host)
        elif joiner is not None:
            tokens = self._carried_tokens(lead.outs, host[0], joiner.idx)
            masked = _join_row(lead.outs["masked"].value, host[1],
                               joiner.idx)
        else:
            tokens = self._carried_tokens(lead.outs)
            masked = lead.outs["masked"].value
        return active, (tokens, positions, bt, live, None,
                        {"masked": masked, "quota": quota, "fresh": fresh})

    def _book_pass(self, s: _Slot, outs: dict, now: float, riders: int):
        """Book one slot's pass: a denoising pass moves the host's
        mirror of the block on; a commit pass books and streams the
        block's tokens (one timestamp for all of them), cut by the
        budget, and moves the slot a block on."""
        s.steps += 1
        if self.keep_logits:
            # the block as the program was fed it, beside what it gave
            # and how many slots rode the pass
            rec = {"base": s.position, "tokens": s.blk_tokens.copy(),
                   "masked": s.blk_masked.copy(),
                   "quota": self._block_quota(s.blk_left, s.blk_done),
                   "riders": riders, "logits": outs["logits"][s.idx]}
            if "router_logits" in outs:       # [L_moe, B, E]
                rec["router_logits"] = outs["router_logits"][s.idx]
            s.passes.append(rec)
        if s.blk_left:
            s.blk_left -= self._block_quota(s.blk_left, s.blk_done)
            s.blk_done += 1
            s.blk_tokens = outs["tokens"][s.idx]
            s.blk_masked = outs["masked"][s.idx]
            return
        toks = [int(t) for t in
                s.blk_tokens[s.blk_head:s.blk_head + self._block_yield(s)]]
        s.position += self._blk
        self._new_block(s)
        self._count("block_tokens_committed", len(toks))
        stat_add("serving_block_tokens_committed", len(toks))
        for i, tok in enumerate(toks):
            s.tokens.append(tok)
            self._book_token(s, tok, now, last=i == len(toks) - 1)
            if s.req is None:
                break                # EOS inside the block

    def _book_step(self, active, outs: dict, t0: float, t1: float):
        """The host half of a grid step after its token fetch: step
        accounting, then one booked token per riding slot."""
        next_tokens, logits = outs.get("next_token"), outs.get("logits")
        router = outs.get("router_logits")
        ms = (t1 - t0) * 1e3
        self._h_step.observe(ms)
        telemetry.histogram_observe("serving_decode_step_ms", ms)
        self._count("decode_steps")
        stat_add("serving_decode_steps")
        tenants = [s for s in active if s.req.tenant is not None]
        if tenants:
            # one grid dispatch serves N sequences: each participant
            # books one decode_step (sequence-step, NOT dispatch —
            # documented in the README cost-vector schema) and its
            # row-weighted share of the step's manifest flops
            # (largest-remainder: integer shares sum exactly)
            led = usage.ledger()
            shares = usage.split_ints(self._decode_flops(),
                                      [1] * len(tenants))
            for s, f in zip(tenants, shares):
                led.book(s.req.tenant, decode_steps=1, flops=f)
        dt = ms / 1e3
        self._decode_rate_ema = (1.0 / dt if self._decode_rate_ema is None
                                 else 0.9 * self._decode_rate_ema
                                 + 0.1 / dt)
        if self._blk:
            # (the booked state is the state the pass was dispatched from)
            commits = sum(not s.blk_left for s in active)
            for key, n in (("block_passes_denoise", len(active) - commits),
                           ("block_passes_commit", commits)):
                if n:
                    self._count(key, n)
                    stat_add("serving_" + key, n)
            for s in active:
                self._book_pass(s, outs, t1, len(active))
            return
        for s in active:
            tok = int(next_tokens[s.idx])
            s.position += 1
            s.steps += 1
            s.tokens.append(tok)
            if logits is not None and s.req.keep_logits:
                s.logits.append(logits[s.idx])
            if router is not None and s.req.keep_logits:
                s.router_logits.append(router[s.idx])
            # one timestamp for the whole grid step: per-token
            # bookkeeping adds no extra clock reads to the step
            self._book_token(s, tok, t1)

    def _book_token(self, slot: _Slot, tok: int, now: float,
                    last: bool = True):
        """Account one generated token and finish the slot on EOS /
        token budget / cache exhaustion — freeing it for the next
        queued request at the very next scheduler iteration.  ``now``
        is the caller's already-taken post-step timestamp (the whole
        grid shares one clock read): it feeds the sequence timeline,
        the TTFT / inter-token histograms, and the per-token
        callback.  ``last``: the step booked nothing after this token
        (all but the last token of a committed block pass False: the
        cache is not full while the block is being booked)."""
        self._count("generated_tokens")
        stat_add("serving_generated_tokens")
        req = slot.req
        if req.tenant is not None:
            # same site as the global counter above: per-tenant
            # tokens_out sums stay equal to it at tolerance 0
            usage.ledger().book(req.tenant, tokens_out=1)
        tele = telemetry.enabled()
        if req.record_timeline:
            # _timeline_record is the only consumer: an on_token-only
            # request (streaming with telemetry off) pays no list
            req.t_tokens.append(now)
        if req.t_first is None:
            req.t_first = now
            if tele:
                ttft = (now - req.t_submit) * 1e3
                self._h_ttft.observe(ttft, trace_id=req.trace_id)
                telemetry.histogram_observe("serving_ttft_ms", ttft,
                                            trace_id=req.trace_id)
        elif tele:
            itl = (now - (req.t_last if req.t_last is not None
                          else req.t_first)) * 1e3
            self._h_itl.observe(itl, trace_id=req.trace_id)
            telemetry.histogram_observe("serving_inter_token_ms", itl,
                                        trace_id=req.trace_id)
        req.t_last = now
        if req.on_token is not None:
            try:
                req.on_token(tok, now)
            except Exception as e:  # noqa: BLE001 — a broken stream
                # consumer must not take down the scheduler (or the
                # other sequences riding this grid step)
                logger.warning("on_token callback failed (token "
                               "dropped from stream): %s", e)
                req.on_token = None
        finish = None
        if tok == self.eos_id:
            finish = "eos"
        elif len(slot.tokens) >= req.max_new_tokens:
            finish = "length"
        elif last and slot.position + self._rows > self.max_seq_len:
            # the next decode step would write at index max_seq_len —
            # past the cache bucket, where dynamic_update_slice would
            # silently clamp onto the last row; finishing HERE is the
            # out-of-bounds guard (reachable: submit does not clamp a
            # request's budget to the capacity left after its prompt)
            finish = "cache_full"
        if finish is not None:
            self._finish(slot, finish)

    def _finish(self, slot: _Slot, finish: str):
        req = slot.req
        now = time.monotonic()
        req.note("finish", now, {"reason": finish})
        total_ms = (now - req.t_submit) * 1e3
        self._count("served")
        self._h_gen.observe(total_ms, trace_id=req.trace_id)
        telemetry.histogram_observe("serving_generate_ms", total_ms,
                                    trace_id=req.trace_id)
        if req.tenant is not None:
            led = usage.ledger()
            led.book(req.tenant, served=1)
            led.observe_latency(req.tenant, total_ms)
        result = {
            "tokens": [int(t) for t in slot.tokens],
            "prompt_len": int(req.prompt.size),
            "steps": slot.steps,
            "finish": finish,
            "trace_id": req.trace_id,
            "queue_wait_ms": round(
                ((req.t_claimed or now) - req.t_submit) * 1e3, 3),
            "prefill_ms": round(req.prefill_ms, 3),
            "ttft_ms": round((req.t_first - req.t_submit) * 1e3, 3)
            if req.t_first is not None else None,
            "total_ms": round(total_ms, 3),
            "slot": slot.idx,
        }
        if self.keep_logits:
            result["logits"] = slot.logits
            slot.logits = []
            if self._blk:
                # every pass, denoising and commit: the block as fed
                # (base, tokens, masked, quota) and its [B, V] logits
                result["passes"] = slot.passes
                slot.passes = []
            if slot.router_logits:
                # [L_moe, E] per generated token, for a reference check
                # (block diffusion: the prefill's, [L_moe, bucket, E];
                # the passes' are on their records)
                result["router_logits"] = slot.router_logits
                slot.router_logits = []
        if slot.hit_tokens:
            result["prefix_hit_tokens"] = slot.hit_tokens
        if req.record_timeline:
            result["timeline"] = self._timeline_record(req, result)
            self._store_timeline(result)
        self._end_seq_span(slot, finish)
        slot.req = None
        slot.decoding = False
        self.kv.release_pages(slot)
        self._sample_slot_track()
        blackbox.request_end(req.bb)
        req.future._resolve(outputs=result)

    def _timeline_record(self, req: GenRequest, result: dict) -> dict:
        """The per-sequence timeline as relative-ms offsets from
        admission — the Dapper-style record behind TTFT/ITL: every
        phase boundary (claim, prefix hit, each prefill slice, every
        token, finish) as the user's clock saw it."""
        t0 = req.t_submit

        def rel(t):
            return round((t - t0) * 1e3, 3)

        events = []
        for label, t, extra in req.events:
            ev = {"at_ms": rel(t), "event": label}
            if extra:
                ev.update(extra)
            events.append(ev)
        token_ms = [rel(t) for t in req.t_tokens]
        tl = {"trace_id": req.trace_id, "events": events,
              "token_ms": token_ms,
              "ttft_ms": result.get("ttft_ms")}
        if len(token_ms) >= 2:
            gaps = [round(b - a, 3)
                    for a, b in zip(token_ms, token_ms[1:])]
            gaps_sorted = sorted(gaps)
            tl["inter_token_ms"] = {
                "p50": gaps_sorted[len(gaps_sorted) // 2],
                "max": gaps_sorted[-1],
                "mean": round(sum(gaps) / len(gaps), 3),
            }
        return tl

    def _store_timeline(self, result: dict):
        """Bounded finished-sequence store for ``/tracez``: recent
        ring + always-kept slowest-N by total latency (exemplar trace
        ids from the TTFT/ITL histograms resolve here)."""
        rec = {k: result[k] for k in ("trace_id", "finish", "steps",
                                      "prompt_len", "queue_wait_ms",
                                      "prefill_ms", "ttft_ms",
                                      "total_ms") if k in result}
        rec["timeline"] = result.get("timeline")
        with self._timeline_lock:
            self._timelines_recent.append(rec)
            if self._tail_keep:
                self._timelines_slow.append(rec)
                self._timelines_slow.sort(
                    key=lambda r: -(r.get("total_ms") or 0.0))
                del self._timelines_slow[self._tail_keep:]

    def retry_after_s(self) -> float:
        """Backoff hint for 503 sheds (the ``Retry-After`` header):
        queued requests over the slot grid at the measured per-request
        p50 generation time, bounded to [0.5, 30] s (the one-shot
        engine's contract, sized for sequences instead of batches)."""
        with self._cv:
            depth = len(self._queue)
        summ = self._h_gen.summary()
        per_req_s = (summ.get("p50") or 250.0) / 1e3
        est = (depth / max(1, self.num_slots) + 1) * per_req_s
        return min(30.0, max(0.5, est))

    # -- introspection ------------------------------------------------------
    def _sample_slot_track(self):
        """Per-slot occupancy as a Perfetto counter track
        (``generation_slots``): one stacked series per slot (0/1) plus
        the active total, sampled only on occupancy TRANSITIONS
        (claim/finish) so a long decode burst costs ring entries at
        the rate slots turn over, not per step."""
        if not telemetry.enabled():
            return
        vec = tuple(1.0 if s.active else 0.0 for s in self._slots)
        if vec == self._occ_vec:
            return
        self._occ_vec = vec
        series = {f"slot{i}": v for i, v in enumerate(vec)}
        series["active"] = float(sum(vec))
        telemetry.counter_sample("generation_slots", series)

    def tracez(self) -> dict:
        """The ``/tracez`` ``generation`` block: recent finished
        sequence timelines (newest first) + the slowest-N tail, plus
        the live TTFT / inter-token exemplars — a histogram exemplar's
        trace id resolves to its full timeline here."""
        with self._timeline_lock:
            recent = list(self._timelines_recent)
            slow = list(self._timelines_slow)
        return {"recent": recent[::-1], "slowest": slow,
                "ttft_exemplars": self._h_ttft.exemplars(),
                "inter_token_exemplars": self._h_itl.exemplars()}

    def _publish_gauges(self) -> int:
        """Publish the per-iteration gauges; returns the active slot
        count it took them from."""
        active = len(self._active())
        if not telemetry.enabled():
            return active
        telemetry.gauge_set("serving_slot_occupancy",
                            active / self.num_slots)
        if self.speculate:
            with self._n_lock:
                prop = self._n["spec_tokens_proposed"]
                acc = self._n["spec_tokens_accepted"]
            if prop:
                telemetry.gauge_set("serving_spec_acceptance_rate",
                                    acc / prop)
        return active

    def decode_manifest(self) -> Optional[dict]:
        """The decode-step executable's cost/memory manifest (flops,
        bytes accessed, peak HBM — see costmodel.executable_manifest);
        None before the first decode step or when the backend exposes
        no analysis."""
        for e in self._decode_exe.cache_info()["entries"]:
            if e.get("manifest"):
                return e["manifest"]
        return None

    def decode_mfu(self) -> Optional[float]:
        """Achieved decode-step MFU: manifest FLOPs × measured grid
        step rate over the chip peak."""
        m = self.decode_manifest()
        if not m or not m.get("flops") or not self._decode_rate_ema:
            return None
        return costmodel.mfu(m["flops"] * self._decode_rate_ema)

    def stats(self) -> dict:
        with self._n_lock:
            n = dict(self._n)
        with self._cv:
            depth = len(self._queue)
            active = len(self._active())
            draining = self._draining
        return {
            "queue_depth": depth,
            "queue_cap": self.queue_cap,
            "role": self.role,
            "slots": self.num_slots,
            "slots_active": active,
            "slot_occupancy": round(active / self.num_slots, 4),
            "continuous": self.continuous,
            "max_seq_len": self.max_seq_len,
            "prefill_buckets": list(self.prefill_buckets),
            "kv_cache_bytes": self.kv_cache_bytes,
            "kv_live_bytes": self.kv_live_bytes,
            "slot_state_bytes": self.slot_state_bytes,
            "paged": {
                "page_tokens": self.page_tokens,
                "num_pages": self.num_pages,
                "pages_per_slot": self.pages_per_slot,
                "pages_free": self.kv.free_pages(),
                "pages_live": self.kv.live_pages(),
                "page_bytes": self.page_bytes,
                "latent_layers": len(self._latent_layers),
                "window": None if self.window is None else {
                    "window": self.window,
                    "num_pages": self.num_window_pages,
                    "pages_per_slot": self.window_pages_per_slot,
                    "pages_free": self.kv.free_pages("window"),
                    "pages_live": self.kv.live_pages("window"),
                    "page_bytes": self.kv.window_page_bytes,
                    "pages_released": n["window_pages_released"],
                    "pages_released_in_prefill":
                        n["window_pages_released_in_prefill"],
                },
                "prefill_chunk": self.prefill_chunk,
                "prefix_reuse": self.prefix_reuse,
                "prefix_index_entries": self.kv.prefix_entries,
                "prefix_hit_rate": round(
                    n["prefix_hits"] / max(n["prefills"], 1), 4),
            },
            "speculate": None if not self.speculate else {
                "spec_tokens": self.spec_tokens,
                "spec_ngram": self.spec_ngram,
                "drafts": n["spec_drafts"],
                "tokens_proposed": n["spec_tokens_proposed"],
                "tokens_accepted": n["spec_tokens_accepted"],
                "rollbacks": n["spec_rollbacks"],
                "acceptance_rate": round(
                    n["spec_tokens_accepted"]
                    / max(n["spec_tokens_proposed"], 1), 4),
            },
            "mesh": None if self.mesh is None
            else _describe_mesh(self.mesh),
            "kv_shard_axis": self.kv.kv_shard_axis,
            "draining": draining,
            "weights_version": self.weights_version,
            "counters": n,
            # the process's one writer of token streams (every engine
            # of the process reads the same figures)
            "stream_writer": stream_writer.stats(),
            # where this process's start-up went, by part and by program
            # (the process's, like the writer's: ``telemetry.py``)
            "startup": telemetry.startup_account(),
            "tokens_per_request": round(
                n["generated_tokens"] / max(n["served"], 1), 2),
            "generate_ms": self._h_gen.summary(),
            "prefill_ms": self._h_prefill.summary(),
            "decode_step_ms": self._h_step.summary(),
            "spec_verify_ms": self._h_verify.summary(),
            "ttft_ms": self._h_ttft.summary(),
            "inter_token_ms": self._h_itl.summary(),
        }

    def introspect(self) -> dict:
        """The generator half of ``/statusz``: stats + the decode
        executable manifest + achieved decode MFU."""
        return {
            "stats": self.stats(),
            "decode_manifest": self.decode_manifest(),
            "decode_mfu": self.decode_mfu(),
            "decode_executables": self._decode_exe.cache_info(),
        }
