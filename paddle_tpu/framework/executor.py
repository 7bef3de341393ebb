"""Executor: whole-block XLA compilation with a functional scope.

TPU-native replacement for the reference Executor
(framework/executor.cc:183,474 — a per-op interpreter loop) and its Python
front-end (python/paddle/fluid/executor.py:914).  Instead of dispatching a
kernel per op per step, `Executor.run` lowers the entire block into ONE
JAX function:

    fn(feed_values, state_values, step) -> (fetch_values, new_state_values)

jit-compiled once per (program, feed-signature, fetch-list) and cached.
`state` is the set of persistable variables (parameters, optimizer moments,
BN running stats, learning rate): the reference's mutable Scope becomes a
functional state-threading with donated buffers, which XLA updates in-place
in HBM.  Garbage collection (framework/garbage_collector.h) disappears:
intermediate lifetimes are managed by XLA's buffer assignment.

Randomness is stateless: a per-run step counter is folded into a base key
derived from program.random_seed (replaces cuRAND generator state).

Telemetry (paddle_tpu/telemetry.py; all opt-out via ``FLAGS_telemetry=0``):
every compiled run opens an ``executor/step`` span whose children name
the run's host phases in order: ``executor/prepare`` (feed conversion,
signature, cache lookup), ``startup/step_build`` (block analysis,
``lower_block``'s closure and ``jax.jit``, on a miss; a part of the
start-up account, ``telemetry.py``; until PR 53 a first span named
``executor/compile``), ``executor/gather_state`` (scope reads),
``executor/stage_feed`` (H2D staging), ``executor/compile`` (the entry's
one ahead-of-time ``lower().compile()``, at its first dispatch; jax's
trace, lowering and backend compile of the program fall under it as
``compile/trace`` / ``lower`` / ``backend`` spans that carry its
``program``), ``executor/dispatch`` (the compiled call),
``executor/commit_state`` (scope writes, efficiency gauges) and
``executor/fetch`` (blocking host reads); the host
wall time per run feeds the ``executor_step_host_ms`` histogram and the
``examples_per_sec`` gauge / heartbeat via ``telemetry.note_step``, the
feed double-buffer depth feeds the ``feed_ring_occupancy`` gauge, and
the run epilogue drives the periodic exporter flush
(``telemetry.maybe_flush``).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import costmodel as _costmodel
from .. import program_store as _program_store
from .. import telemetry as _telemetry
from ..compile_cache import ensure_compile_cache

from ..ops.registry import LowerContext, get_op_def, lower_op
from .core import (Block, Operator, Program, Variable, convert_dtype,
                   default_main_program, dtype_to_np)

__all__ = ["Executor", "FetchHandle", "AsyncRunResult", "Scope",
           "global_scope", "scope_guard"]

# hot-path stat handles resolved once (a per-step registry lookup would
# pay an import + two lock acquisitions per run)
from ..flags import flag_value  # noqa: E402
from ..monitor import monitor as _monitor  # noqa: E402
_STEP_STAT = _monitor.get("executor_run_steps")
_JIT_STAT = _monitor.get("executor_jit_builds")
_SKIP_STAT = _monitor.get("skipped_nonfinite_steps")
_CKPT_FAIL_STAT = _monitor.get("checkpoint_write_failures")
_HOST_SYNC_STAT = _monitor.get("host_syncs")
_GUARD_RES_STAT = _monitor.get("guard_resolutions")
# the step's argument that is donated: the state it rebinds
_DONATED = (1,)


# ---------------------------------------------------------------------------
# Lazy fetches: the async-pipeline user handle
# ---------------------------------------------------------------------------
class FetchHandle:
    """A fetch that stays on device until first host read.

    ``Executor.run(..., return_numpy=False)`` / ``run_async`` return these
    instead of blocking device arrays: the device value is held lazily and
    the host fences (``host_syncs``) only on the first ``numpy()`` /
    ``np.asarray`` / ``float()`` / ``block()``.  Reading a handle also
    resolves every pending non-finite-guard verdict up to its step (the
    step's completion proves the verdicts are ready), so guard callbacks
    never fire later than the data they explain.

    Device-side consumers never pay a sync: ``.value`` /
    ``__jax_array__`` hand back the raw device array, and ``shape`` /
    ``dtype`` / ``ndim`` read jax metadata without a transfer.
    """

    __slots__ = ("_value", "_exe", "_step", "_np")

    def __init__(self, value, exe: Optional["Executor"] = None,
                 step: int = 0):
        self._value = value
        self._exe = exe
        self._step = step
        self._np = None

    # -- device-side (never syncs) ------------------------------------------
    @property
    def value(self):
        """The underlying device array (no host fence)."""
        return self._value

    def __jax_array__(self):
        return self._value

    @property
    def shape(self):
        return tuple(np.shape(self._value))

    @property
    def dtype(self):
        return self._value.dtype if hasattr(self._value, "dtype") \
            else np.asarray(self._value).dtype

    @property
    def ndim(self):
        return len(self.shape)

    def __len__(self):
        return self.shape[0]

    def __getitem__(self, idx):
        return self._value[idx]

    def ravel(self):
        return self._value.ravel()

    def reshape(self, *shape):
        return self._value.reshape(*shape)

    def __repr__(self):
        state = "read" if self._np is not None else "pending"
        return (f"FetchHandle(step={self._step}, shape={self.shape}, "
                f"dtype={self.dtype}, {state})")

    def ready(self) -> bool:
        """Whether the device has already produced the value: a probe
        that neither waits nor counts as a host sync.  The device runs
        what it is given in order, so False also says that it is still
        busy with this step or one before it."""
        return self._np is not None or self._value.is_ready()

    # -- host-side (first call fences) --------------------------------------
    def numpy(self) -> np.ndarray:
        if self._np is None:
            _HOST_SYNC_STAT.increase()
            self._np = np.asarray(self._value)
            if self._exe is not None:
                self._exe._resolve_guard(upto=self._step)
        return self._np

    def block(self) -> "FetchHandle":
        """Fence without copying to host (device value stays primary)."""
        if self._np is None:
            import jax
            _HOST_SYNC_STAT.increase()
            jax.block_until_ready(self._value)
            if self._exe is not None:
                self._exe._resolve_guard(upto=self._step)
        return self

    def __array__(self, dtype=None):
        a = self.numpy()
        return a if dtype is None else a.astype(dtype, copy=False)

    def __float__(self):
        # numpy semantics: raises on a multi-element fetch instead of
        # silently returning element 0 (masking a missing reduction)
        return float(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __bool__(self):
        return bool(self.numpy())


class AsyncRunResult:
    """What ``Executor.run_async`` hands back: the step's lazy fetches
    plus a ``sync()`` fence.  Indexes/iterates like the list Executor.run
    returns."""

    __slots__ = ("fetches", "_exe", "_step")

    def __init__(self, fetches: List[FetchHandle], exe: "Executor",
                 step: int):
        self.fetches = fetches
        self._exe = exe
        self._step = step

    def __len__(self):
        return len(self.fetches)

    def __iter__(self):
        return iter(self.fetches)

    def __getitem__(self, i):
        return self.fetches[i]

    def sync(self) -> List[np.ndarray]:
        """Block until this step (and its guard verdict) has landed;
        returns the fetches as numpy."""
        self._exe.sync(upto=self._step)
        return [h.numpy() for h in self.fetches]


# ---------------------------------------------------------------------------
# Scope: name -> device array holder (reference framework/scope.h:52)
# ---------------------------------------------------------------------------
class Scope:
    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, Any] = {}
        self.parent = parent
        self._kids: List[Scope] = []

    def var(self, name: str):
        """Create-or-get, like reference Scope::Var."""
        return self._vars.setdefault(name, None)

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has_var(self, name: str) -> bool:
        s: Optional[Scope] = self
        while s is not None:
            if name in s._vars:
                return True
            s = s.parent
        return False

    def set_var(self, name: str, value):
        self._vars[name] = value

    def erase(self, names: Sequence[str]):
        for n in names:
            self._vars.pop(n, None)

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self._kids.append(kid)
        return kid

    def local_var_names(self) -> List[str]:
        return list(self._vars)

    def drop_kids(self):
        self._kids.clear()


_global_scope = Scope()
_scope_stack: List[Scope] = [_global_scope]


def global_scope() -> Scope:
    return _scope_stack[-1]


class scope_guard:
    def __init__(self, scope: Scope):
        self.scope = scope

    def __enter__(self):
        _scope_stack.append(self.scope)
        return self.scope

    def __exit__(self, *exc):
        _scope_stack.pop()
        return False


# ---------------------------------------------------------------------------
# Block analysis: classify vars into feed / state-in / state-out / temps
# ---------------------------------------------------------------------------

def _op_io(op, block):
    """Effective (reads, writes) of an op, descending into control-flow
    sub-blocks (conditional_block / cond2 / while) so state read only
    inside a branch/loop still threads through the compiled step."""
    reads = list(op.input_arg_names())
    writes = list(op.output_arg_names())
    prog = block.program
    for key in ("sub_block", "true_block", "false_block"):
        idx = op.attr(key, None)
        if idx is None:
            continue
        sub = prog.block(idx)
        sub_written: set = set()
        for o in sub.ops:
            r, w = _op_io(o, sub)
            reads.extend(n for n in r if n not in sub_written)
            sub_written.update(w)
    return reads, writes


def analyze_block(block: Block, feed_names: Sequence[str]):
    """Returns (state_in, state_out): persistable vars the compiled function
    must consume from / produce back into the scope."""
    written: set = set()
    state_in: List[str] = []
    state_out: List[str] = []
    seen_in: set = set(feed_names)
    seen_out: set = set()
    for op in block.ops:
        op_reads, _ = _op_io(op, block)
        for name in op_reads:
            if name in seen_in or name in written or not name:
                continue
            v = block._find_var_recursive(name)
            if v is not None and (v.persistable or v.is_data):
                state_in.append(name)
                seen_in.add(name)
            elif v is not None and not v.persistable and name not in written:
                # temp read before write inside the block: must come from
                # scope too (e.g. a fetched var from a previous partial run)
                state_in.append(name)
                seen_in.add(name)
        for name in op.output_arg_names():
            if not name:
                continue
            written.add(name)
            v = block._find_var_recursive(name)
            if v is not None and v.persistable and name not in seen_out:
                state_out.append(name)
                seen_out.add(name)
    return state_in, state_out


def lower_block(block: Block, env: Dict[str, Any], base_key,
                is_test: bool = False, mesh=None) -> LowerContext:
    ctx = LowerContext(block, env, base_key=base_key, is_test=is_test,
                       mesh=mesh,
                       amp=getattr(block.program, "_amp_lowering", None))
    for op in block.ops:
        if op.type in ("feed", "fetch"):
            continue
        lower_op(ctx, op)
    return ctx


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------
class _CacheEntry:
    """One compiled-program cache slot: the jitted step function plus
    its AOT-compiled executable and cost/memory **manifest**
    (paddle_tpu/costmodel.py).  The executable compiles exactly once —
    either here via ``lower().compile()`` (manifest captured) or, if
    the AOT path fails on this backend, lazily inside the jit call
    (``aot_failed`` latches the fallback so it is attempted once)."""

    __slots__ = ("fn", "mut_in", "const_in", "state_out", "guarded",
                 "compiled", "manifest", "aot_failed", "sig", "prev_t",
                 "store_digest")

    def __init__(self, fn, mut_in, const_in, state_out, guarded,
                 store_digest=None):
        self.fn = fn
        # the Program's half of its key in the program store, None where
        # there is no store (``program_store.py``)
        self.store_digest = store_digest
        self.mut_in = mut_in
        self.const_in = const_in
        self.state_out = state_out
        self.guarded = guarded
        self.compiled = None
        self.manifest = None
        self.aot_failed = False
        self.sig = None
        # per-ENTRY inter-dispatch clock: two programs interleaving
        # through one executor (train step + eval clone) must each
        # measure their own full cycle, not the gap since the other
        self.prev_t = None


class Executor:
    """`Executor(place)` — place is advisory; jax selects the backend.

    API mirrors reference fluid.Executor (python/paddle/fluid/executor.py):
    run(program, feed, fetch_list, scope, return_numpy).
    """

    def __init__(self, place=None):
        self.place = place
        self._cache: Dict[Tuple, Any] = {}
        self._step = 0
        # deferred non-finite guard: ring of (step, on-device ok scalar)
        # verdicts awaiting host resolution (see _resolve_guard)
        self._pending_guard: List[Tuple[int, Any]] = []
        # double-buffered feed staging: keep the last 2 steps' device_put
        # results alive so the H2D copy of step N+1 overlaps step N's
        # compute without recycling a buffer the in-flight step still reads
        self._feed_ring: List[Any] = []
        self._last_dispatch = None

    # -- public API ---------------------------------------------------------
    def run(self, program: Optional[Program] = None,
            feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[Sequence] = None,
            scope: Optional[Scope] = None,
            return_numpy: bool = True,
            use_program_cache: bool = True,
            on_launch=None):
        """``on_launch``: called with no argument the moment before the
        compiled step is handed to the device, after every host phase
        that precedes it (the generation engine asks there whether the
        device had run dry); only with ``FLAGS_telemetry`` on, and only on
        the compiled path."""
        if program is None:
            program = default_main_program()
        # CompiledProgram (data-parallel wrapper) delegates here
        if hasattr(program, "_compile_and_run"):
            return program._compile_and_run(self, feed, fetch_list, scope,
                                            return_numpy)
        if getattr(program, "_pipeline", None):
            return self._run_pipeline(program, feed, fetch_list, scope,
                                      return_numpy)
        feed = dict(feed or {})
        fetch_names = _fetch_names(fetch_list)
        scope = scope or global_scope()

        if flag_value("FLAGS_check_nan_inf"):
            _program_store.refuse("FLAGS_check_nan_inf runs the ops one by "
                                  "one and makes no module")
            return self._run_debug(program, feed, fetch_names, scope,
                                   return_numpy)

        if not _telemetry.enabled():
            return self._run_compiled(program, feed, fetch_names, scope,
                                      return_numpy, use_program_cache)[0]
        t0 = time.perf_counter()
        span = _telemetry.span_begin("executor/step", cpu=True,
                                     step=self._step + 1)
        try:
            out, examples = self._run_compiled(
                program, feed, fetch_names, scope, return_numpy,
                use_program_cache, on_launch)
        finally:
            _telemetry.span_end(span)
        _telemetry.note_step(self._step,
                             (time.perf_counter() - t0) * 1e3, examples)
        _telemetry.maybe_flush()
        return out

    def _run_compiled(self, program, feed, fetch_names, scope,
                      return_numpy, use_program_cache, on_launch=None):
        """The compiled-run body of :meth:`run`; returns (fetch result,
        examples in this step's feed) so the telemetry wrapper can feed
        the throughput gauge without re-inspecting the feed."""
        import jax

        # phase spans: a raise inside one is unwound by run()'s
        # span_end(executor/step), which closes everything above it
        phase = _telemetry.span_begin("executor/prepare")
        block = program.global_block()
        feed_arrays = _prepare_feed(block, feed)
        # .dtype directly: np.asarray on a device array would round-trip
        # the whole buffer to host just to read its dtype
        sig = tuple(
            (n, tuple(np.shape(a)),
             str(a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype))
            for n, a in feed_arrays.items())
        # guard every run of the bound training program that produces the
        # loss (fetched or not — env holds it either way); other programs
        # (startup, an interleaved eval clone) compile unguarded so an
        # eval NaN can't back off the loss scale or count as a skip
        guard_loss = getattr(self, "_guard_loss", None)
        if guard_loss is not None:
            gp = getattr(self, "_guard_program", None)
            if (gp is not None and program is not gp) or \
                    not block.has_var(guard_loss):
                guard_loss = None
        key = (program._uid, program._mod_count, sig, tuple(fetch_names),
               guard_loss)

        entry = self._cache.get(key) if use_program_cache else None
        _telemetry.span_end(phase)
        if entry is None:
            _JIT_STAT.increase()
            ensure_compile_cache()
            with _telemetry.startup_span("startup/step_build",
                                         program=program._uid,
                                         fetches=len(fetch_names)):
                entry = self._build(program, block, list(feed_arrays),
                                    fetch_names, guard_loss)
            if use_program_cache:
                self._cache[key] = entry
        fn, mut_in, const_in, state_out, guarded = \
            entry.fn, entry.mut_in, entry.const_in, entry.state_out, \
            entry.guarded

        def _val(name):
            val = scope.find_var(name)
            if val is None:
                raise RuntimeError(
                    f"variable {name!r} has no value in scope; did you run "
                    f"the startup program first?")
            return val

        phase = _telemetry.span_begin("executor/gather_state",
                                      vars=len(mut_in) + len(const_in))
        mut_vals = tuple(_val(n) for n in mut_in)
        const_vals = tuple(_val(n) for n in const_in)
        _telemetry.span_end(phase)
        phase = _telemetry.span_begin("executor/stage_feed")
        feed_vals = self._stage_feed(feed_arrays)
        _telemetry.span_end(phase)

        self._step += 1
        _STEP_STAT.increase()
        step = np.int32(self._step)
        # AOT-compile the entry at its first dispatch: same single XLA
        # compile the jit call would pay, but through lower().compile()
        # so the executable's cost/memory manifest is readable
        # (costmodel.executable_manifest -> cache_info / gauges)
        call = entry.compiled
        if call is None and not entry.aot_failed:
            call = self._aot_compile(entry, sig, feed_vals, mut_vals,
                                     const_vals, step, program._uid)
        if call is None:
            call = fn
        bench = flag_value("FLAGS_benchmark")
        if bench:
            _HOST_SYNC_STAT.increase()
            jax.block_until_ready(mut_vals)
            t0 = time.perf_counter()
        # the dispatch span carries the executable's HBM footprint, so
        # the Perfetto HBM counter track is attributable span-by-span
        # to the signature that was executing under it
        dattrs = {"step": self._step, "guarded": guarded}
        if entry.manifest and "peak_hbm_bytes" in entry.manifest:
            dattrs["peak_hbm_bytes"] = entry.manifest["peak_hbm_bytes"]
        dspan = _telemetry.span_begin("executor/dispatch", **dattrs)
        if on_launch is not None:
            on_launch()
        try:
            out_vals = call(feed_vals, mut_vals, const_vals, step)
        except (TypeError, ValueError):
            if call is not entry.compiled:
                raise
            # aval drift vs the AOT executable (argument validation
            # raises BEFORE execution, so donated inputs are intact):
            # fall back to the jit path, which recompiles per aval set
            entry.compiled, entry.aot_failed = None, True
            out_vals = fn(feed_vals, mut_vals, const_vals, step)
        if guarded:
            fetches, new_state, ok = out_vals
        else:
            fetches, new_state = out_vals
            ok = None
        _telemetry.span_end(dspan)
        phase = _telemetry.span_begin("executor/commit_state",
                                      vars=len(state_out))
        self._publish_efficiency(entry, new_state or fetches)
        if bench:
            t_dispatch = time.perf_counter() - t0
            _HOST_SYNC_STAT.increase()
            jax.block_until_ready((fetches, new_state))
            print(f"[FLAGS_benchmark] step {self._step}: "
                  f"{(time.perf_counter() - t0) * 1e3:.3f} ms "
                  f"(host dispatch {t_dispatch * 1e3:.3f} ms)")
        for name, val in zip(state_out, new_state):
            scope.set_var(name, val)
        _telemetry.span_end(phase)
        self._last_dispatch = new_state if new_state else fetches
        if guarded:
            # deferred verdict: keep the on-device scalar; the host learns
            # about a skipped step lazily — on fetch read, at the resolve
            # interval, at checkpoint time, or at close/sync
            self._pending_guard.append((self._step, ok))
            interval = int(flag_value("FLAGS_guard_resolve_interval") or 0)
            if interval > 0 and len(self._pending_guard) >= interval:
                self._resolve_guard()
        self._maybe_auto_checkpoint(program, scope)
        examples = 0
        if feed_arrays:
            shape = np.shape(next(iter(feed_arrays.values())))
            examples = int(shape[0]) if shape else 0
        return self._finish_fetches(fetches, return_numpy,
                                    resolve_guard=True), examples

    def _aot_compile(self, entry: "_CacheEntry", sig, feed_vals,
                     mut_vals, const_vals, step, program_uid=None):
        """Lower + compile the entry's step function at the concrete
        argument set and capture its executable manifest.  On any
        failure the entry latches ``aot_failed`` and the caller uses
        the plain jit path — observability must never break a step."""
        try:
            # (jax's three compile events of this program become
            # ``compile/*`` spans under this one, and copy ``program``)
            with _telemetry.trace_span("executor/compile",
                                       program=program_uid,
                                       step=int(step), aot=True):
                entry.compiled, entry.manifest = _costmodel.aot_compile(
                    entry.fn, feed_vals, mut_vals, const_vals, step,
                    signature=sig, store_digest=entry.store_digest,
                    donate_argnums=_DONATED)
            entry.sig = sig
        except Exception as e:
            entry.compiled, entry.aot_failed = None, True
            import logging
            logging.getLogger("paddle_tpu.executor").debug(
                "AOT compile unavailable (falling back to jit): %s", e)
            return None
        if entry.manifest is not None and _telemetry.enabled():
            _telemetry.log_event(
                "executable_manifest", step=int(step),
                **{k: v for k, v in entry.manifest.items()
                   if k != "signature"})
        return entry.compiled

    def _publish_efficiency(self, entry: "_CacheEntry", out_vals):
        """Per-step achieved MFU / HBM-bandwidth gauges: the entry's
        manifest (flops, bytes accessed per execution) over THIS
        entry's steady-state inter-dispatch interval.  The manifest
        covers the whole program, so the rate divides by the number of
        devices the dispatched outputs actually span (per-chip peaks in
        the denominator)."""
        if not _telemetry.enabled() or entry.manifest is None:
            return
        now = time.monotonic()
        prev, entry.prev_t = entry.prev_t, now
        if prev is None or now <= prev:
            return
        n_dev = 1
        try:
            first = out_vals[0] if out_vals else None
            ds = getattr(getattr(first, "sharding", None),
                         "device_set", None)
            if ds:
                n_dev = len(ds)
        except (TypeError, IndexError, AttributeError):
            pass  # ok: unsharded/opaque outputs count as one device
        _costmodel.publish_achieved(entry.manifest, 1.0 / (now - prev),
                                    n_devices=n_dev)

    def cache_info(self) -> dict:
        """Compiled-program inventory with per-entry manifests (the
        executor sibling of ``Predictor.cache_info``): one record per
        cache entry with its feed signature and cost/memory manifest
        summary (None when the backend exposes no analysis)."""
        entries = []
        for e in self._cache.values():
            if not isinstance(e, _CacheEntry):
                continue  # pipeline entries carry no manifest
            entries.append({
                "signature": None if e.sig is None else str(e.sig),
                "aot": e.compiled is not None,
                "manifest": _costmodel.manifest_summary(e.manifest),
            })
        return {"compiled": len(entries), "entries": entries}

    def _finish_fetches(self, fetches, return_numpy: bool,
                        resolve_guard: bool = False):
        """Common run epilogue: blocking numpy fetches (one logical fence
        per run — the first asarray blocks on the step, the rest copy out
        already-landed buffers) or lazy FetchHandles.  `resolve_guard`
        marks the paths where a blocking fetch read doubles as a
        guard-resolution point."""
        if return_numpy:
            if not fetches:
                return []
            _HOST_SYNC_STAT.increase()
            with _telemetry.trace_span("executor/fetch",
                                       n=len(fetches), step=self._step):
                out = [np.asarray(f) for f in fetches]
            if resolve_guard:
                self._resolve_guard(upto=self._step)
            return out
        return [FetchHandle(f, self, self._step) for f in fetches]

    def run_async(self, program: Optional[Program] = None,
                  feed: Optional[Dict[str, Any]] = None,
                  fetch_list: Optional[Sequence] = None,
                  scope: Optional[Scope] = None,
                  use_program_cache: bool = True) -> "AsyncRunResult":
        """Fully asynchronous step: dispatches the compiled step and
        returns immediately — no device→host fence anywhere on the path.
        The result holds lazy :class:`FetchHandle`\\ s plus a ``sync()``
        fence; a deferred non-finite guard verdict resolves on the first
        read (or at ``FLAGS_guard_resolve_interval`` / checkpoint /
        ``close``)."""
        handles = self.run(program, feed, fetch_list, scope,
                           return_numpy=False,
                           use_program_cache=use_program_cache)
        return AsyncRunResult(list(handles), self, self._step)

    def sync(self, upto: Optional[int] = None):
        """Host fence: block until dispatched work has completed and
        resolve pending non-finite-guard verdicts (all of them, or those
        up to step `upto`)."""
        import jax

        if self._last_dispatch is not None:
            _HOST_SYNC_STAT.increase()
            jax.block_until_ready(self._last_dispatch)
            self._last_dispatch = None
        self._resolve_guard(upto=upto)
        return self

    # -- deferred non-finite guard resolution -------------------------------
    def _resolve_guard(self, upto: Optional[int] = None):
        """Pull pending on-device ok-verdicts to the host (oldest first)
        and fire the skip-step bookkeeping — ``skipped_nonfinite_steps`` +
        guard callback with the ORIGINAL step id — exactly as if each had
        been checked synchronously at its own step."""
        pending = self._pending_guard
        if not pending:
            return
        if upto is None:
            take, rest = pending, []
        else:
            take = [p for p in pending if p[0] <= upto]
            if not take:
                return
            rest = [p for p in pending if p[0] > upto]
        self._pending_guard = rest
        _GUARD_RES_STAT.increase()
        _HOST_SYNC_STAT.increase()  # one fence resolves the whole batch
        import jax
        oks = jax.device_get([ok for _, ok in take])
        cb = getattr(self, "_guard_cb", None)
        for (step_id, _), okv in zip(take, oks):
            if not bool(okv):
                _SKIP_STAT.increase()
                if cb is not None:
                    cb(step_id)

    def resolve_nonfinite_guard(self):
        """Public fence for the deferred guard only (train_guard uses it
        before final checkpoints and on close)."""
        self._resolve_guard()

    # -- feed staging (double buffer) ---------------------------------------
    def _stage_feed(self, feed_arrays: Dict[str, Any]) -> Tuple:
        """Route numpy feeds through a 2-deep ``device_put`` ring
        (reader.stage_to_device): the H2D copy dispatches asynchronously
        and overlaps the still-running previous step, and the executor's
        jit call then binds already-device-resident arrays."""
        if not feed_arrays:
            return ()
        if not flag_value("FLAGS_feed_double_buffer"):
            return tuple(feed_arrays.values())
        from ..reader import stage_to_device

        staged = stage_to_device(feed_arrays)
        self._feed_ring.append(staged)
        if len(self._feed_ring) > 2:
            self._feed_ring.pop(0)
        # occupancy 2 = the ring is actually overlapping H2D with compute;
        # stuck at 1 means feeds are arriving slower than steps complete
        _telemetry.gauge_set("feed_ring_occupancy", len(self._feed_ring))
        return tuple(staged.values())

    # -- auto checkpoint ----------------------------------------------------
    def enable_auto_checkpoint(self, directory: str,
                               interval_steps: int = 100,
                               program=None, max_keep: int = 3):
        """Periodic checkpoint + resume (reference incubate
        fluid.incubate.checkpoint.auto_checkpoint + the trainer's
        failure-recovery contract): every `interval_steps` successful
        runs the persistable state is checkpointed; on enable, the
        newest *valid* checkpoint (if any) is restored — corrupt or
        torn ones are skipped — so a restarted process continues where
        it died."""
        from .. import checkpoint as ckpt

        program = program or default_main_program()
        self._auto_ckpt = {"dir": directory,
                           "interval": max(1, int(interval_steps)),
                           "program": program, "max_keep": max_keep}
        step, _extra = ckpt.restore_latest(directory, program=program)
        if step is not None:
            self._step = int(step)
        return step

    def disable_auto_checkpoint(self):
        self._auto_ckpt = None

    def _maybe_auto_checkpoint(self, program, scope):
        ac = getattr(self, "_auto_ckpt", None)
        if not ac or self._step % ac["interval"]:
            return
        # checkpoint is a guard-resolution point: the skip/backoff
        # bookkeeping must be final before the state is snapshotted
        self._resolve_guard()
        # only checkpoint runs of the bound training program: an
        # interleaved eval-program run must not snapshot a state set
        # without optimizer moments
        if program is not ac["program"]:
            return
        from .. import checkpoint as ckpt

        try:
            ckpt.save_checkpoint(ac["dir"], self._step,
                                 program=ac["program"], scope=scope,
                                 keep_last_n=ac["max_keep"])
        except OSError as e:
            # best-effort: a flaky store must not kill the training job
            # (the write already retried with backoff inside)
            _CKPT_FAIL_STAT.increase()
            import logging
            logging.getLogger("paddle_tpu.checkpoint").error(
                "auto-checkpoint at step %d failed: %s", self._step, e)

    # -- non-finite guard ---------------------------------------------------
    def set_nonfinite_guard(self, loss, callback=None, program=None):
        """Always-on cheap skip-step: compile the step so that whenever
        `loss` comes out non-finite, the state update is discarded
        *in-graph* (the old state is re-selected) — one extra scalar
        reduce per step, no host round-trip before the optimizer.
        `callback(step)` fires after each skipped step (train_guard uses
        it for the AMP loss-scale backoff).  With `program` given, only
        runs of that exact program are guarded (an eval clone carrying
        the same loss var stays unguarded)."""
        self._guard_loss = loss if isinstance(loss, str) else loss.name
        self._guard_cb = callback
        self._guard_program = program

    def clear_nonfinite_guard(self):
        # resolve BEFORE dropping the callback: verdicts still in flight
        # must fire their skip bookkeeping, not vanish
        self._resolve_guard()
        self._guard_loss = None
        self._guard_cb = None
        self._guard_program = None

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Dataset-driven training pass (reference executor.py:1642 —
        MultiTrainer + DeviceWorker over the in-memory channel).  The
        XLA-compiled step is the device worker; the dataset pipeline
        streams host batches into it."""
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        from ..reader import device_prefetch

        program = program or default_main_program()
        scope = scope or global_scope()
        fetch_names = _fetch_names(fetch_list)
        info = list(fetch_info or fetch_names)
        step = 0
        for batch in device_prefetch(dataset.batch_iter(), depth=2):
            out = self.run(program, feed=batch,
                           fetch_list=fetch_names or None, scope=scope)
            step += 1
            if debug and fetch_names and step % print_period == 0:
                vals = " ".join(
                    f"{n}={float(np.asarray(v).reshape(-1)[0]):.6f}"
                    for n, v in zip(info, out))
                print(f"step {step}: {vals}")
        self._resolve_guard()  # end of the pass: land deferred verdicts
        return step

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Same loop over a test-mode program (reference
        executor.py:1554)."""
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period)

    def _run_debug(self, program, feed, fetch_names, scope, return_numpy):
        """check_nan_inf mode: lower op-by-op on concrete (eager) arrays
        and raise, naming the op, on the first non-finite float output.

        Reference: framework/details/nan_inf_utils_detail.cc
        CheckVarHasNanOrInf under FLAGS_check_nan_inf — per-op host
        checks in exchange for speed (no jit here by design).
        """
        import jax
        import jax.numpy as jnp

        from ..ops.registry import LowerContext, lower_op

        block = program.global_block()
        feed_arrays = _prepare_feed(block, feed)
        state_in, state_out = analyze_block(block, list(feed_arrays))
        env: Dict[str, Any] = dict(feed_arrays)
        for n in state_in:
            v = scope.find_var(n)
            if v is None:
                raise RuntimeError(
                    f"variable {n!r} has no value in scope; did you run "
                    f"the startup program first?")
            env[n] = v
        self._step += 1
        base_key = jax.random.fold_in(
            jax.random.key(np.uint32(program.random_seed or 0)),
            np.int32(self._step))
        ctx = LowerContext(block, env, base_key=base_key,
                           amp=getattr(program, "_amp_lowering", None))
        from .selected_rows import densify, is_selected_rows

        for op in block.ops:
            if op.type in ("feed", "fetch"):
                continue
            lower_op(ctx, op)
            for name in op.output_arg_names():
                val = env.get(name)
                if is_selected_rows(val):
                    val = val.values
                if val is None:
                    continue
                # infer-vs-runtime shape drift check (round-5: a
                # conv2d_transpose stride bug shipped because infer
                # promised one shape and the lowering produced another
                # — the jit path only sees the lowered value)
                v = block._find_var_recursive(name)
                decl = getattr(v, "shape", None) if v is not None \
                    else None
                run_shape = tuple(jnp.shape(val))
                if (decl is not None and len(decl) == len(run_shape)
                        and all(int(d) >= 0 for d in decl)
                        and tuple(int(d) for d in decl) != run_shape):
                    raise RuntimeError(
                        f"shape-inference drift: op {op.type!r} output "
                        f"{name!r} declared {tuple(decl)} but lowered "
                        f"to {run_shape} (op index {op.idx})")
                if not jnp.issubdtype(jnp.asarray(val).dtype,
                                      jnp.floating):
                    continue
                if not bool(jnp.isfinite(val).all()):
                    raise FloatingPointError(
                        f"FLAGS_check_nan_inf: non-finite value in "
                        f"output {name!r} of op {op.type!r} "
                        f"(op index {op.idx})")
        for name in state_out:
            scope.set_var(name, densify(env[name]))
        fetches = [densify(env[n]) for n in fetch_names]
        return self._finish_fetches(fetches, return_numpy)

    # -- compilation --------------------------------------------------------
    def _build(self, program: Program, block: Block,
               feed_names: List[str], fetch_names: List[str],
               guard_loss: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        state_in, state_out = analyze_block(block, feed_names)
        # fetched temps must be emitted; ensure they exist in the block
        for n in fetch_names:
            block.var(n)  # raises if unknown

        out_set = set(state_out)
        mut_in = [n for n in state_in if n in out_set]
        const_in = [n for n in state_in if n not in out_set]
        seed = program.random_seed or 0

        def step_fn(feed_vals, mut_vals, const_vals, step):
            base_key = jax.random.fold_in(
                jax.random.key(np.uint32(seed)), step)
            env: Dict[str, Any] = {}
            env.update(zip(feed_names, feed_vals))
            env.update(zip(mut_in, mut_vals))
            env.update(zip(const_in, const_vals))
            lower_block(block, env, base_key)
            from .selected_rows import densify

            # SELECTED_ROWS fetches/state leave the step as dense
            # tensors (user-facing contract; reference fetch densifies
            # SelectedRows the same way)
            fetches = tuple(densify(env[n]) for n in fetch_names)
            new_state = tuple(densify(env[n]) for n in state_out)
            if guard_loss is not None:
                # non-finite skip-step: select the OLD state when the
                # loss went NaN/Inf (donated inputs stay readable here;
                # a scalar-cond where is free next to the matmuls)
                gval = env.get(guard_loss)
                ok = jnp.isfinite(densify(gval)).all() \
                    if gval is not None else jnp.asarray(True)
                old = dict(zip(mut_in, mut_vals))
                new_state = tuple(
                    jnp.where(ok, v, old[n]) if n in old else v
                    for n, v in zip(state_out, new_state))
                return fetches, new_state, ok
            return fetches, new_state

        # Donate only rebound state: params update in place in HBM.
        fn = jax.jit(step_fn, donate_argnums=_DONATED)
        return _CacheEntry(fn, mut_in, const_in, state_out,
                           guard_loss is not None,
                           _program_store.program_digest(
                               program, feed_names, fetch_names, guard_loss))

    def _run_pipeline(self, program, feed, fetch_list, scope, return_numpy):
        """Programs marked by PipelineOptimizer: microbatch-scan schedule
        (parallel/pipeline.py) replacing the reference PipelineTrainer/
        SectionWorker dispatch (fluid/executor.py:1209 trainer branch)."""
        from ..parallel.pipeline import build_pipeline_step

        feed = dict(feed or {})
        fetch_names = _fetch_names(fetch_list)
        scope = scope or global_scope()
        block = program.global_block()
        feed_arrays = _prepare_feed(block, feed)
        # .dtype directly: np.asarray on a device array would round-trip
        # the whole buffer to host just to read its dtype
        sig = tuple(
            (n, tuple(np.shape(a)),
             str(a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype))
            for n, a in feed_arrays.items())
        key = ("pipeline", program._uid, program._mod_count, sig,
               tuple(fetch_names))
        entry = self._cache.get(key)
        if entry is None:
            entry = build_pipeline_step(
                program, list(feed_arrays), fetch_names,
                program._pipeline["num_microbatches"])
            self._cache[key] = entry
        fn, mut_in, const_in, extra_out = entry

        def _val(name):
            v = scope.find_var(name)
            if v is None:
                raise RuntimeError(
                    f"variable {name!r} has no value in scope; did you "
                    f"run the startup program first?")
            return v

        mut_vals = tuple(_val(n) for n in mut_in)
        const_vals = tuple(_val(n) for n in const_in)
        self._step += 1
        fetches, new_mut, extra = fn(tuple(feed_arrays.values()),
                                     mut_vals, const_vals,
                                     np.int32(self._step))
        for n, v in zip(mut_in, new_mut):
            scope.set_var(n, v)
        for n, v in zip(extra_out, extra):
            scope.set_var(n, v)
        self._last_dispatch = new_mut
        return self._finish_fetches(fetches, return_numpy)

    def close(self):
        self._resolve_guard()
        self._cache.clear()
        self._feed_ring.clear()
        self._last_dispatch = None
        _telemetry.flush()  # final exporter write (no-op without a dir)


def _fetch_names(fetch_list) -> List[str]:
    names = []
    for f in fetch_list or []:
        if isinstance(f, Variable):
            names.append(f.name)
        elif isinstance(f, str):
            names.append(f)
        else:
            raise TypeError(f"bad fetch entry: {f!r}")
    return names


def _prepare_feed(block: Block, feed: Dict[str, Any]) -> Dict[str, Any]:
    """Canonical (sorted-name) feed order: the cache signature and the
    positional binding of values to the compiled step must agree regardless
    of the caller's dict insertion order."""
    out = {}
    for name, value in sorted(feed.items()):
        if hasattr(value, "dtype") and hasattr(value, "shape") and \
                not isinstance(value, np.ndarray):
            # device array: pass through — np.asarray would round-trip
            # the whole buffer to host (any dtype fixup runs on device)
            arr = value
        else:
            arr = np.asarray(value)
        if block.has_var(name):
            v = block.var(name)
            want = dtype_to_np(v.dtype)
            if np.dtype(arr.dtype) != want:
                arr = arr.astype(want)
            if v.shape is not None and len(v.shape) == arr.ndim + 1 and \
                    v.shape and v.shape[-1] == 1:
                # labels fed as (N,) for (N,1) vars, as the reference allows
                arr = arr.reshape(tuple(arr.shape) + (1,))
        out[name] = arr
    return out
