"""Where the persistent XLA compilation cache lives.

One rule, applied wherever the program first compiles (executor,
``Predictor``, ``GenerationEngine``, ``build_sharded_step``,
``chip_smoke.py``) through :func:`ensure_compile_cache`:

* ``JAX_COMPILATION_CACHE_DIR`` set — the cache was placed from
  outside.  jax reads the variable itself; this module sets no
  directory on that branch.
* unset, on an accelerator — one fixed directory inside the checkout,
  :data:`DEFAULT_DIR` (``<repo>/.jax_cache``, git-ignored).  The path
  is part of jax's cache key, so it never carries a temp name, a pid
  or a time: a directory that moves never hits.
* unset, on the CPU — no persistent cache.  An XLA:CPU executable is
  tied to the CPU features of the machine that compiled it (XLA warns
  of SIGILL when it loads one elsewhere), CPU programs compile in
  seconds, and a checkout's directory travels with the tree.

**The program store** (``program_store.py``, PR 60) lives beside the cache
by the same rule and no other: ``<that directory>/programs/`` holds the
lowered module of every Program's step (``jax.export``), which a later
process loads in place of tracing the Program and lowering its kernels;
jax's cache lists and evicts only the ``*-cache`` files of its directory,
never the subdirectory.  No cache (the third branch), no store: no file is
made and ``costmodel.aot_compile`` is ``jitted.lower(*args).compile()``.

Child processes (fleet replicas) inherit the environment and resolve
the same directory.  jax's own thresholds decide what is worth
caching (programs that took about a second or more to compile).  Hits
feed the ``compile_cache_hits`` stat from jax's
``/jax/compilation_cache/cache_hits`` monitoring event — counted
process-wide, whichever layer compiled.

The same listeners give the start-up account (``telemetry.py``) its
``compile/`` family, whoever compiles (the executor, a driver's own
``fn.lower(...).compile()``, an eager op): jax reports, with start and end
and the jitted function's name, the trace to a jaxpr
(``compile/trace``, a Pallas kernel's body included), the jaxpr's lowering
to StableHLO (``compile/lower``, where a Pallas kernel is lowered to
Mosaic; a ``jit`` traced inside another's trace or lowering has a
``compile/trace`` of its own only when it took 5 ms or more, and is
otherwise a part of the event it is inside) and the backend's compile
(``compile/backend``: XLA's compile **or** the persistent cache's read).
Each becomes a span made after the fact on the thread that compiled,
under the span open there
(``executor/compile``, a scheduler's phase inside a window), with
``fun_name`` and, copied from the nearest open span that carries them,
``program`` (the executor's program uid), ``kind`` and ``bucket`` (the
engine's ``startup/warm_program``): every Program is jitted as ``step_fn``,
so the name alone does not say which.  A backend span that asked the cache
carries ``cache_hit`` 0 / 1 and ``retrieval_ms``; one that asked and did
not find feeds ``compile_cache_misses``.  :func:`ensure_compile_cache`
also times the device client's start where the program touches it first
(``startup/backend_init``).  With ``FLAGS_telemetry=0`` no span is made
and ``compile_cache_misses`` stays 0.
"""
from __future__ import annotations

import functools
import os
import threading
from typing import Optional

from . import telemetry as _telemetry
from . import watch as _watch
from .monitor import monitor as _monitor

__all__ = ["ENV_VAR", "DEFAULT_DIR", "ensure_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_HIT_STAT = _monitor.get("compile_cache_hits")
_MISS_STAT = _monitor.get("compile_cache_misses")
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_ASKED_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_NESTED_TRACE_MIN_S = 0.005
_SPANS = {
    _TRACE_EVENT: "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
# which program a ``step_fn`` is: copied from the spans open on the thread
_INHERIT = ("program", "kind", "bucket")

# jax's compilation-cache config is process-global, so the latch is too
_lock = threading.Lock()
_active_dir = None
_listening = False
_backend_timed = False
# what the cache said to the backend compile in progress on this thread:
# jax's cache events fire inside the backend event's interval
_tls = threading.local()


def _unwatched(listener):
    """The listeners run on the compiling thread in the middle of a trace;
    the flags they read and the stats they book are no lowering's
    (``watch.py``)."""
    @functools.wraps(listener)
    def quiet(*args, **kw):
        if not _watch.active:
            return listener(*args, **kw)
        with _watch.paused():
            return listener(*args, **kw)
    return quiet


@_unwatched
def _on_event(event, **_kw):
    if event == _HIT_EVENT:
        _HIT_STAT.increase()
        asked = getattr(_tls, "asked", None)
        if asked is not None:
            asked["cache_hit"] = 1
    elif event == _ASKED_EVENT:
        # (jax "asks" whenever caching is enabled, placed or not)
        import jax

        if jax.config.jax_compilation_cache_dir:
            _tls.asked = {"cache_hit": 0, "retrieval_ms": 0.0}


@_unwatched
def _on_duration(event, secs, **_kw):
    if event == _RETRIEVAL_EVENT:
        asked = getattr(_tls, "asked", None)
        if asked is not None:
            asked["retrieval_ms"] = round(secs * 1e3, 3)


@_unwatched
def _on_begin(event, _start, **_kw):
    """(jax reports an event's start as a scalar.)  How many compile
    events are open on this thread: an inner ``jit``'s trace ends before
    its outer one's, and a lowering rule traces jnp functions of its
    own."""
    if event in _SPANS and _telemetry.enabled():
        _tls.open = getattr(_tls, "open", 0) + 1


@_unwatched
def _on_time_span(event, start, end, fun_name=None, **_kw):
    if not _telemetry.enabled():
        return
    name = _SPANS.get(event)
    if name is None:
        return
    attrs = {"fun_name": fun_name}
    inside = _tls.open = max(getattr(_tls, "open", 1) - 1, 0)
    # a model's trace and its lowering hold thousands of jnp functions'
    # own traces: one stays a part of the event it is inside unless it is
    # worth a line (a jitted kernel wrapper)
    if inside and name == "compile/trace" \
            and end - start < _NESTED_TRACE_MIN_S:
        return
    if name == "compile/backend":
        asked, _tls.asked = getattr(_tls, "asked", None), None
        if asked is not None:
            attrs.update(asked)
            if not asked["cache_hit"]:
                _MISS_STAT.increase()
    # jax stamps with time.time(): onto the span clock
    off = _telemetry._EPOCH_OFFSET
    _telemetry.span_record(name, start - off, end - off, inherit=_INHERIT,
                           **attrs)


def _backend(jax) -> str:
    """``jax.default_backend()``; the first call of the process is timed
    as ``startup/backend_init``: the device client's start, where the
    program is the first to touch it."""
    global _backend_timed
    if _backend_timed:
        return jax.default_backend()
    _backend_timed = True
    with _telemetry.startup_span("startup/backend_init") as span:
        platform = jax.default_backend()
        span.attrs.update(platform=platform, devices=jax.device_count())
    return platform


def ensure_compile_cache() -> Optional[str]:
    """Make sure this process compiles against the persistent cache;
    returns the directory it lives in (None: the CPU, no cache).  Call
    it where a compile is about to happen — it asks jax for the default
    backend.  Idempotent and cheap after the first call."""
    global _active_dir, _listening
    import jax

    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_time_span_listener(_on_time_span)
            jax.monitoring.register_scalar_listener(_on_begin)
            _listening = True
        placed = os.environ.get(ENV_VAR)
        if placed:
            return placed
        if _backend(jax) == "cpu":
            return None
        if _active_dir != DEFAULT_DIR:
            from jax.experimental.compilation_cache import \
                compilation_cache as cc

            os.makedirs(DEFAULT_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
            # jax latches the cache at the first compile of the process;
            # one may already have happened (an eager op at import)
            cc.reset_cache()
            _active_dir = DEFAULT_DIR
        return DEFAULT_DIR
