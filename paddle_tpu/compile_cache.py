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

Child processes (fleet replicas) inherit the environment and resolve
the same directory.  jax's own thresholds decide what is worth
caching (programs that took about a second or more to compile).  Hits
feed the ``compile_cache_hits`` stat from jax's
``/jax/compilation_cache/cache_hits`` monitoring event — counted
process-wide, whichever layer compiled.
"""
from __future__ import annotations

import os
import threading
from typing import Optional

from .monitor import monitor as _monitor

__all__ = ["ENV_VAR", "DEFAULT_DIR", "ensure_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_HIT_STAT = _monitor.get("compile_cache_hits")
_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# jax's compilation-cache config is process-global, so the latch is too
_lock = threading.Lock()
_active_dir = None
_listening = False


def _on_event(event, **_kw):
    if event == _HIT_EVENT:
        _HIT_STAT.increase()


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
            _listening = True
        placed = os.environ.get(ENV_VAR)
        if placed:
            return placed
        if jax.default_backend() == "cpu":
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
