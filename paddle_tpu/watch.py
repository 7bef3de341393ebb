"""What one thread reads and books while it traces a Program.

A trace does more than make a module: a lowering may read a flag
(``flags.flag_value``) and books stats (``StatValue.increase``:
``attention_lowered_pallas``, ``kv_pool_write_pages``, ...).  The program
store (``program_store.py``) keeps both beside the module it stores, to
compare the flags and add the stats again where a later process loads the
module instead of tracing.  ``with watching() as seen:`` records them for
THIS thread (``seen.flags`` name -> value, ``seen.stats`` name -> sum);
other threads' reads and increases are not recorded, nor are those made
inside ``paused()``, which the start-up account's listeners
(``compile_cache.py``) wrap around their own work: it runs on the tracing
thread in the middle of the trace and is no lowering's.

The two hot paths pay one read of :data:`active` while nobody watches.
"""
from __future__ import annotations

import contextlib
import threading

__all__ = ["watching", "paused", "current"]

active = 0                        # threads inside watching()
_lock = threading.Lock()
_tls = threading.local()


class Seen:
    __slots__ = ("flags", "stats")

    def __init__(self):
        self.flags = {}
        self.stats = {}


def current():
    """This thread's open record, or None."""
    return getattr(_tls, "seen", None)


@contextlib.contextmanager
def watching():
    global active
    outer, seen = current(), Seen()
    _tls.seen = seen
    with _lock:
        active += 1
    try:
        yield seen
    finally:
        with _lock:
            active -= 1
        _tls.seen = outer


@contextlib.contextmanager
def paused():
    outer = current()
    if outer is None:
        yield
        return
    _tls.seen = None
    try:
        yield
    finally:
        _tls.seen = outer
