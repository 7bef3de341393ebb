"""Reduction of a JAX profiler trace (``.xplane.pb``) to numbers.

What the trace of a TPU v5e holds (looked at by hand, PR 23): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
program execution, named ``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per
HLO instruction that ran, named by the instruction's text,
``%fusion.12 = ...``) and ``Async XLA Ops``; and one plane ``/host:CPU``
with a line per thread (runtime events, ``TraceAnnotation``s and, with the
Python tracer on, one event per Python call, named ``$file:line fn``).
All planes share one clock, nanoseconds from the start of the trace.

Busy is the union of the ``XLA Ops`` intervals of a device, cut to the
traced window; idle is the window minus busy.  Gaps are charged to the
most specific host event that covers most of the gap.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_ANNOTATION = "bench/traced_window"
_OP_NAME = re.compile(r"^%?([\w.\-]+)")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
# a Python call that waits (on a lock, a queue, a socket, the clock) spans a
# gap tightly without causing it: it owns a gap only if nothing else does
WAITING = re.compile(
    r"^\$.*(acquire|wait|sleep|select|poll|recv|accept|readinto|readline"
    r"| get$)")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _OP_NAME.match(text)
    return m.group(1) if m else text


def load(path: str) -> dict:
    """Planes of interest as plain lists of ``(start_s, end_s, name)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, e.name)
                        for e in line.events]
            devices[plane.name] = {"ops": lines.get("XLA Ops", []),
                                   "modules": lines.get("XLA Modules", [])}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.append((line.name, [
                    (e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    for e in line.events]))
    return {"devices": devices, "host": host}


def union(intervals) -> list:
    """Merged, sorted, disjoint ``(start, end)`` intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def traced_window(trace: dict):
    """The ``bench/traced_window`` annotation's interval; without one, the
    span of all device events."""
    for _, events in trace["host"]:
        for s, e, name in events:
            if name == WINDOW_ANNOTATION:
                return s, e
    starts = [s for d in trace["devices"].values() for s, _, _ in d["ops"]]
    ends = [e for d in trace["devices"].values() for _, e, _ in d["ops"]]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def _owner(gap, host_events):
    """The host event a device gap is charged to: among events that cover
    at least half of the gap, the shortest (the most specific) that is
    not a wait; failing that the shortest wait; if none covers half, the
    one that overlaps the gap most."""
    gs, ge = gap
    best = {False: None, True: None}
    best_any = None
    for s, e, name in host_events:
        ov = min(e, ge) - max(s, gs)
        if ov <= 0:
            continue
        if ov >= 0.5 * (ge - gs):
            waits = bool(WAITING.search(name))
            if best[waits] is None or e - s < best[waits][0]:
                best[waits] = (e - s, name)
        if best_any is None or ov > best_any[0]:
            best_any = (ov, name)
    for waits in (False, True):
        if best[waits] is not None:
            return best[waits][1]
    return best_any[1] if best_any is not None else "no_host_event"


def charge_gaps(gaps, host_events, longest: int = 400) -> dict:
    """Seconds of device idle by owning host event.  The longest gaps carry
    nearly all of the idle time; they are swept in time order against the
    host events sorted by start, so the cost is linear in the events."""
    owners = defaultdict(float)
    active, i = [], 0
    for gs, ge in sorted(sorted(gaps, key=lambda g: g[0] - g[1])[:longest]):
        while i < len(host_events) and host_events[i][0] < ge:
            active.append(host_events[i])
            i += 1
        active = [ev for ev in active if ev[1] > gs]
        owners[_owner((gs, ge), active)] += ge - gs
    return owners


def reduce(trace: dict, top: int = 10) -> dict:
    """Window, busy, idle, per-operation sums, per-module runs, exposed
    collective time and the longest gaps by owner.  Seconds; the busy,
    collective and exposed figures are averaged over the devices."""
    lo, hi = traced_window(trace)
    window = hi - lo
    if not trace["devices"]:
        raise ValueError("the trace holds no device plane")
    busy_s, coll_s, exposed_s = [], [], []
    op_sum = defaultdict(float)
    op_text = {}
    modules = defaultdict(list)
    first_gaps = None
    for dev in sorted(trace["devices"]):
        d = trace["devices"][dev]
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in d["ops"]
               if min(e, hi) > max(s, lo)]
        busy = union((s, e) for s, e, _ in ops)
        busy_s.append(total(busy))
        coll = union((s, e) for s, e, n in ops
                     if COLLECTIVE.match(op_name(n)))
        comp = union((s, e) for s, e, n in ops
                     if not COLLECTIVE.match(op_name(n)))
        coll_s.append(total(coll))
        exposed_s.append(total(subtract(coll, comp)))
        for s, e, n in ops:
            key = op_name(n)
            op_sum[key] += (e - s) / len(trace["devices"])
            op_text.setdefault(key, n)
        for s, e, n in d["modules"]:
            if lo <= s and e <= hi:
                modules[n].append((s, e))
        if first_gaps is None:
            first_gaps = subtract([(lo, hi)], busy)
    host_events = sorted(ev for _, events in trace["host"] for ev in events
                         if ev[2] != WINDOW_ANNOTATION
                         and ev[1] > lo and ev[0] < hi)
    owners = charge_gaps(first_gaps, host_events)
    n = len(busy_s)
    return {
        "window_s": window,
        "busy_s": sum(busy_s) / n,
        "collective_s": sum(coll_s) / n,
        "collective_exposed_s": sum(exposed_s) / n,
        "op_seconds": dict(op_sum),
        "op_text": op_text,
        "modules": dict(modules),
        "device_ops": [[k, v] for k, v in sorted(
            op_sum.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k.lstrip("$").replace(" ", "_"), v]
                      for k, v in sorted(
                          owners.items(), key=lambda kv: -kv[1])[:top]],
        "window": (lo, hi),
    }
