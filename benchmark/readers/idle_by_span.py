"""Device idle of the traced window by the program's phase spans, in % of
the window.

Gaps are the complement, between the first and the last program run the
reduced trace holds (``trace["modules"]``, runs that lie wholly inside
``trace["window"]``), of the union of those runs.  Shifted onto the
host's monotonic clock (``trace["to_monotonic"]``), each gap's seconds go,
pro rata, to the deepest span of the scheduler thread in
``ctx["trace_spans"]`` that covers them; spans nest by thread and time.
That span's *phase* is the nearest ``generation/`` span at or above it
(``executor/dispatch`` beneath ``generation/decode_dispatch`` counts for
the decode side, beneath ``generation/prefill`` for the prefill side);
seconds under no span are the phase ``(none)``.  ``read`` returns the
share of the phases its metric's file lists.

The edges of the window, before the first run and after the last, are
printed as ``(window edge)`` and belong to no metric: a run that straddles
an edge is not among ``modules``, so those seconds may be busy.  The
whole table goes to the run's log once, by ``phase > deepest span``.
"""
from collections import Counter, defaultdict

import xplane

NO_SPAN = "(none)"
EDGE = "(window edge)"
SCHEDULER_MARKS = ("generation/iteration", "generation/decode_step")
# begun on the scheduler thread but detached: it outlives every iteration
DETACHED = ("generation/sequence",)


def scheduler_spans(spans):
    """The ``generation/`` and ``executor/`` spans of the thread that runs
    the scheduler loop, sorted outermost first."""
    tids = Counter(s.tid for s in spans if s.name in SCHEDULER_MARKS)
    if not tids:
        return []
    tid = tids.most_common(1)[0][0]
    return sorted(
        (s for s in spans if s.tid == tid and s.name not in DETACHED
         and s.name.startswith(("generation/", "executor/"))),
        key=lambda s: (s.start, -s.end))


def segments(spans):
    """Disjoint ``(start, end, phase, deepest)`` pieces, in time order:
    every instant some span covers, with the deepest span covering it."""
    out, stack = [], []

    def emit(upto):
        # the top of the stack owns the time from its cursor to `upto`
        top = stack[-1]
        if upto > top[2]:
            phase = next((s.name for s, _, _ in reversed(stack)
                          if s.name.startswith("generation/")), NO_SPAN)
            out.append((top[2], upto, phase, top[0].name))
            top[2] = upto

    for s in spans:
        while stack and stack[-1][1] <= s.start:
            emit(stack[-1][1])
            end = stack.pop()[1]
            if stack:
                stack[-1][2] = end
        if stack and s.end > stack[-1][1]:
            continue                  # not nested: no owner of its own
        if stack:
            emit(s.start)
        stack.append([s, s.end, s.start])
    while stack:
        emit(stack[-1][1])
        end = stack.pop()[1]
        if stack:
            stack[-1][2] = end
    return out


def table(ctx):
    """``{(phase, deepest): idle seconds}`` for the traced window."""
    t = ctx["trace"]
    shift = t["to_monotonic"]
    lo, hi = (x + shift for x in t["window"])
    busy = xplane.union((s + shift, e + shift)
                        for runs in t["modules"].values() for s, e in runs)
    rows = defaultdict(float)
    if not busy:
        rows[(EDGE, EDGE)] = hi - lo
        return rows
    first, last = busy[0][0], busy[-1][1]
    rows[(EDGE, EDGE)] = (first - lo) + (hi - last)
    gaps = xplane.subtract([(first, last)], busy)
    segs = segments(scheduler_spans(ctx.get("trace_spans", ())))
    i = 0
    for gs, ge in gaps:
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        covered, k = 0.0, i
        while k < len(segs) and segs[k][0] < ge:
            ov = min(segs[k][1], ge) - max(segs[k][0], gs)
            if ov > 0:
                rows[segs[k][2:]] += ov
                covered += ov
            k += 1
        rows[(NO_SPAN, NO_SPAN)] += (ge - gs) - covered
    return rows


def read(ctx, phases):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    if "idle_by_span" not in ctx:
        rows = ctx["idle_by_span"] = table(ctx)
        say = ctx["run"].say
        say(f"device idle by phase span, seconds of {t['window_s']:.3f} "
            f"traced (gaps between program runs):")
        for (phase, deepest), sec in sorted(rows.items(),
                                            key=lambda kv: -kv[1]):
            name = phase if deepest == phase else f"{phase} > {deepest}"
            say(f"  {sec:9.6f} s {100 * sec / t['window_s']:7.3f} %  {name}")
        say(f"  {sum(rows.values()):9.6f} s in all; the operations' own "
            f"union leaves {t['window_s'] - t['busy_s']:.6f} s idle")
    rows = ctx["idle_by_span"]
    sec = sum(v for (phase, _), v in rows.items() if phase in phases)
    return 100.0 * sec / t["window_s"]
