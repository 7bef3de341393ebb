"""Mean duration, in ms, of the host spans named ``span`` that lie inside
a span named ``inside`` of the same thread (``executor/step`` inside
``generation/decode_step``: what one decode dispatch costs the host in
the executor).  Both started inside the window."""
import bisect
from collections import defaultdict


def read(ctx, span, inside):
    outer = defaultdict(list)
    for s in ctx.get("spans", ()):
        if s.name == inside:
            outer[s.tid].append((s.start, s.end))
    for runs in outer.values():
        runs.sort()
    ms = []
    for s in ctx.get("spans", ()):
        if s.name != span:
            continue
        runs = outer.get(s.tid, [])
        i = bisect.bisect_right(runs, (s.start, float("inf"))) - 1
        if i >= 0 and s.end <= runs[i][1]:
            ms.append((s.end - s.start) * 1e3)
    return sum(ms) / len(ms) if ms else None
