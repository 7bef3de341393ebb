"""Mean host time of one scheduler iteration, in ms: the duration of
each ``span`` (``generation/iteration``) that started inside the window,
less the parts of it in which the same thread sat in one of the ``less``
spans (``generation/token_fetch``, ``generation/prefill_fetch``: blocked
on the device).  Spans nest by thread and time, not by ``parent_id``: the
per-request spans keep their request's trace."""
import bisect
from collections import defaultdict


def read(ctx, span, less):
    waits = defaultdict(list)
    for s in ctx.get("spans", ()):
        if s.name in less:
            waits[s.tid].append((s.start, s.end))
    for runs in waits.values():
        runs.sort()
    host_ms = []
    for it in ctx.get("spans", ()):
        if it.name != span:
            continue
        runs = waits.get(it.tid, [])
        i = bisect.bisect_left(runs, (it.start,))
        waited = 0.0
        while i < len(runs) and runs[i][0] < it.end:
            waited += min(runs[i][1], it.end) - runs[i][0]
            i += 1
        host_ms.append((it.end - it.start - waited) * 1e3)
    return sum(host_ms) / len(host_ms) if host_ms else None
