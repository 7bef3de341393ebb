"""What a scheduler pass did with its time, from the program's own spans
of the measured window (``ctx["spans"]``: no profiler runs there).

Spans nest by thread and time, never by ``parent_id`` (the per-request
spans keep their request's trace).  ``what`` picks the number:

- ``offcpu_ms``: mean per ``generation/iteration`` of its wall time less
  the spans named in ``WAITS`` (blocked on the device) less its
  ``cpu_ms`` (the thread's CPU time): what the scheduler thread wanted
  to run and could not, because another thread held the interpreter or a
  lock.
- ``unnamed_ms``: mean per iteration of its self time, the wall time
  that none of its direct child spans covers.
- ``stream_cpu_pct``: 100 x sum of the iterations' ``stream_cpu_ms``
  (CPU time of the streaming handler threads since the pass before)
  over the window's wall time: the share of one interpreter the
  handlers take.
- ``pass_max_ms``: the longest iteration of the window less its waits;
  that pass's phases go to the run's log.

A program whose iteration spans lack the attribute a number needs
(``cpu_ms``, ``stream_cpu_ms``) gives None for it.  The mean pass by
phase (count, wall, self and CPU time a pass, by path of span names)
goes to the run's log once, and under it where the iteration's self
time lies: between which two of its direct children.
"""
from collections import Counter, defaultdict

ITERATION = "generation/iteration"
WAITS = ("generation/token_fetch", "generation/prefill_fetch")


def scheduler_spans(spans):
    """The spans of the thread that runs the scheduler loop, outermost
    first."""
    tids = Counter(s.tid for s in spans if s.name == ITERATION)
    if not tids:
        return []
    tid = tids.most_common(1)[0][0]
    return sorted((s for s in spans if s.tid == tid),
                  key=lambda s: (s.start, -s.end))


def window_ms(sched):
    """Wall time the iterations span, first start to last end, in ms:
    the window as the scheduler saw it (a pass is 4-30 ms of 40 s)."""
    its = [s for s in sched if s.name == ITERATION]
    if not its:
        return 0.0
    return (max(s.end for s in its) - min(s.start for s in its)) * 1e3


def passes(sched):
    """``[(iteration, [(path, span), ...])]``: each iteration with the
    spans nested inside it, ``path`` the names from its direct child
    down.  A span that outlives its parent (``generation/sequence``
    begins on this thread, detached) belongs to nothing."""
    out, stack, cur = [], [], None
    for s in sched:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        if s.name == ITERATION:
            stack, cur = [s], []
            out.append((s, cur))
            continue
        if not stack or s.end > stack[-1].end:
            continue
        stack.append(s)
        cur.append((tuple(x.name for x in stack[1:]), s))
    return out


def _ms(s):
    return (s.end - s.start) * 1e3


def host_ms(it, inner):
    """The iteration less the time it was blocked on the device."""
    return _ms(it) - sum(_ms(s) for _, s in inner if s.name in WAITS)


def self_ms(it, inner):
    return _ms(it) - sum(_ms(s) for path, s in inner if len(path) == 1)


def phase_table(table):
    """``{path: [count, wall ms, cpu ms or None]}`` over ``table``'s
    passes, paths in order of first appearance, with each path's self
    time."""
    rows = {}
    for _, inner in table:
        for path, s in inner:
            r = rows.setdefault(path, [0, 0.0, None])
            r[0] += 1
            r[1] += _ms(s)
            if "cpu_ms" in s.attrs:
                r[2] = (r[2] or 0.0) + s.attrs["cpu_ms"]
    kids = defaultdict(float)
    for path, r in rows.items():
        kids[path[:-1]] += r[1]
    return [(path, r[0], r[1], r[1] - kids.get(path, 0.0), r[2])
            for path, r in rows.items()]


def self_by_position(table):
    """An iteration's self time by where it lies: ``{(name before, name
    after): ms}`` over the gaps between its direct children (``|`` for
    the iteration's own start and end), largest first."""
    gaps = defaultdict(float)
    for it, inner in table:
        at, before = it.start, "|"
        for path, s in inner:
            if len(path) == 1:
                gaps[(before, s.name)] += (s.start - at) * 1e3
                at, before = s.end, s.name
        gaps[(before, "|")] += (it.end - at) * 1e3
    return sorted(gaps.items(), key=lambda kv: -kv[1])


def _say_table(say, title, table):
    n = len(table)
    wall = sum(_ms(it) for it, _ in table) / n
    cpu = [it.attrs["cpu_ms"] for it, _ in table if "cpu_ms" in it.attrs]
    say(f"{title} ({n} passes; ms a pass: wall, self, cpu; count a pass)")
    say(f"  {wall:8.3f} {sum(self_ms(*p) for p in table) / n:8.3f} "
        + (f"{sum(cpu) / n:8.3f}" if cpu else "       -")
        + f" {1.0:6.2f}  {ITERATION}")
    for path, count, ms, own, cpu_ms in phase_table(table):
        say(f"  {ms / n:8.3f} {own / n:8.3f} "
            + (f"{cpu_ms / n:8.3f}" if cpu_ms is not None else "       -")
            + f" {count / n:6.2f}  {'  ' * len(path)}{path[-1]}")
    for (before, after), ms in self_by_position(table)[:4]:
        say(f"  {ms / n:8.3f} of the iteration's self time lies between "
            f"{before} and {after}")


def _table(ctx):
    if "iter_account" not in ctx:
        table = ctx["iter_account"] = passes(
            scheduler_spans(ctx.get("spans", ())))
        if table and "run" in ctx:
            _say_table(ctx["run"].say, "mean pass by phase, measured "
                       "window, untraced", table)
    return ctx["iter_account"]


def read(ctx, what):
    table = _table(ctx)
    if not table:
        return None
    n = len(table)
    if what == "unnamed_ms":
        return sum(self_ms(*p) for p in table) / n
    if what == "offcpu_ms":
        if any("cpu_ms" not in it.attrs for it, _ in table):
            return None
        return sum(host_ms(it, inner) - it.attrs["cpu_ms"]
                   for it, inner in table) / n
    if what == "stream_cpu_pct":
        if any("stream_cpu_ms" not in it.attrs for it, _ in table):
            return None
        wall = window_ms([it for it, _ in table])
        return 100.0 * sum(it.attrs["stream_cpu_ms"]
                           for it, _ in table) / wall if wall else None
    if what == "pass_max_ms":
        worst = max(table, key=lambda p: host_ms(*p))
        if "run" in ctx and "pass_max_said" not in ctx:
            ctx["pass_max_said"] = True
            t0 = min(it.start for it, _ in table)
            _say_table(ctx["run"].say, f"the longest pass, "
                       f"{worst[0].start - t0:.3f} s into the window, "
                       f"attributes {worst[0].attrs}", [worst])
        return host_ms(*worst)
    raise ValueError(f"iter_account: no reading {what!r}")
