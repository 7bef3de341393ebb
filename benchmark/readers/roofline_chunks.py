"""Roofline share of the generation engine's prefill CHUNK programs:
``roofline.read``'s ``per: prefill`` pairing for an engine that prefills
in chunks.  A module run of the trace's ``XLA Modules`` line that is not
the decode step's (``module_time.split``) is paired with the last
``generation/prefill_chunk`` span that began before it (one scheduler
thread dispatches them in order); the work is the sum of ``fn(cfg,
tokens, base)`` over the paired spans (the chunk's REAL rows, not its
rung, at the position it ran from), over the peak, over the runs' device
time.  No trace, no such spans (a program that prefills whole prompts)
or no such runs: nothing to read."""
import bisect

from harness import load_module, resolve

split = load_module("readers", "module_time").split


def read(ctx, fn, peak, span="generation/prefill_chunk"):
    t = ctx.get("trace")
    if not t:
        return None
    cfg = ctx["cfg"]
    _, chunks = split(t)
    spans = sorted((s.start - t["to_monotonic"], s.attrs["tokens"],
                    s.attrs["base"])
                   for s in ctx.get("trace_spans", ())
                   if s.name == span and "tokens" in s.attrs
                   and "base" in s.attrs)
    if not spans or not chunks:
        return None
    starts = [s[0] for s in spans]
    need = resolve(fn)
    needed = took_s = 0.0
    for s, e in chunks:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0:
            continue
        needed += need(cfg, spans[i][1], spans[i][2])
        took_s += e - s
    if took_s <= 0:
        return None
    return 100.0 * needed / ctx["run"].peaks[peak] / took_s
