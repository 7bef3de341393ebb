"""Roofline share of one kernel of the prefill programs: the sum of
``fn(cfg, <attr>, itemsize)`` over the prefills that ran in the traced
seconds (each ``generation/prefill`` span's ``attr``; a module run is
paired with the last such span that began before it, as
``roofline.read`` pairs them) over the peak, over the device seconds of
the operations whose HLO text matches ``pattern`` (``roofline_kernel``
says why a pattern).  Spans without the attribute (an earlier commit's),
no trace, or nothing matching: nothing to read."""
import bisect
import re

from harness import item_sizes, load_module, resolve

split = load_module("readers", "module_time").split


def read(ctx, fn, peak, attr, pattern, span="generation/prefill"):
    t = ctx.get("trace")
    if not t:
        return None
    run, cfg = ctx["run"], ctx["cfg"]
    _, prefill = split(t)
    spans = sorted((s.start - t["to_monotonic"], s.attrs[attr])
                   for s in ctx.get("trace_spans", ())
                   if s.name == span and attr in s.attrs)
    rx = re.compile(pattern)
    took_s = sum(sec for name, sec in t["op_seconds"].items()
                 if rx.search(t["op_text"][name]))
    if not spans or not prefill or took_s <= 0:
        return None
    itemsize = item_sizes(ctx)
    starts = [s for s, _ in spans]
    needed = 0.0
    for s, _ in prefill:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0:
            needed += resolve(fn)(cfg, spans[i][1], itemsize)
    return 100.0 * needed / run.peaks[peak] / took_s
