"""Roofline share of a block-diffusion pass: ``fn(cfg, experts_touched,
live_positions, rows, itemsize)`` for the mean pass of the traced seconds
(those attributes of its ``generation/decode_step`` spans: the experts
that got a row, the positions its live slots attended, its live rows)
over the peak, over the pass module's mean device time
(``module_time.split``: the module that ran most often).  A program
without such spans (one token a step) gives nothing to read."""
from harness import item_sizes, load_module, resolve

split = load_module("readers", "module_time").split
ATTRS = ("experts_touched", "live_positions", "rows")


def read(ctx, fn, peak, span="generation/decode_step"):
    t = ctx.get("trace")
    if not t:
        return None
    run, cfg = ctx["run"], ctx["cfg"]
    passes, _ = split(t)
    steps = [s.attrs for s in ctx.get("trace_spans", ())
             if s.name == span and run.trace_t0 <= s.start <= run.trace_t1
             and all(a in s.attrs for a in ATTRS)]
    if not steps or not passes:
        return None
    mean = [sum(a[k] for a in steps) / len(steps) for k in ATTRS]
    itemsize = item_sizes(ctx)
    took_s = sum(e - s for s, e in passes) / len(passes)
    return 100.0 * resolve(fn)(cfg, *mean, itemsize) \
        / run.peaks[peak] / took_s
