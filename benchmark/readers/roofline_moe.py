"""Roofline share of a decode step whose weight bytes depend on the
routing: ``fn(cfg, experts_touched, positions_full, positions_window,
itemsize)`` for the mean decode step of the traced seconds (the
``experts_touched``, ``live_positions`` and ``live_positions_window``
attributes of its ``generation/decode_step`` spans) over the peak, over
the decode module's mean device time (``module_time.split``)."""
from harness import item_sizes, load_module, resolve

split = load_module("readers", "module_time").split
ATTRS = ("experts_touched", "live_positions", "live_positions_window")


def read(ctx, fn, peak, span="generation/decode_step"):
    t = ctx.get("trace")
    if not t:
        return None
    run, cfg = ctx["run"], ctx["cfg"]
    decode, _ = split(t)
    steps = [s.attrs for s in ctx.get("trace_spans", ())
             if s.name == span and run.trace_t0 <= s.start <= run.trace_t1
             and all(a in s.attrs for a in ATTRS)]
    if not steps or not decode:
        return None
    mean = [sum(a[k] for a in steps) / len(steps) for k in ATTRS]
    itemsize = item_sizes(ctx)
    took_s = sum(e - s for s, e in decode) / len(decode)
    return 100.0 * resolve(fn)(cfg, *mean, itemsize) \
        / run.peaks[peak] / took_s
