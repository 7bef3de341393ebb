"""Roofline share of the decode step of a program that writes what a step
did onto its span: ``fn(cfg, *means, itemsize)`` for the mean decode step
of the traced seconds, ``means`` being the means of ``attrs`` (attributes
of its ``generation/decode_step`` spans, in that order), over the peak,
over the decode module's mean device time (``module_time.split``: the
module that ran most often).  A program whose spans lack an attribute (an
earlier commit's) gives nothing to read."""
from harness import item_sizes, load_module, resolve

split = load_module("readers", "module_time").split


def step_means(ctx, attrs, span="generation/decode_step"):
    """Means of ``attrs`` over the traced seconds' step spans that carry
    them all, or None."""
    run = ctx["run"]
    steps = [s.attrs for s in ctx.get("trace_spans", ())
             if s.name == span and run.trace_t0 <= s.start <= run.trace_t1
             and all(a in s.attrs for a in attrs)]
    if not steps:
        return None
    return [sum(a[k] for a in steps) / len(steps) for k in attrs]


def read(ctx, fn, peak, attrs, span="generation/decode_step"):
    t = ctx.get("trace")
    if not t:
        return None
    run, cfg = ctx["run"], ctx["cfg"]
    decode, _ = split(t)
    means = step_means(ctx, attrs, span)
    if means is None or not decode:
        return None
    itemsize = item_sizes(ctx)
    took_s = sum(e - s for s, e in decode) / len(decode)
    return 100.0 * resolve(fn)(cfg, *means, itemsize) \
        / run.peaks[peak] / took_s
