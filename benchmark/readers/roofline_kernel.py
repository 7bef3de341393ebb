"""Roofline share of one kernel of the decode step: ``fn(cfg, *means,
itemsize)`` for the mean decode step of the traced seconds (``means`` of
the step spans' ``attrs``, ``roofline_span.step_means``) times the decode
module's runs in the trace, over the peak, over the device seconds of the
operations whose HLO text matches ``pattern`` (``xplane.reduce`` sums an
operation's time by its name over the whole trace: it keeps no time per
module, so the pattern has to name what only the kernel's calls hold, such
as its output's shape).  Nothing matching, or no such spans: nothing to
read."""
import re

from harness import item_sizes, load_module, resolve

split = load_module("readers", "module_time").split
step_means = load_module("readers", "roofline_span").step_means


def read(ctx, fn, peak, attrs, pattern, span="generation/decode_step"):
    t = ctx.get("trace")
    if not t:
        return None
    run, cfg = ctx["run"], ctx["cfg"]
    decode, _ = split(t)
    means = step_means(ctx, attrs, span)
    rx = re.compile(pattern)
    took_s = sum(sec for name, sec in t["op_seconds"].items()
                 if rx.search(t["op_text"][name]))
    if means is None or not decode or took_s <= 0:
        return None
    itemsize = item_sizes(ctx)
    return 100.0 * len(decode) * resolve(fn)(cfg, *means, itemsize) \
        / run.peaks[peak] / took_s
