"""Roofline share of one kernel over EVERY program of the traced seconds
that calls it, the decode step and the prefill rungs alike: ``fn(cfg,
*means, itemsize)`` for the mean decode step (``means`` of the step
spans' ``attrs``, ``roofline_span.step_means``) times the decode module's
runs, PLUS the same ``fn`` of the mean prefill (the means of the same
``attrs`` over the ``prefill_span`` spans, which carry what a prompt's
programs counted) times the prefill modules' runs, over the peak, over
the device seconds of the operations whose HLO text matches ``pattern``.
``xplane.reduce`` sums an operation's seconds by its name over the whole
trace and the programs' calls of one kernel bear the same names, so the
seconds cannot be cut to one program's (``roofline_kernel`` says so):
here the bytes cover the calls the seconds cover.  ``fn`` is linear in
its attributes, so the mean's bytes times the runs are the runs' bytes.
Spans without an attribute (an earlier commit's prefill spans), no
trace, or nothing matching: nothing to read."""
import re

from harness import item_sizes, load_module, resolve

split = load_module("readers", "module_time").split
step_means = load_module("readers", "roofline_span").step_means


def read(ctx, fn, peak, attrs, pattern, span="generation/decode_step",
         prefill_span="generation/prefill_fetch"):
    t = ctx.get("trace")
    if not t:
        return None
    run, cfg = ctx["run"], ctx["cfg"]
    decode, prefill = split(t)
    rx = re.compile(pattern)
    took_s = sum(sec for name, sec in t["op_seconds"].items()
                 if rx.search(t["op_text"][name]))
    itemsize = item_sizes(ctx)
    needed = 0.0
    for runs, name in ((decode, span), (prefill, prefill_span)):
        if not runs:
            continue
        means = step_means(ctx, attrs, name)
        if means is None:
            return None
        needed += len(runs) * resolve(fn)(cfg, *means, itemsize)
    if needed <= 0 or took_s <= 0:
        return None
    return 100.0 * needed / run.peaks[peak] / took_s
