"""Roofline share of the generation engine's programs, from the trace's
``XLA Modules`` line (``module_time.split``) and the program's host spans.

The metric's file names the arithmetic: ``fn`` is ``<module>.<function>``
of a module under ``benchmark/`` that gives the operations or bytes the
work needs, ``peak`` the key of ``peaks.json`` they are held against, and
``per`` how work and device time are paired.  The item sizes are by class (weights, pages,
slot state), as the harness observed them on the engine that ran
(``harness.item_sizes``).

* ``per: decode_step``: ``fn(cfg, live_kv_tokens, itemsize)`` for one
  step (the live positions are the measured window's mean of the
  ``serving_kv_pages_live`` gauge) over the peak, over the decode module's
  mean device time.
* ``per: prefill``: the sum of ``fn(cfg, n_tokens)`` over the prefills
  that ran in the traced window (each ``generation/prefill`` span's real
  token count, not its bucket) over the peak, over their device time.  A
  module run is paired with the last prefill span that began before it:
  one scheduler thread dispatches them in order.
"""
import bisect

from harness import item_sizes, load_module, resolve

split = load_module("readers", "module_time").split


def read(ctx, per, fn, peak):
    t = ctx.get("trace")
    if not t:
        return None
    cfg, gen = ctx["cfg"], ctx["engine"]
    need, peak = resolve(fn), ctx["run"].peaks[peak]
    decode, prefill = split(t)
    if per == "decode_step":
        live = [g["serving_kv_pages_live"] for g in ctx["gauges"]]
        if not live or not decode:
            return None
        tokens = gen.page_tokens * sum(live) / len(live)
        itemsize = item_sizes(ctx)
        took_s = sum(e - s for s, e in decode) / len(decode)
        return 100.0 * need(cfg, tokens, itemsize) / peak / took_s
    if per == "prefill":
        spans = sorted((s.start - t["to_monotonic"], s.attrs["tokens"])
                       for s in ctx["trace_spans"]
                       if s.name == "generation/prefill")
        starts = [s for s, _ in spans]
        needed = took_s = 0.0
        for s, e in prefill:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0:
                continue
            needed += need(cfg, spans[i][1])
            took_s += e - s
        if took_s <= 0:
            return None
        return 100.0 * needed / peak / took_s
    raise ValueError(f"unknown pairing {per!r}")
