"""Share of the device's busy time, in %, that the traced window spent in
one kind of the generation engine's programs (``module_time.split``):
``decode``, the module that ran most often, or ``prefill``, every other
module that ran for a millisecond or more (an engine that prefills in
chunks: its chunk programs, one a rung).  A module run's time is its span
on the ``XLA Modules`` line, the gaps inside it included, so the two
kinds can add up to a little over the operations' own union."""
from harness import load_module

split = load_module("readers", "module_time").split


def read(ctx, which):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    decode, prefill = split(t)
    runs = decode if which == "decode" else prefill
    if not runs:
        return None
    return 100.0 * sum(e - s for s, e in runs) / t["busy_s"]
