"""Mean device time, in ms, of the generation engine's programs in the
traced window, from the trace's ``XLA Modules`` line: ``decode`` is the
module that ran most often, ``prefill`` every other module that ran for a
millisecond or more (one per prefill rung)."""

MIN_PREFILL_S = 1e-3


def split(trace):
    """``(decode_runs, prefill_runs)`` as lists of ``(start, end)``."""
    by_runs = sorted(trace["modules"].values(), key=lambda r: -len(r))
    if not by_runs:
        return [], []
    prefill = [(s, e) for runs in by_runs[1:] for s, e in runs
               if e - s >= MIN_PREFILL_S]
    return by_runs[0], sorted(prefill)


def read(ctx, which):
    t = ctx.get("trace")
    if not t:
        return None
    decode, prefill = split(t)
    runs = decode if which == "decode" else prefill
    if not runs:
        return None
    return 1e3 * sum(e - s for s, e in runs) / len(runs)
