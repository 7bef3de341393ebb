"""Mean device time, in ms, of the generation engine's programs in the
traced window, from the trace's ``XLA Modules`` line: ``decode`` is the
module that ran most often among those whose mean run is a millisecond or
more, ``prefill`` every run of a millisecond or more of the other modules
(one per prefill rung).  The millisecond tells the step from the
one-microsecond programs that run as often as it does (the reshape that
carries its tokens, PR 45): in a window with no finished prefill the run
counts tie, and a roofline over the reshape's time reads millions of
percent."""

MIN_PREFILL_S = 1e-3


def split(trace):
    """``(decode_runs, prefill_runs)`` as lists of ``(start, end)``."""
    by_runs = sorted(trace["modules"].values(), key=lambda r: -len(r))
    if not by_runs:
        return [], []
    long_runs = [r for r in by_runs
                 if sum(e - s for s, e in r) >= MIN_PREFILL_S * len(r)]
    decode = (long_runs or by_runs)[0]
    prefill = [(s, e) for runs in by_runs if runs is not decode
               for s, e in runs if e - s >= MIN_PREFILL_S]
    return decode, sorted(prefill)


def read(ctx, which):
    t = ctx.get("trace")
    if not t:
        return None
    decode, prefill = split(t)
    runs = decode if which == "decode" else prefill
    if not runs:
        return None
    return 1e3 * sum(e - s for s, e in runs) / len(runs)
