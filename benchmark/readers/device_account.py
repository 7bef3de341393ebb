"""The device's idle time as the program itself accounts for it, from the
spans of the measured window (``ctx["spans"]``), where no profiler runs.

The generation engine asks the device at every launch whether it had run
dry (``paddle_tpu/serving/generation.py`` ``DeviceAccount``) and writes
the answer on the span that holds the launch (``LAUNCHES``): ``drained``
0 or 1 and, when 1, ``idle_known_ms`` (idle for certain: since the last
program was known to have finished) and ``idle_slack_ms`` (possibly
idle: since the device was last seen busy).  ``what`` picks the number:

- ``starved_pct``: 100 x launches with ``drained`` 1 over all launches.
- ``idle_known_pct``: 100 x sum of ``idle_known_ms`` over the window's
  wall time (``iter_account.window_ms``): the floor of the idle share.
- ``idle_slack_pct``: 100 x sum of ``idle_slack_ms`` less
  ``idle_known_ms`` over the same: what the ceiling adds to the floor.

A program that writes no ``drained`` gives None.  In a traced run the
same account over the traced seconds (``ctx["trace_spans"]``) goes to
the run's log beside the trace's own idle: between the first and the
last program run that lie wholly inside the traced window, the share in
which no program ran, which is what a launch can see, against the
account's gaps cut to the same stretch on the host's clock; and the
share of the whole window in which no operation ran
(``device_idle_pct.*``: that one also counts the gaps between the
operations of one program).
"""
from harness import load_module

iter_account = load_module("readers", "iter_account")
LAUNCHES = ("generation/decode_dispatch", "generation/prefill",
            "generation/prefill_chunk", "generation/spec_verify")


def launches(spans):
    """The scheduler thread's launches that say ``drained``."""
    return [s for s in iter_account.scheduler_spans(spans)
            if s.name in LAUNCHES and "drained" in s.attrs]


def gaps_ms(hit, lo=None, hi=None):
    """``(known, slack)`` ms over the launches ``hit``; each gap
    ends inside its launch's span (taken as the span's end: a launch is
    a millisecond or two) and is cut to ``[lo, hi]`` when given."""
    known = slack = 0.0
    for s in hit:
        for key in ("idle_known_ms", "idle_slack_ms"):
            ms = s.attrs.get(key, 0.0)
            if lo is not None:
                end = min(s.end, hi)
                ms = max(0.0, end - max(s.end - ms / 1e3, lo)) * 1e3
            if key == "idle_known_ms":
                known += ms
            else:
                slack += ms
    return known, slack


def _cross_check(ctx):
    """The account over the traced seconds beside the trace's idle."""
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0 or "device_account_said" in ctx:
        return
    ctx["device_account_said"] = True
    import xplane

    runs = xplane.union((s, e) for rs in t["modules"].values()
                        for s, e in rs)
    if not runs:
        return
    lo, hi = (x + t["to_monotonic"] for x in (runs[0][0], runs[-1][1]))
    near = launches(ctx.get("trace_spans", ()))
    hit = [s for s in near if lo <= s.end < hi]
    if not hit:
        return
    known, slack = gaps_ms([s for s in near if lo <= s.end < hi + 1.0],
                           lo, hi)
    wall = (hi - lo) * 1e3
    no_program = 100.0 * (1.0 - xplane.total(runs) / (hi - lo))
    no_op = 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    ctx["run"].say(
        f"device account over {hi - lo:.3f} traced s (first to last "
        f"program run): {len(hit)} launches, "
        f"{sum(s.attrs['drained'] for s in hit)} drained; idle known "
        f"{100 * known / wall:.3f} %, at most {100 * slack / wall:.3f} %; "
        f"the trace: no program ran {no_program:.3f} % of the same "
        f"stretch, no operation ran {no_op:.3f} % of the "
        f"{t['window_s']:.3f} s window")


def read(ctx, what):
    _cross_check(ctx)
    spans = ctx.get("spans", ())
    hit = launches(spans)
    if not hit:
        return None
    if what == "starved_pct":
        return 100.0 * sum(s.attrs["drained"] for s in hit) / len(hit)
    wall = iter_account.window_ms(iter_account.scheduler_spans(spans))
    if not wall:
        return None
    known, slack = gaps_ms(hit)
    if what == "idle_known_pct":
        return 100.0 * known / wall
    if what == "idle_slack_pct":
        return 100.0 * (slack - known) / wall
    raise ValueError(f"device_account: no reading {what!r}")
