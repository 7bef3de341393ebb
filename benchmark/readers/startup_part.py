"""Self seconds of parts of the program's start-up account, in s.

The program splits what it does before the window into spans of two
families, ``startup/`` and ``compile/``, each opened where the work
happens, and keeps them in a ring of their own that a window's traffic
does not turn over (``paddle_tpu/telemetry.py``, ``get_spans(kept=True)``;
``ctx["spans"]`` is the window's and holds none of them).  Each carries
``self_ms``: its duration less what spans of the two families inside it on
the same thread cover, so the parts are disjoint on a thread.  ``read``
sums it over the spans named ``spans`` that began before the window
opened.

A program that keeps no such ring (a tree from before PR 53) gives
nothing to read.
"""


def window_open(run) -> float:
    """When the measured window opened, on the span clock: the run's start
    and every set-up phase the driver marked."""
    return run.t_start + sum(s for _, s in run.phases)


def setup_spans(ctx):
    """The kept spans that began before the window opened, oldest first;
    None where the program keeps none.  ``ctx["startup_spans"]`` stands in
    for the program's ring (a test's hand-made spans)."""
    kept = ctx.get("startup_spans")
    if kept is None:
        from paddle_tpu import telemetry

        try:
            kept = telemetry.get_spans(kept=True)
        except TypeError:         # this program's get_spans keeps one ring
            return None
    opened = window_open(ctx["run"])
    return [s for s in kept if s.end is not None and s.start < opened]


def read(ctx, spans):
    kept = setup_spans(ctx)
    if kept is None:
        return None
    return sum(s.attrs.get("self_ms", 0.0) for s in kept
               if s.name in spans) / 1e3
