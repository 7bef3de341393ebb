"""Seconds of set-up that no span of the program covers, in s: the run's
``setup_s`` (process start to the window's open) less the mix's own
``warm traffic`` phase, less the **union on the clock** of every span of
the program's two start-up families (``startup_part.py``) that began
before the warm traffic did.  A union, so that two threads' spans side by
side (the front's warm-up beside the generator's) are taken off once.
What is left is the benchmark's own work, in files the program does not
reach: the seeded weights' draw, the float32 reference's forward, the
device claim (``harness.claim_devices``), the plan and the child's start.

The first read also logs the whole account through ``run.say``: every
part's self seconds and count, the five costliest programs by trace +
lowering and by backend compile with ``cache_hit``, beside the harness's
own phases, so that a traced run's log answers "which part, which
program".
"""
import xplane
from harness import load_module

WARM_TRAFFIC = "warm traffic"


def table(ctx, kept):
    """``(seconds covered, seconds before the warm traffic)`` and the
    account's lines in the run's log."""
    from paddle_tpu import telemetry

    run = ctx["run"]
    part = load_module("readers", "startup_part")
    warm = sum(s for name, s in run.phases if name == WARM_TRAFFIC)
    t_warm = part.window_open(run) - warm
    before = t_warm - run.t_start
    covered = xplane.total(xplane.union(
        (max(s.start, run.t_start), min(s.end, t_warm)) for s in kept))
    account = telemetry.startup_account(kept)
    programs = account.pop("programs")
    say = run.say
    say(f"start-up account, seconds of {before:.3f} of set-up (its "
        f"{warm:.3f} of warm traffic left out; {len(kept)} spans of the "
        f"program cover {covered:.3f}, "
        f"{100 * covered / max(before, 1e-9):.1f} %):")
    say("  harness phases: " + ", ".join(
        f"{name} {s:.3f}" for name, s in run.phases))
    for name, p in sorted(account.items(), key=lambda kv: -kv[1]["s"]):
        say(f"  {p['s']:9.3f} s {p['n']:5d} x  {name}")
    say(f"  {before - covered:9.3f} s          (no span of the program: "
        f"the benchmark's weights, reference forward, device claim)")

    for title, key in (("trace + lower", lambda r: r[3] + r[4]),
                       ("backend", lambda r: r[5])):
        say(f"  costliest programs by {title}: " + "; ".join(
            f"{telemetry.program_label(*r[:3])} {key(r):.3f} (trace {r[3]:.3f} lower {r[4]:.3f} "
            f"backend {r[5]:.3f} cache_hit {r[6]})"
            for r in sorted(programs, key=key, reverse=True)[:5]))
    return covered, before


def read(ctx):
    if "startup_table" not in ctx:
        kept = load_module("readers", "startup_part").setup_spans(ctx)
        ctx["startup_table"] = None if kept is None else table(ctx, kept)
    if ctx["startup_table"] is None:
        return None
    covered, before = ctx["startup_table"]
    return before - covered
