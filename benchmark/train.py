"""Driver for training mixes (``"driver": "train"``): a Program trained
through ``build_sharded_step`` on the cell's chips.  What belongs to the
model family (programs, host batches, reference check, FLOPs a token
needs) comes from the configuration's builder,
``benchmark/builders/<builder>.py``."""
from __future__ import annotations

import itertools
import time

import numpy as np

from harness import seeded_weights


def run_cell(run) -> int:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu.parallel import build_sharded_step, dp_mesh
    from paddle_tpu.reader import device_prefetch

    cell, args = run.cell, run.args
    cfg, mix, builder = cell.cfg, cell.mix, cell.builder()
    devices = run.claim_devices()
    n = cell.chips
    run.setup_compile_cache()
    run.phase("imports")

    seq = int(mix["seq_len"])
    batch = int(mix["per_chip_batch"]) * n
    main_p, startup, feed_names, loss = builder.build(
        cfg, batch, seq, cfg["recipe"]["dropout"])
    scope = pt.Scope()
    place = pt.CPUPlace() if run.rehearse else pt.TPUPlace()
    pt.Executor(place).run(startup, scope=scope)
    seeded_weights(scope, [p.name for p in main_p.all_parameters()],
                   args.seed)
    run.phase("weights")

    correct = builder.reference_check(run, cfg, scope, seq, args.seed)
    run.phase("reference check")

    mesh = dp_mesh(n, devices=devices[:n])
    fn, mut_in, const_in, _ = build_sharded_step(
        main_p, feed_names, [loss.name], mesh)
    host = builder.host_batches(args.seed, cfg, batch, seq,
                                int(mix["distinct_batches"]))
    stream = (tuple(b[k] for k in feed_names)
              for b in itertools.cycle(host))
    batches = device_prefetch(stream, depth=int(mix["prefetch_depth"]),
                              device=NamedSharding(mesh, P("dp")))
    mut = tuple(scope.find_var(k) for k in mut_in)
    const = tuple(scope.find_var(k) for k in const_in)

    step = 0
    losses = []
    wait_s = 0.0

    # one compilation, ahead of time (the harness reads every such
    # compilation's temporaries for the memory peak)
    feed = next(batches)
    compiled = fn.lower(feed, mut, const, np.int32(1)).compile()

    def one_step(feed):
        """Dispatch one step; at most one more is in flight behind it."""
        nonlocal step, mut
        step += 1
        with jax.profiler.TraceAnnotation("bench/dispatch_step"):
            fetches, mut, _ = compiled(feed, mut, const, np.int32(step))
        losses.append(fetches[0])
        if len(losses) >= 2:
            with jax.profiler.TraceAnnotation("bench/fence_previous"):
                jax.block_until_ready(losses[-2])

    def next_feed():
        nonlocal wait_s
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench/next_batch"):
            feed = next(batches)
        wait_s += time.monotonic() - t0
        return feed

    for _ in range(int(mix["warm_steps"])):
        one_step(feed)
        feed = next_feed()
    jax.block_until_ready(losses[-1])
    run.phase("cache load or compile + warm-up")
    run.say_phases()

    # -- the window: steps dispatched one ahead, fenced on the last loss ----
    seconds = float(args.seconds)
    compiles0 = run.compile_count()
    n_warm = len(losses)
    wait_s = 0.0
    t_open = time.monotonic()
    setup_s = t_open - run.t_start
    while time.monotonic() - t_open < seconds:
        one_step(feed)
        feed = next_feed()
    jax.block_until_ready(losses[-1])
    t_close = time.monotonic()
    compiles = run.compile_count() - compiles0
    steps = len(losses) - n_warm
    window_wait_s = wait_s

    # -- a few traced steps after the window, in the same steady state ------
    if run.trace_on:
        run.trace_start()
        for _ in range(int(mix["trace_steps"])):
            one_step(feed)
            feed = next_feed()
        jax.block_until_ready(losses[-1])
        run.trace_stop()

    window = t_close - t_open
    values = np.asarray([float(np.asarray(x).reshape(-1)[0])
                         for x in losses])
    failed = int((~np.isfinite(values[n_warm:n_warm + steps])).sum())
    correct = correct and bool(np.isfinite(values).all())
    # (beside the set-up check's readings; limit 0)
    run.check = dict(run.check or {},
                     losses_not_finite=int((~np.isfinite(values)).sum()))
    tokens_per_s_chip = steps * batch * seq / window / n
    run.say(f"window {window:.3f} s, {steps} steps of {batch} x {seq} on "
            f"{n} chip(s), loss {values[n_warm]:.4f} -> "
            f"{values[n_warm + steps - 1]:.4f}, "
            f"compiles in window {compiles}")

    flops_per_token = builder.flops_per_token(cfg, seq)
    ctx = {
        "run": run, "cfg": cfg, "mix": mix, "trace": run.trace,
        "values": {
            "step_mean_ms": 1e3 * window / steps,
            "input_wait_ms": 1e3 * window_wait_s / steps,
            "compiles_in_window": compiles,
            "tokens_per_s_per_chip": tokens_per_s_chip,
            "flops_per_token": flops_per_token,
        },
        "counts": {"steps": steps, "tokens_per_step": batch * seq,
                   "compiles_in_window": compiles,
                   "flops_per_token": flops_per_token},
    }
    return run.finish(
        correct=correct, attempted=steps, failed=failed,
        end_to_end={"train_tokens_per_s_per_chip": tokens_per_s_chip,
                    "setup_s": setup_s},
        ctx=ctx)
