"""Driver for serving mixes of a configuration whose slots hold the delta
rule's state and that routes over no experts (``"driver":
"serve_delta"``): ``serve_state``, by import (its plan of fillers, reused
slots, live neighbours and joiners, its check engine of the timed size,
what the plan promised read back off the results), with the three things
that assume a router restated, and the clock its times are told on.
``serve_state.reference_check`` stacks ``res["router_logits"]``, hands the
reference a ``program_router`` and reports the expert bias's share; this
model has neither.

* ``seeded_scope``: the weights redrawn from the seed and, as the
  harness's redraw leaves vectors alone, the decay constants ``A_log`` /
  ``dt_bias`` drawn from it by the builder (``seed_delta_gates``).
* ``check_request``: the nine logit rows of a compared request (its paged
  prefill and eight cached decode steps) against the plain reference's
  full forward over prompt plus generated tokens, and nothing else: there
  is no discrete choice, so no near-tie rule.
* ``served_plan``: ``serve_state``'s, which since PR 67 tells every
  request's ``(claimed, first token, finished)`` on the engine's clock as
  this driver's own did from PR 41 on.
* ``reference_check``: the verdict, put in ``serve``'s place by name.
* ``run_cell``: the check's readings, each beside its limit, go to
  ``run.check``, which the harness prints under a last key ``check`` of
  the result line, so that a run that reads NOT correct says by which of
  them on the line itself.
"""
from __future__ import annotations

import numpy as np

import serve
import serve_blocks
import serve_state

CHECK_NEW_TOKENS = serve_state.CHECK_NEW_TOKENS


def seeded_scope(builder, cfg, mix, seed):
    scope = serve_blocks.seeded_scope(builder, cfg, mix, seed)
    builder.seed_delta_gates(scope, cfg, seed)
    return scope


def jitted_forward(ref, cfg):
    import jax

    return jax.jit(lambda p, ids, rows: ref.forward(p, ids, cfg, rows))


def check_request(forward, params, tol, pad, prompt, res):
    """What decides ``correct`` for one compared request: ``(fine,
    {"rel": share of the reference's range})``."""
    n = len(prompt)
    got = np.stack(res["logits"])                            # [9, V]
    ids = np.zeros((pad,), "int32")
    seq = list(prompt) + list(res["tokens"])
    ids[:len(seq)] = seq
    rows = np.arange(n - 1, n - 1 + CHECK_NEW_TOKENS)
    want = np.asarray(forward(params, ids, rows))
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    fine = got.shape == want.shape and bool(np.isfinite(got).all()) \
        and rel <= tol
    return bool(fine), {"rel": rel}


# every request's ``(claimed, first token, finished)`` on the engine's
# clock: first told so here (PR 41), ``serve_state``'s own since PR 67
served_plan = serve_state.served_plan


def reference_check(run, cfg, mix, seed):
    import gc

    builder = run.cell.builder()
    plan = serve_state.check_plan(cfg, mix, seed)
    scope = seeded_scope(builder, cfg, mix, seed)
    tol = run.cell.tolerance      # of what that engine ran in
    results, times, stats = served_plan(builder, cfg, mix, scope, plan)
    # (as serve_state: the closed engine's pool must be gone before the
    # timed engine's is made)
    gc.collect()
    ref = run.cell.reference()
    params = ref.params_from_scope(scope, cfg)
    forward, pad = jitted_forward(ref, cfg), serve_state.check_pad(mix)
    ok = all(len(r["tokens"]) == n_new and r["finish"] == "length"
             for (_, n_new, _), r in zip(plan, results))
    readings = {"tolerance": tol, "rel": {}, "exact_tokens": ok}
    if not ok:
        run.say("reference check: a request did not get exactly its "
                "tokens: NOT correct")
    for (prompt, _, kind), res in zip(plan, results):
        if not isinstance(kind, int):
            continue
        fine, got = check_request(forward, params, tol, pad, prompt, res)
        # (a line is JSON: logits that are not finite read null)
        readings["rel"][str(len(prompt))] = \
            got["rel"] if np.isfinite(got["rel"]) else None
        ok = ok and fine
        run.say(f"reference check: prompt {len(prompt)} in reused slot "
                f"{res['slot']}, paged prefill + {CHECK_NEW_TOKENS - 1} "
                f"cached decode steps off the float32 reference's full "
                f"forward by {got['rel']:.4g} of its range (tolerance "
                f"{tol:.4g})" + ("" if fine else ": NOT correct"))
    held, notes = serve_state.plan_held(plan, results, times)
    for note in notes:
        run.say("reference check: " + note)
    run.say(f"reference check: in a grid of {mix['engine']['num_slots']} "
            f"slots, {len(plan)} requests, {stats['decode_steps']} grid "
            f"steps, {stats['slot_state_writes']} prefills wrote a slot's "
            f"state, {stats['delta_state_steps']} slot-layers of delta "
            f"state moved on")
    run.check = dict(readings, plan_held=held)
    del params, forward
    return ok and held, scope


def run_cell(run) -> int:
    # ``serve.Served`` looks its set-up check up by name when it is
    # built: the one thing this driver puts in its place.  (``run.check``
    # goes out as the result line's last key: ``harness.Run.finish``.)
    serve.reference_check = reference_check
    return serve.run_cell(run)
