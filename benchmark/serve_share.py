"""Driver for serving mixes of a configuration whose slots hold the delta
rule's state AND that routes over experts of which it holds a share
(``"driver": "serve_share"``): the two drivers that each know one of
those, by import, and nothing new but their meeting.

* from ``serve_state``: the plan of fillers, reused slots, live neighbours
  and joiners (``check_plan``), what it promised read back off the
  results (``plan_held``), and the comparison that knows routing is
  discrete (``check_request``: the program's router logits of the nine
  compared rows go to the reference, which takes the program's experts at
  a near tie; the configuration's ``check_tolerance``);
* from ``serve_delta``: every request's times on the engine's clock
  (``served_plan``), and the result line with the check's readings under
  a last key ``check`` (``run_cell``);
* here: ``seeded_scope`` draws the selection bias AND the decay constants
  from the seed (the harness's redraw leaves vectors alone), and
  ``reference_check`` is the verdict, put in ``serve``'s place by name.
  ``run_cell`` first asks the builder whether the program can run the
  configuration at all (``require_program``), before a device is claimed
  or a weight drawn.
"""
from __future__ import annotations

import numpy as np

import harness

import serve
import serve_blocks
import serve_delta
import serve_state

CHECK_NEW_TOKENS = serve_state.CHECK_NEW_TOKENS


def seeded_scope(builder, cfg, mix, seed):
    scope = serve_blocks.seeded_scope(builder, cfg, mix, seed)
    builder.seed_expert_bias(scope, cfg, seed)
    builder.seed_delta_gates(scope, cfg, seed)
    return scope


def reference_check(run, cfg, mix, seed):
    import gc

    builder = run.cell.builder()
    plan = serve_state.check_plan(cfg, mix, seed)
    scope = seeded_scope(builder, cfg, mix, seed)
    tol = run.cell.tolerance      # of what that engine ran in
    router_tol = run.cell.router_tolerance
    results, times, stats = serve_delta.served_plan(builder, cfg, mix,
                                                    scope, plan)
    # (as serve_state: the closed engine's pool must be gone before the
    # timed engine's is made)
    gc.collect()
    ref = run.cell.reference()
    params = ref.params_from_scope(scope, cfg)
    forward, pad = serve_state.jitted_forward(ref, cfg), \
        serve_state.check_pad(mix)
    ok = all(len(r["tokens"]) == n_new and r["finish"] == "length"
             for (_, n_new, _), r in zip(plan, results))
    margin = cfg["check_tolerance"]["near_tie_margin_share_of_router_range"]
    readings = {"tolerance": tol, "near_tie_margin": margin,
                "router_tolerance": router_tol, "rel": {},
                "router_off": {}, "near_ties": {}, "taken": {},
                "exact_tokens": ok}
    if not ok:
        run.say("reference check: a request did not get exactly its "
                "tokens: NOT correct")
    routers = []
    for (prompt, _, kind), res in zip(plan, results):
        if not isinstance(kind, int):
            continue
        fine, got = serve_state.check_request(forward, params, tol, pad,
                                              prompt, res, router_tol)
        routers.append(got.pop("router"))
        for what, v in got.items():
            # (a line is JSON: logits that are not finite read null)
            readings[what][str(len(prompt))] = \
                v if np.isfinite(v) else None
        ok = ok and fine
        run.say(f"reference check: prompt {len(prompt)} in reused slot "
                f"{res['slot']}, paged prefill + {CHECK_NEW_TOKENS - 1} "
                f"cached decode steps off the float32 reference's full "
                f"forward by {got['rel']:.4g} of its range (tolerance "
                f"{tol:.4g}); router scores off by at most "
                f"{got['router_off']:.3g} of a row's range"
                f"{harness.said_limit(router_tol)}, "
                f"{got['near_ties']} row-layers a near tie, "
                f"{got['taken']} taking the program's choice"
                + ("" if fine else ": NOT correct"))
    held, notes = serve_state.plan_held(plan, results, times)
    for note in notes:
        run.say("reference check: " + note)
    biases = np.stack([np.asarray(p["bias"]) for p in params["layers"]])
    first, count = ref.held_range(cfg)
    chosen = np.argsort(
        -(1.0 / (1.0 + np.exp(-np.concatenate(routers).astype("float64")))
          + biases[None]), axis=-1, kind="stable")[
              ..., :cfg["num_experts_per_tok"]]
    readings["pairs_held_pct"] = 100.0 * float(
        ((chosen >= first) & (chosen < first + count)).mean())
    run.say(f"reference check: in a grid of {mix['engine']['num_slots']} "
            f"slots, {len(plan)} requests, {stats['decode_steps']} grid "
            f"steps, {stats['slot_state_writes']} prefills wrote a slot's "
            f"state, {stats['delta_state_steps']} slot-layers of delta "
            f"state moved on; of {stats['moe_pairs_routed']} pairs routed "
            f"over {cfg['expert_share']['router_experts']} experts "
            f"{stats['moe_pairs_held']} were held here "
            f"({readings['pairs_held_pct']:.2f}% of the compared rows'); "
            f"the expert bias moved the choice of "
            f"{100 * serve_state.bias_moved_share(np.concatenate(routers), biases, cfg['num_experts_per_tok']):.1f}% "
            f"of the compared row-layers")
    run.check = dict(readings, plan_held=held)
    del params, forward
    return ok and held, scope


def run_cell(run) -> int:
    run.cell.builder().require_program()
    # ``serve_delta.run_cell`` puts ITS check in ``serve``'s place and then
    # wraps the result line; this driver's check goes where that one went
    serve_delta.reference_check = reference_check
    return serve_delta.run_cell(run)
