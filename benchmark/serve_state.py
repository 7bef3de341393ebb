"""Driver for serving mixes of a configuration whose slots hold state that
is not pages (``"driver": "serve_state"``): everything ``serve`` does (the
engine the builder makes behind ``serve(ServingEngine)``, the child
``loadgen.py``, the window, its statistics and summary), by import, with
the set-up check that decides ``correct`` restated for such state.
``serve.reference_check`` compares requests on a fresh two-slot engine,
where every slot's state is zero: it would pass a program that never
resets a reused slot, or that lets a neighbour's step or a joiner's
prefill touch a slot's state.

**The check.**  A check engine of the timed engine's size (the mix's
slots, pool and programs; ``keep_logits`` on, kept for the compared
requests alone) is built on weights redrawn from the seed.  ``check_plan``
sends: fillers that take every slot, a few of which (never two side by
side) finish first, all at about the same step; then the mix's
``reference_prompts``, each followed by a joiner, which take the slots
those fillers left.  So each compared request lands in a slot **that an
earlier request used and left**, between neighbours that decode all the
while, and the prefills of the requests behind it are dispatched while it
decodes (the engine prefills one request an iteration).  Of each compared
request the logits of its paged prefill and eight cached decode steps
must be the plain reference's: its full forward over prompt plus
generated tokens, rows ``n - 1 .. n + 7``.  Logits are compared, not
tokens; at a routing near tie on a compared row the reference takes the
program's four experts (the configuration's ``check_tolerance``).  Of
every request, fillers too: exactly the tokens asked for.  What the plan
promises is read back off the results (``plan_held``) and is part of the
verdict.  Returns ``(ok, scope)``: the timed engine is built on the same
weights.
"""
from __future__ import annotations

import numpy as np

import harness

import serve
import serve_blocks
import traffic

CHECK_NEW_TOKENS = serve.CHECK_NEW_TOKENS   # prefill + eight decode steps
SETTLE_STEPS = 4          # steps the early fillers outlast the last joiner
FILLER_PROMPT = (40, 100)                   # the smallest rung


def check_plan(cfg, mix, seed):
    """The check's requests in the order they are sent: ``[(prompt, n_new,
    kind), ...]``, kind ``"filler"``, ``"early"`` (a filler that finishes
    first), ``"joiner"`` or the index of a reference prompt.  The engine
    claims free slots lowest first and prefills one request an iteration
    with a grid step behind it, so filler ``i`` takes slot ``i`` and has
    made ``slots - i`` tokens when the last filler joins: an early one
    asks for ``SETTLE_STEPS`` more, every other for enough to outlast
    what follows."""
    lens = list(mix["reference_prompts"])
    slots = int(mix["engine"]["num_slots"])
    rng = np.random.default_rng([int(seed), 34])
    behind = 2 * len(lens) + 1           # compared + joiners
    # early fillers: spread over the grid, never the edge, never adjacent
    early = sorted(int(x) for x in np.linspace(
        1, slots - 2, min(behind, max((slots - 1) // 2, 1))).round())
    early = [s for j, s in enumerate(early) if j == 0 or s > early[j - 1] + 1]
    tail = SETTLE_STEPS + 2 * behind + CHECK_NEW_TOKENS + 6
    plan = []

    def add(n, n_new, kind):
        plan.append((traffic.token_ids(seed, 900000 + len(plan), n,
                                       cfg["vocab_size"]), n_new, kind))

    lo, hi = FILLER_PROMPT
    hi = min(hi, min(mix["engine"]["prefill_buckets"]))
    lo = min(lo, hi)
    for i in range(slots):
        is_early = i in early
        add(int(rng.integers(lo, hi + 1)),
            slots - i + (SETTLE_STEPS if is_early else tail),
            "early" if is_early else "filler")
    for j, n in enumerate(lens):
        add(n, CHECK_NEW_TOKENS, j)
        add(int(rng.integers(lo, hi + 1)), CHECK_NEW_TOKENS + 4, "joiner")
    for _ in range(max(len(early) - 2 * len(lens), 0)):
        add(int(rng.integers(lo, hi + 1)), CHECK_NEW_TOKENS + 4, "joiner")
    return plan


def plan_held(plan, results, times):
    """What the plan promised of each compared request, read back:
    ``(held, notes)``.  ``times[i]`` is ``(claimed, first token,
    finished)`` of request ``i`` on the host's clock.  Its slot was used
    and left by an earlier request; the requests in the slots either side
    were claimed before it and finished after it; and some other
    request's prefill ran (its first token came) while it decoded."""
    held, notes = True, []
    by_slot = {}
    for i, res in enumerate(results):
        by_slot.setdefault(res["slot"], []).append(i)
    for i, (_, _, kind) in enumerate(plan):
        if not isinstance(kind, int):
            continue
        slot, (t0, first, t1) = results[i]["slot"], times[i]
        before = [j for j in by_slot[slot] if times[j][2] <= t0]
        sides = []
        for nb in (slot - 1, slot + 1):
            sides.append(any(times[j][0] < t0 and times[j][2] > t1
                             for j in by_slot.get(nb, [])))
        joined = [j for j in range(len(plan)) if j != i
                  and first < times[j][1] < t1]
        fine = bool(before) and all(sides) and bool(joined)
        held = held and fine
        notes.append(f"prompt {len(plan[i][0])} in slot {slot}, which "
                     f"{len(before)} earlier request(s) used and left, "
                     f"neighbours live {sides}, {len(joined)} prefill(s) "
                     f"joined while it decoded"
                     + ("" if fine else ": the plan did NOT hold"))
    return held, notes


def check_request(forward, params, tol, pad, prompt, res, router_tol=None):
    """What decides ``correct`` for one compared request.  ``res`` is the
    engine's result under ``keep_logits``: ``logits`` and
    ``router_logits`` hold one row a generated token.  ``forward(params,
    ids, rows, program_router)`` is the plain reference's, jitted.
    ``router_tol``: how far the program's router scores may lie off the
    reference's, where the entry states it (``Cell.router_tolerance``).
    Returns ``(fine, readings)``."""
    n = len(prompt)
    got = np.stack(res["logits"])                            # [9, V]
    prog = np.stack(res["router_logits"]).astype("float32")  # [9, L, E]
    ids = np.zeros((pad,), "int32")
    seq = list(prompt) + list(res["tokens"])
    ids[:len(seq)] = seq
    rows = np.arange(n - 1, n - 1 + CHECK_NEW_TOKENS)
    want, report = forward(params, ids, rows, prog)
    want, report = np.asarray(want), np.asarray(report)
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    off = float(report[:, 0].max())
    fine = got.shape == want.shape and bool(np.isfinite(got).all()) \
        and rel <= tol and (router_tol is None or off <= router_tol)
    return bool(fine), {"rel": rel, "router_off": off,
                        "near_ties": int(report[:, 2].sum()),
                        "taken": int(report[:, 3].sum()), "router": prog}


def bias_moved_share(router_logits, biases, top_k):
    """Share of row-layers whose choice ``top_k(sigmoid(l) + b)`` is not
    their ``top_k`` largest sigmoids: the biased path at work.
    ``router_logits`` [R, L, E], ``biases`` [L, E]."""
    s = 1.0 / (1.0 + np.exp(-router_logits.astype("float64")))
    plain = np.sort(np.argsort(-s, axis=-1, kind="stable")[..., :top_k])
    moved = np.sort(np.argsort(-(s + biases[None]), axis=-1,
                               kind="stable")[..., :top_k])
    return float((plain != moved).any(-1).mean())


def seeded_scope(builder, cfg, mix, seed):
    """``serve_blocks.seeded_scope`` (the configuration's weights redrawn
    from ``seed`` in a scope that engines of any size are then built on)
    with the expert biases drawn from the seed too: the harness's redraw
    leaves vectors alone."""
    scope = serve_blocks.seeded_scope(builder, cfg, mix, seed)
    builder.seed_expert_bias(scope, cfg, seed)
    return scope


def jitted_forward(ref, cfg):
    import jax

    return jax.jit(lambda p, ids, rows, prog: ref.forward(
        p, ids, cfg, rows, program_router=prog))


def check_pad(mix):
    """Rows of the reference's forward: the longest compared sequence."""
    return -(-(max(mix["reference_prompts"]) + CHECK_NEW_TOKENS)
             // 128) * 128


def served_plan(builder, cfg, mix, scope, plan):
    """The plan's requests through a check engine of the mix's size on
    the weights in ``scope``: their results (logits kept for the
    compared ones alone), each one's ``(claimed, first token, finished)``
    and the engine's counters.  The engine is closed and its pool and
    state out of the scope when this returns.

    The times are told on ONE clock, the engine's.  Until PR 67 this
    function added the engine's milliseconds, which count from ITS stamp
    of the submission, to a stamp the harness took before the call: a
    request's times were early by what ``submit`` did in between, the
    conversion of the prompt's list (0.3-0.5 ms for a 5000-token prompt,
    0.05 ms for a filler) and whatever the scheduler thread kept of the
    interpreter.  ``plan_held`` asks whether a slot's earlier tenant had
    finished when the compared request claimed it, two stamps of one
    thread that read 0.1-0.45 ms apart (my chip run, PR 41): a submit
    that took half a millisecond longer read "the plan did NOT hold" on a
    plan that held, and the driver's check of PR 67 drew such a run
    (``lfm2-24b-longanswer``, seed 257746178: ``correct`` false there,
    true twice on the same seed on my chip runs).  The engine hands
    ``on_token`` its own stamp of every token and counts ``ttft_ms`` from
    the same one, so the first token's stamp less ``ttft_ms`` is its stamp
    of the submission."""
    rungs = mix["engine"]["prefill_buckets"]
    buckets = sorted({min(b for b in rungs if b >= len(p))
                      for p, _, _ in plan})
    gen = builder.engine(cfg, mix, scope=scope, keep_logits=True,
                         buckets=buckets)
    try:
        gen.warmup()
        stamps, futures = [[] for _ in plan], []
        for (prompt, n_new, kind), at in zip(plan, stamps):
            futures.append(gen.submit(
                prompt, n_new, keep_logits=isinstance(kind, int),
                on_token=lambda _, t, at=at: at.append(t)))
        results = [f.result(600) for f in futures]
        sent = [at[0] - r["ttft_ms"] / 1e3 for at, r in zip(stamps, results)]
        times = [(t + r["queue_wait_ms"] / 1e3, t + r["ttft_ms"] / 1e3,
                  t + r["total_ms"] / 1e3) for t, r in zip(sent, results)]
        return results, times, gen.stats()["counters"]
    finally:
        gen.close()
        scope.erase(list(gen.cache_names) + list(gen.state_names))


def reference_check(run, cfg, mix, seed):
    import gc

    builder = run.cell.builder()
    plan = check_plan(cfg, mix, seed)
    scope = seeded_scope(builder, cfg, mix, seed)
    tol = run.cell.tolerance      # of what that engine ran in
    router_tol = run.cell.router_tolerance
    results, times, stats = served_plan(builder, cfg, mix, scope, plan)
    # the closed engine still holds its pool, in a cycle: without this
    # the timed engine's pool may come to lie beside it
    gc.collect()
    ref = run.cell.reference()
    params = ref.params_from_scope(scope, cfg)
    forward, pad = jitted_forward(ref, cfg), check_pad(mix)
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
        fine, got = check_request(forward, params, tol, pad, prompt, res,
                                  router_tol)
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
    held, notes = plan_held(plan, results, times)
    for note in notes:
        run.say("reference check: " + note)
    biases = np.stack([np.asarray(p["bias"]) for p in params["layers"]
                       if "bias" in p])
    run.say(f"reference check: in a grid of "
            f"{mix['engine']['num_slots']} slots, {len(plan)} requests, "
            f"{stats['decode_steps']} grid steps, "
            f"{stats['slot_state_writes']} prefills wrote a slot's state; "
            f"the expert bias moved the choice of "
            f"{100 * bias_moved_share(np.concatenate(routers), biases, cfg['num_experts_per_tok']):.1f}% "
            f"of the compared row-layers")
    run.check = dict(readings, plan_held=held)
    del params, forward
    return ok and held, scope


def run_cell(run) -> int:
    # ``serve.Served`` looks its set-up check up by name when it is
    # built: the one thing this driver puts in its place (the process
    # runs one cell)
    serve.reference_check = reference_check
    return serve.run_cell(run)
