"""Driver for serving mixes of a configuration that prefills in chunks and
routes over experts of which it holds a share (``"driver":
"serve_chunks"``): the drivers that know a full grid, a router and a
result line with the check's readings, by import, and nothing new but
what a chunked engine asks of them.

* from ``serve_state``: the comparison that knows routing is discrete
  (``check_request``: the program's router logits of the nine compared
  rows go to the reference, which takes the program's experts at a near
  tie; the configuration's ``check_tolerance``), what a plan promised of
  slots and neighbours read back off the results (``plan_held``), the
  jitted reference and its padding;
* from ``serve_delta``: the result line with the check's readings under a
  last key ``check`` (``run_cell``), and the engine's own clock for every
  request's times (``served_plan``'s way, restated here because a chunked
  engine's rungs are those of its chunks, not of its prompts);
* here: ``check_plan``, ``serve_state``'s plan of fillers, reused slots,
  live neighbours and joiners with two things changed: the fillers
  outlast a prompt that takes an iteration a chunk, and the joiner behind
  each compared prompt is itself a prompt of several chunks, so the
  scheduler's round-robin puts ANOTHER prompt's chunks between the
  compared prompt's own, with the grid's decode steps between them all;
  ``chunks_held`` reads that back off the engine's spans and is part of
  the verdict; ``reference_check`` is put in ``serve``'s place by name.
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
import traffic

CHECK_NEW_TOKENS = serve_state.CHECK_NEW_TOKENS
SETTLE_STEPS = serve_state.SETTLE_STEPS
JOINER_CHUNKS = 1.5       # a joiner's prompt, in chunks


def n_chunks(n, chunk):
    return -(-int(n) // int(chunk))


def chunk_rungs(mix, lengths):
    """The rungs the chunks of prompts of these lengths need."""
    e = mix["engine"]
    chunk, rungs = int(e["prefill_chunk"]), e["prefill_buckets"]
    return sorted({min(b for b in rungs if b >= min(chunk, n - lo))
                   for n in lengths for lo in range(0, n, chunk)})


def check_plan(cfg, mix, seed):
    """``serve_state.check_plan`` for an engine that takes an iteration a
    chunk: ``[(prompt, n_new, kind), ...]`` in the order sent.  Fillers of
    one small chunk take every slot, a few (never two side by side)
    finish first; then the reference prompts, the longest first, and as
    many joiners of ``JOINER_CHUNKS`` chunks take the slots those left
    and, as they finish, each other's; two short joiners come last.
    Every other filler outlasts all the chunks and steps that follow."""
    e = mix["engine"]
    lens, slots = list(mix["reference_prompts"]), int(e["num_slots"])
    chunk = int(e["prefill_chunk"])
    rng = np.random.default_rng([int(seed), 51])
    behind = 2 * len(lens) + 1
    early = sorted(int(x) for x in np.linspace(
        1, slots - 2, min(behind, max((slots - 1) // 2, 1))).round())
    early = [s for j, s in enumerate(early) if j == 0 or s > early[j - 1] + 1]
    joiner = min(int(JOINER_CHUNKS * chunk),
                 int(e["max_seq_len"]) - CHECK_NEW_TOKENS - 5)
    # an iteration runs one chunk and one grid step: everything behind
    # the early fillers takes about this many
    iterations = sum(n_chunks(n, chunk) for n in lens) \
        + behind * n_chunks(joiner, chunk) + 3 * (CHECK_NEW_TOKENS + 4)
    tail = SETTLE_STEPS + iterations + 6
    plan = []

    def add(n, n_new, kind):
        plan.append((traffic.token_ids(seed, 900000 + len(plan), n,
                                       cfg["vocab_size"]), n_new, kind))

    lo, hi = serve_state.FILLER_PROMPT
    hi = min(hi, min(e["prefill_buckets"]))
    lo = min(lo, hi)
    for i in range(slots):
        is_early = i in early
        add(int(rng.integers(lo, hi + 1)),
            slots - i + (SETTLE_STEPS if is_early else tail),
            "early" if is_early else "filler")
    # the slots the early fillers left are taken together, by the
    # reference prompts and the first joiner: the round-robin walks each
    # one's chunks among the others', the shorter ones decode while the
    # longer ones still come in, and the joiners behind take the slots
    # they leave while the longest decodes
    for j, n in sorted(enumerate(lens), key=lambda jn: -jn[1]):
        add(n, CHECK_NEW_TOKENS, j)
    for _ in lens:
        add(joiner, CHECK_NEW_TOKENS + 4, "joiner")
    for _ in range(2):
        add(int(rng.integers(lo, hi + 1)), CHECK_NEW_TOKENS + 4, "joiner")
    return plan


def served_plan(builder, cfg, mix, scope, plan):
    """``serve_delta.served_plan`` (every request's ``(claimed, first
    token, finished)`` on the engine's clock) for an engine whose rungs
    are its chunks', with the chunk and step launches the engine's spans
    recorded while the plan ran: ``[(start, name, slot or None), ...]``."""
    import time

    from paddle_tpu import telemetry

    gen = builder.engine(cfg, mix, scope=scope, keep_logits=True,
                         buckets=chunk_rungs(mix, [len(p) for p, _, _
                                                   in plan]))
    try:
        gen.warmup()
        t_sent = time.monotonic()
        stamps, futures = [[] for _ in plan], []
        for (prompt, n_new, kind), at in zip(plan, stamps):
            futures.append(gen.submit(
                prompt, n_new, keep_logits=isinstance(kind, int),
                on_token=lambda _, t, at=at: at.append(t)))
        results = [f.result(900) for f in futures]
        sent = [at[0] - r["ttft_ms"] / 1e3 for at, r in zip(stamps, results)]
        times = [(t + r["queue_wait_ms"] / 1e3, t + r["ttft_ms"] / 1e3,
                  t + r["total_ms"] / 1e3) for t, r in zip(sent, results)]
        launches = sorted(
            (s.start, s.name, s.attrs.get("slot"))
            for s in telemetry.get_spans()
            if s.start >= t_sent and s.name in (
                "generation/prefill_chunk", "generation/decode_step"))
        return results, times, gen.stats()["counters"], launches
    finally:
        gen.close()
        scope.erase(list(gen.cache_names) + list(gen.state_names))


def chunks_held(plan, results, times, launches, chunk):
    """What the plan promised of each compared prompt of several chunks,
    read back off the engine's spans: between its first and its last
    chunk another slot's chunk was launched, and so was a grid step.
    ``(held, notes)``."""
    held, notes = True, []
    for i, (prompt, _, kind) in enumerate(plan):
        if not isinstance(kind, int) or len(prompt) <= chunk:
            continue
        slot, (t0, first, _) = results[i]["slot"], times[i]
        own = [t for t, name, s in launches if t0 <= t <= first
               and name == "generation/prefill_chunk" and s == slot]
        others = steps = 0
        if own:
            others = sum(1 for t, name, s in launches
                         if own[0] < t < own[-1] and s != slot
                         and name == "generation/prefill_chunk")
            steps = sum(1 for t, name, _ in launches
                        if own[0] < t < own[-1]
                        and name == "generation/decode_step")
        fine = len(own) == n_chunks(len(prompt), chunk) and others > 0 \
            and steps > 0
        held = held and fine
        notes.append(f"prompt {len(prompt)} in slot {slot} went in "
                     f"{len(own)} chunks with {others} chunk(s) of other "
                     f"prompts and {steps} grid step(s) between them"
                     + ("" if fine else ": the plan did NOT hold"))
    return held, notes


def reference_check(run, cfg, mix, seed):
    import gc

    builder = run.cell.builder()
    chunk = int(mix["engine"]["prefill_chunk"])
    plan = check_plan(cfg, mix, seed)
    scope = serve_blocks.seeded_scope(builder, cfg, mix, seed)
    tol = run.cell.tolerance      # of what that engine ran in
    router_tol = run.cell.router_tolerance
    results, times, stats, launches = served_plan(builder, cfg, mix, scope,
                                                  plan)
    # (as serve_state: the closed engine's pools must be gone before the
    # reference's temporaries and the timed engine's pools are made)
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
                f"{res['slot']}, {n_chunks(len(prompt), chunk)} prefill "
                f"chunk(s) + {CHECK_NEW_TOKENS - 1} cached decode steps "
                f"off the float32 reference's single forward by "
                f"{got['rel']:.4g} of its range (tolerance {tol:.4g}); "
                f"router scores off by at most {got['router_off']:.3g} of "
                f"a row's range"
                f"{harness.said_limit(router_tol)}, "
                f"{got['near_ties']} row-layers a near tie, "
                f"{got['taken']} taking the program's choice"
                + ("" if fine else ": NOT correct"))
    held, notes = serve_state.plan_held(plan, results, times)
    between, more = chunks_held(plan, results, times, launches, chunk)
    for note in notes + more:
        run.say("reference check: " + note)
    first, count = ref.held_range(cfg)
    chosen = np.argsort(-np.concatenate(routers).astype("float64"),
                        axis=-1, kind="stable")[
                            ..., :cfg["num_experts_per_tok"]]
    readings["pairs_held_pct"] = 100.0 * float(
        ((chosen >= first) & (chosen < first + count)).mean())
    run.say(f"reference check: in a grid of {mix['engine']['num_slots']} "
            f"slots, {len(plan)} requests, {stats['prefill_chunks']} "
            f"chunks, {stats['decode_steps']} grid steps, "
            f"{stats['window_pages_released_in_prefill']} window pages let "
            f"go while prompts came in and "
            f"{stats['window_pages_released']} in all; of "
            f"{stats['moe_pairs_routed']} pairs routed over "
            f"{cfg['expert_share']['router_experts']} experts "
            f"{stats['moe_pairs_held']} were held here "
            f"({readings['pairs_held_pct']:.2f}% of the compared rows')")
    run.check = dict(readings, plan_held=held, chunks_between=between)
    del params, forward
    return ok and held and between, scope


def run_cell(run) -> int:
    run.cell.builder().require_program()
    # ``serve_delta.run_cell`` puts ITS check in ``serve``'s place and then
    # wraps the result line; this driver's check goes where that one went
    serve_delta.reference_check = reference_check
    return serve_delta.run_cell(run)
