"""Driver for serving mixes of a block-diffusion configuration
(``"driver": "serve_blocks"``): everything ``serve`` does (the engine the
builder makes behind ``serve(ServingEngine)``, the child ``loadgen.py``,
the window, its statistics and summary), by import, with the set-up check
that decides ``correct`` restated for a step that yields a block:
``serve.reference_check`` compares a prefill and eight one-token steps
with a causal forward, which cannot check a denoising pass.

**The check.**  A check engine of the timed engine's size (the mix's
slots, pool and pass program; ``keep_logits`` on) is built on weights
redrawn from the seed (``seeded_scope``).  The mix's ``reference_prompts``
(tails of every kind) go in together with fillers that take every other
slot (``check_plan``), so the compared passes are those of a full grid:
slots in every phase side by side, requests that join and finish in the
middle of others' blocks, a pass in flight that carries every slot's
block.  Of each reference prompt's ``check_blocks`` blocks EVERY pass's
``[B, vocab]`` logits, denoising and commit alike, must be the plain
reference's: its full forward, under the block-causal mask, over the
prompt's whole blocks + the blocks committed so far + the block as the
program fed it to that pass (``check_request``).  Teacher-forced: logits
are compared, the program's own unmasking decisions are taken as they
fell (with random weights the largest logit changes on rounding) and so,
at a routing near tie on any row whose K/V the compared pass reads (the
prefill's rows, the committed blocks', the block's own), are its own
eight experts (the configuration's ``check_tolerance``).  Of every request,
fillers too: exactly the tokens asked for, the commit passes' inputs are
the tokens returned, no commit pass holds a mask.  Returns ``(ok,
scope)``: the timed engine is built on the same weights.
"""
from __future__ import annotations

import numpy as np

import harness

import serve
import traffic
from harness import seeded_weights


def check_plan(cfg, mix, seed):
    """The check's requests in the order they are sent, ``[(prompt,
    n_new, compared), ...]``.  The engine prefills one request an
    iteration, so request i joins about pass i.  Fillers take all but
    the last few slots; the reference prompts follow with two fillers
    between them, the last on the last slot; most fillers last until the
    reference prompts are through, one in five finishes while they run,
    and the fillers behind the last slot take what those free."""
    B = int(cfg["assumed"]["generation"]["block_length"])
    per_block = int(mix["passes"]) + 1
    lens = list(mix["reference_prompts"])
    slots = int(mix["engine"]["num_slots"])
    rng = np.random.default_rng([int(seed), 32])
    # True: a filler; an int: that reference prompt
    order = [True] * max(slots - 3 * len(lens) + 2, 0)
    for j in range(len(lens)):
        order += [True, True] * (j > 0) + [j]
    last = len(order) - 1                     # joins about this pass
    ref_passes = per_block * int(mix["check_blocks"])
    order += [True] * max(slots // 8, 1)
    longest = sorted(lens)[len(lens) // 2]
    plan = []
    for i, what in enumerate(order):
        if what is True:
            n = int(rng.integers(mix["prompt_len"]["min"], longest + 1))
            end = last + ref_passes + 2 + int(rng.integers(0, per_block)) \
                if rng.integers(0, 5) else \
                max(last - 2, i + 1) + int(rng.integers(0, ref_passes))
            blocks = max(-(-(end - i) // per_block), 1)
            n_new = max(blocks * B - n % B - int(rng.integers(0, B)), 1)
        else:
            n = lens[what]
            n_new = int(mix["check_blocks"]) * B - n % B
        prompt = traffic.token_ids(seed, 900000 + i, n, cfg["vocab_size"])
        plan.append((prompt, n_new, what is not True))
    return plan


def check_request(forward, params, B, tol, pad, prompt, n_new, res,
                  compared=True, router_tol=None):
    """What decides ``correct`` for one request.  ``res`` is the engine's
    result under ``keep_logits``: ``tokens``, ``finish`` and ``passes``,
    each pass's ``base``, ``tokens`` / ``masked`` [B] (the block as the
    pass was fed it), ``quota`` (0: a commit pass), ``logits`` [B, V]
    and ``router_logits`` [L, B, E], ``riders`` (the slots that rode the
    pass, where the engine says); ``router_logits`` of the result itself
    are the prefill's, ``[[L, bucket, E]]`` (where the engine says).
    ``forward(params, ids, masked, rows, program_router, router_covers)``
    is the plain reference's, jitted.  ``router_tol``: how far the
    program's router logits may lie off the reference's, where the entry
    states it (``Cell.router_tolerance``).  Returns ``(fine, readings)``."""
    n = len(prompt)
    seq = list(prompt) + list(res["tokens"])
    commits = [p for p in res["passes"] if not p["quota"]]
    fine = len(res["tokens"]) == n_new and res["finish"] == "length" \
        and len(commits) == -(-(n % B + n_new) // B) \
        and not any(np.asarray(p["masked"]).any() for p in commits) \
        and [int(t) for p in commits
             for t in p["tokens"]][n % B:][:n_new] == list(res["tokens"])
    got = {"denoise": 0.0, "commit": 0.0, "router_off": 0.0,
           "near_ties": 0, "taken": 0, "passes": len(res["passes"]),
           "riders": [p.get("riders", 1) for p in res["passes"]]}
    # the program's router logits, row by row as the sequence grows: the
    # prefill's over the prompt's whole blocks, then each commit pass's
    # ([L, rows, E] as the program yields them -> [rows, L, E])
    prog = covers = None
    if compared:
        first = res["passes"][0]["router_logits"]
        prog = np.zeros((pad, first.shape[0], first.shape[-1]), "float32")
        covers = np.zeros((pad,), bool)
        if res.get("router_logits"):         # the prefill's (the engine)
            whole = n - n % B
            prog[:whole] = np.transpose(
                res["router_logits"][0][:, :whole], (1, 0, 2))
            covers[:whole] = True
    for p in res["passes"] if compared else ():
        base = int(p["base"])
        ids = np.zeros((pad,), "int32")
        ids[:base + B] = seq[:base] + [int(t) for t in p["tokens"]]
        masked = np.zeros((pad,), bool)
        masked[base:base + B] = np.asarray(p["masked"]).astype(bool)
        # (a denoising pass's rows are overwritten by the next pass's)
        prog[base:base + B] = np.transpose(
            np.asarray(p["router_logits"], np.float32), (1, 0, 2))
        covers[base:base + B] = True
        want, report = forward(params, ids, masked,
                               np.arange(base, base + B), prog, covers)
        want, report = np.asarray(want), np.asarray(report)
        logits = np.asarray(p["logits"])
        rel = float(np.abs(logits - want).max() / np.abs(want).max())
        kind = "denoise" if p["quota"] else "commit"
        got[kind] = max(got[kind], rel)
        off = float(report[:, 0].max())
        got["router_off"] = max(got["router_off"], off)
        got["near_ties"] = max(got["near_ties"], int(report[:, 2].sum()))
        got["taken"] = max(got["taken"], int(report[:, 3].sum()))
        fine = fine and logits.shape == want.shape \
            and bool(np.isfinite(logits).all()) and rel <= tol \
            and (router_tol is None or off <= router_tol)
    return bool(fine), got


def seeded_scope(builder, cfg, mix, seed):
    """A scope with the configuration's weights redrawn from ``seed``.
    A two-slot engine makes them (the redraw holds a second copy of the
    largest matrix for a moment, 1.6 GB at published widths: before the
    mix's pool is there, not beside it); engines of any size are then
    built on the scope."""
    import jax

    small = builder.engine(
        cfg, mix, num_slots=2,
        buckets=[min(mix["engine"]["prefill_buckets"])])
    small.close()
    names = [n for n in small.scope.local_var_names()
             if n.startswith(small.name + ".")
             and n not in small.cache_names]
    seeded_weights(small.scope, names, seed)
    # an engine built while the redraw still runs allocates its pool
    # twice over: the zeros it copies are freed only behind the redraw
    jax.block_until_ready([small.scope.find_var(n) for n in names])
    return small.scope


def jitted_forward(ref, cfg):
    import jax

    return jax.jit(lambda p, ids, m, rows, prog, covers: ref.forward(
        p, ids, m, cfg, rows, program_router=prog, router_covers=covers))


def check_pad(cfg, mix):
    """Rows of the reference's forward: the longest compared sequence."""
    B = int(cfg["assumed"]["generation"]["block_length"])
    return -(-(max(mix["reference_prompts"])
               + int(mix["check_blocks"]) * B) // 128) * 128


def served_plan(builder, cfg, mix, scope, plan):
    """The plan's requests through a check engine of the mix's size on
    the weights in ``scope``: their results (``keep_logits``) and the
    engine's counters.  The engine is closed and its pool out of the
    scope when this returns."""
    B = int(cfg["assumed"]["generation"]["block_length"])
    rungs = mix["engine"]["prefill_buckets"]
    buckets = sorted({min([b for b in rungs
                           if b >= max(len(p) - len(p) % B, 1)],
                          default=max(rungs)) for p, _, _ in plan})
    gen = builder.engine(cfg, mix, scope=scope, keep_logits=True,
                         buckets=buckets)
    try:
        gen.warmup()
        futures = [gen.submit(prompt, n_new) for prompt, n_new, _ in plan]
        return [f.result(600) for f in futures], gen.stats()["counters"]
    finally:
        gen.close()
        scope.erase(gen.cache_names)


def reference_check(run, cfg, mix, seed):
    import gc

    B = int(cfg["assumed"]["generation"]["block_length"])
    slots = int(mix["engine"]["num_slots"])
    plan = check_plan(cfg, mix, seed)
    scope = seeded_scope(run.cell.builder(), cfg, mix, seed)
    tol = run.cell.tolerance      # of what that engine ran in
    router_tol = run.cell.router_tolerance
    results, stats = served_plan(run.cell.builder(), cfg, mix, scope, plan)
    # the closed engine still holds its pool, in a cycle: without this
    # the timed engine's pool may come to lie beside it (1.6 GB)
    gc.collect()
    ref = run.cell.reference()
    params = ref.params_from_scope(scope, cfg)
    forward, pad = jitted_forward(ref, cfg), check_pad(cfg, mix)
    ok = True
    readings = run.check = {"tolerance": tol, "router_tolerance": router_tol,
                            "denoise": {}, "commit": {}, "router_off": {},
                            "near_ties": {}, "taken": {}}
    fillers = [0, 0]                 # requests, of them not fine
    for (prompt, n_new, compared), res in zip(plan, results):
        fine, got = check_request(forward, params, B, tol, pad, prompt,
                                  n_new, res, compared, router_tol)
        res.clear()                  # a pass's logits are the whole grid's
        ok = ok and fine
        if not compared:
            fillers[0] += 1
            fillers[1] += not fine
            continue
        n = len(prompt)
        for what in ("denoise", "commit", "router_off", "near_ties",
                     "taken"):
            # (a line is JSON: logits that are not finite read null)
            readings[what][str(n)] = \
                got[what] if np.isfinite(got[what]) else None
        run.say(f"reference check: prompt {n} (tail {n % B}), paged "
                f"block-causal prefill + {got['passes']} passes of "
                f"{mix['check_blocks']} blocks off the float32 "
                f"reference's full forward by {got['denoise']:.4g} "
                f"(denoising) / {got['commit']:.4g} (commit) of its range "
                f"(tolerance {tol:.4g}); router logits off by at most "
                f"{got['router_off']:.3g} of a row's range"
                f"{harness.said_limit(router_tol)} "
                f"over the rows a pass reads, at most {got['near_ties']} row-layers of "
                f"them a near tie, {got['taken']} taking the program's "
                f"choice; "
                f"{min(got['riders'])}-{max(got['riders'])} of "
                f"{slots} slots rode its passes"
                + ("" if fine else ": NOT correct"))
    passes = stats["block_passes_denoise"] + stats["block_passes_commit"]
    run.say(f"reference check: in a grid of {slots} slots with "
            f"{fillers[0]} fillers ({fillers[1]} of them not exactly "
            f"their tokens), {passes / max(stats['decode_steps'], 1):.1f} "
            f"slots live a pass over {stats['decode_steps']} passes")
    readings["fillers_not_their_tokens"] = fillers[1]      # limit 0
    del params, forward
    return ok, scope


def run_cell(run) -> int:
    # ``serve.Served`` looks its set-up check up by name when it is
    # built: the one thing this driver puts in its place (the process
    # runs one cell)
    serve.reference_check = reference_check
    return serve.run_cell(run)
