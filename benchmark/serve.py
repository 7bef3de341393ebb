"""Driver for serving mixes (``"driver": "serve"``; ``"loop": "open"`` or
``"closed"``): ``serve(ServingEngine)`` with the generation engine the
configuration's builder makes (``benchmark/builders/<builder>.py``)
attached in this process, driven over HTTP ``/generate`` (``stream:
true``) by the child process ``loadgen.py``."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

import traffic
from harness import HERE, quantile, seeded_weights, share_over

CHECK_NEW_TOKENS = 9      # one from the prefill, eight cached decode steps


def jitted_forward(ref, cfg):
    import jax

    return jax.jit(lambda p, ids, rows: ref.forward(p, ids, cfg, rows))


def check_request(forward, params, tol, pad, prompt, res, cfg=None):
    """What decides ``correct`` for one compared request: ``(fine,
    {"rel": share of the reference's range})``.  ``res`` is the engine's
    result under ``keep_logits``; ``forward(params, ids, rows)`` the plain
    reference's, jitted.  A check engine that keeps its router logits
    leaves them where the reference finds them itself
    (``builders/smallthinker_engine.py``); a stand-in's result
    (``tests/standins.py``) brings them in ``res`` and names ``cfg``, and
    they lie there for this call."""
    n = len(prompt)
    got = np.stack(res["logits"])
    ids = np.zeros((pad,), "int32")
    seq = list(prompt) + list(res["tokens"])
    ids[:len(seq)] = seq
    rows = np.arange(n - 1, n - 1 + CHECK_NEW_TOKENS)
    offered = cfg is not None and "router_logits" in res
    if offered:
        cfg["_program_router"] = {"ids": [int(t) for t in seq],
                                  "first_row": n - 1,
                                  "logits": np.stack(res["router_logits"])}
    try:
        want = np.asarray(forward(params, ids, rows))
    finally:
        if offered:
            del cfg["_program_router"]
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    fine = len(res["tokens"]) == CHECK_NEW_TOKENS \
        and got.shape == want.shape and bool(np.isfinite(got).all()) \
        and rel <= tol
    return bool(fine), {"rel": rel}


def reference_check(run, cfg, mix, seed):
    """A check-only engine (two slots, ``keep_logits`` on) makes the
    weights, which are then redrawn from the seed; for one short and one
    long prompt its paged prefill and eight cached decode steps must give the logits of the plain
    reference's full forward over prompt plus generated tokens.  Returns
    ``(ok, scope)``: the timed engine is built on the same weights."""
    lens = list(mix["reference_prompts"])
    buckets = sorted({min(b for b in mix["engine"]["prefill_buckets"]
                          if b >= n) for n in lens})
    gen = run.cell.builder().engine(cfg, mix, num_slots=2, keep_logits=True,
                                    buckets=buckets)
    tol = run.cell.tolerance      # of what that engine ran in
    seeded_weights(gen.scope,
                   [n for n in gen.scope.local_var_names()
                    if n.startswith(gen.name + ".")
                    and n not in gen.cache_names], seed)
    ref = run.cell.reference()
    params = ref.params_from_scope(gen.scope, cfg, gen.name)
    pad = -(-(max(lens) + CHECK_NEW_TOKENS) // 128) * 128
    forward = jitted_forward(ref, cfg)
    ok = True
    readings = run.check = {"tolerance": tol, "rel": {}}
    try:
        gen.warmup()
        for j, n in enumerate(lens):
            prompt = traffic.token_ids(seed, 900000 + j, n,
                                       cfg["vocab_size"])
            res = gen.generate(prompt, CHECK_NEW_TOKENS, timeout=600)
            fine, got = check_request(forward, params, tol, pad, prompt,
                                      res)
            ok, rel = ok and fine, got["rel"]
            # (a line is JSON: logits that are not finite read null)
            readings["rel"][str(n)] = rel if np.isfinite(rel) else None
            run.say(f"reference check: prompt {n}, paged prefill + "
                    f"{CHECK_NEW_TOKENS - 1} cached decode steps off the "
                    f"float32 reference's full forward by {rel:.4g} of "
                    f"its range (tolerance {tol:.4g})")
    finally:
        gen.close()
    del params, forward
    return ok, gen.scope


def _front_predictor(run):
    """``ServingEngine`` wants a predictor for ``/predict``; the cells
    only use ``/generate``, so it gets the smallest one (as
    ``chip_smoke._mlp_predictor`` builds it)."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.inference import Predictor

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        x = layers.data("x", [8])
        y = layers.fc(x, 8, param_attr="bench_front.w",
                      bias_attr="bench_front.b")
    scope = pt.Scope()
    place = pt.CPUPlace() if run.rehearse else pt.TPUPlace()
    pt.Executor(place).run(startup, scope=scope)
    return Predictor(main, ["x"], [y], scope=scope)


class GaugeSampler(threading.Thread):
    """Samples the program's gauges every 50 ms: slot occupancy and live
    pages change per step, and a gauge holds only its last value."""

    NAMES = ("serving_slot_occupancy", "serving_kv_pages_live")

    def __init__(self):
        super().__init__(name="bench-gauges", daemon=True)
        self.samples = []
        self._halt = threading.Event()

    def run(self):
        from paddle_tpu import telemetry

        gauges = {n: telemetry.metrics.gauge(n) for n in self.NAMES}
        while not self._halt.wait(0.05):
            self.samples.append(
                (time.monotonic(), {n: g.get() for n, g in gauges.items()}))

    def stop(self):
        self._halt.set()
        if self.is_alive():
            self.join(5.0)


def _plan(cfg, mix, url, seed, seconds, tail_s=0.0):
    """The child's plan: schedule, token ids, and the clock.  ``tail_s``
    more seconds of the same traffic follow the window in a traced run,
    and are traced.  Returns the plan and, for an open loop, when the
    window opens; a closed loop's window is cut by the child."""
    lead_s = 1.0                     # the child starts, reads, encodes
    t0 = time.monotonic() + lead_s
    plan = {"url": url, "loop": mix["loop"], "t0": t0,
            "timeout_s": float(mix["deadline_ms"]) / 1e3 + 30.0}
    if mix["loop"] == "open":
        sched = traffic.open_schedule(mix, seed, seconds, tail_s)
        t_open = t0 + float(mix["warm_s"])
    else:
        sched = traffic.closed_schedule(
            mix, seed, int(mix["blocks"]) * int(mix["block"]))
        t_open = None
        plan.update(
            workers=int(mix["workers_per_slot"]) * mix["engine"]["num_slots"],
            block=int(mix["block"]), warm_blocks=int(mix["warm_blocks"]),
            seconds=seconds, tail_s=tail_s,
            t_stop=t0 + seconds + float(mix["give_up_s"]))
    reqs = sched["requests"]
    for i, r in enumerate(reqs):
        r["prompt"] = traffic.token_ids(seed, i, r.pop("prompt_len"),
                                        cfg["vocab_size"])
    plan["requests"] = reqs
    return plan, t_open


def _client_stats(records, t_open, t_close, mix):
    """End-to-end readings from the child's records.  A request counts if
    it was due inside the window; a gap counts if its later token arrived
    inside the window."""
    deadline_s = float(mix["deadline_ms"]) / 1e3

    def met(r):
        return (r["outcome"] == "ok" and r["complete"]
                and r["stream_matches_summary"] and r["finish"] == "length"
                and r["done"] - r["due"] <= deadline_s)

    due = [r for r in records if t_open <= r["due"] < t_close]
    good = [r for r in due if met(r)]
    bad = [r for r in due if not met(r)]
    gaps, late, ttft, overhead = [], [], [], []
    for r in records:
        a = r["arrivals"]
        gaps += [1e3 * (b - x) for x, b in zip(a, a[1:])
                 if t_open <= b < t_close]
    for r in good:
        late.append(1e3 * (r["sent"] - r["due"]))
        t = 1e3 * (r["arrivals"][0] - r["due"])
        ttft.append(t)
        if r["engine_ttft_ms"] is not None:
            overhead.append(t - r["engine_ttft_ms"])
    ok = [r for r in records if r["outcome"] == "ok" and r["complete"]]
    done = [r for r in ok if t_open <= r["done"] < t_close]
    # tokens served in the window: a prompt counts when its first token
    # arrives, an output token when it arrives.  A closed loop's window
    # opens and closes on such a first token: the one that closes it is
    # inside, the one that opens it (prefilled before) is not
    served = sum(
        (r["prompt_len"] if t_open < r["arrivals"][0] <= t_close else 0)
        + sum(t_open < a <= t_close for a in r["arrivals"]) for r in ok)
    return {"due": due, "bad": bad, "gaps": gaps, "late": late,
            "ttft": ttft, "overhead": overhead, "completed": len(done),
            "served_tokens": served}


class Served:
    """The system under test, up: the timed engine behind
    ``serve(ServingEngine)``, every program warm."""

    def __init__(self, run):
        from paddle_tpu import flags
        from paddle_tpu.serving import ServingEngine, serve

        cell, args = run.cell, run.args
        self.run, self.cfg, self.mix = run, cell.cfg, cell.mix
        run.claim_devices()
        run.setup_compile_cache()
        # every span of a window must still be in the ring when it closes
        flags.set_flags({"FLAGS_trace_buffer_size": 1 << 17})
        run.phase("imports")

        self.correct, scope = reference_check(run, self.cfg, self.mix,
                                              args.seed)
        run.phase("weights + reference check")

        deadline_ms = float(self.mix["deadline_ms"])
        self.gen = cell.builder().engine(self.cfg, self.mix, scope=scope)
        self.engine = ServingEngine(
            _front_predictor(run), workers=1, max_batch=8, max_delay_ms=2.0,
            deadline_ms=deadline_ms, warmup_shapes={"x": (8,)})
        self.engine.attach_generator(self.gen)
        self.server = serve(self.engine,
                            request_timeout_s=deadline_ms / 1e3)
        try:
            compiled = self.gen.warmup()
        except BaseException:
            self.server.close()
            raise
        run.phase("cache loads or compiles + warm-up")
        run.say(f"{compiled} generation programs warm; KV pool "
                f"{self.gen.kv_cache_bytes / 2 ** 30:.2f} GiB, "
                f"{self.gen.num_slots} slots x {self.gen.max_seq_len} "
                f"positions")

    def close(self):
        self.server.close()

    def window(self, mix, seed, seconds, trace=False) -> dict:
        """Warm traffic, then one measured window of ``mix`` from the
        child process.  Returns the client's statistics and what the
        per-layer readers need."""
        from paddle_tpu import telemetry

        run = self.run
        trace_s = float(mix["trace_s"]) if trace else 0.0
        plan, t_open = _plan(self.cfg, mix, self.server.url, seed, seconds,
                             tail_s=trace_s + 1.0 if trace else 0.0)
        plan_path = os.path.join(run.workdir, "plan.json")
        result_path = os.path.join(run.workdir, "result.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        sampler = GaugeSampler()
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"), plan_path,
             result_path], stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        said = []

        def edge(name, at):
            """Wait for an edge of the window: an open loop's is a time
            on the clock, a closed loop's is marked by the child."""
            if at is not None:
                time.sleep(max(0.0, at - time.monotonic()))
                return at
            for line in child.stdout:
                said.append(line)
                if line.startswith('{"mark"'):
                    doc = json.loads(line)
                    if doc["mark"] == name:
                        return float(doc["t"])
            raise RuntimeError(
                f"loadgen ended before {name}: {''.join(said)[-2000:]}")

        try:
            sampler.start()
            t_open = edge("t_open", t_open)
            run.phase("warm traffic", at=t_open)
            compiles0 = run.compile_count()
            t_close = edge("t_close", None if mix["loop"] == "closed"
                           else t_open + seconds)
            compiles = run.compile_count() - compiles0
            if trace:
                # the same traffic goes on for a few traced seconds
                run.trace_start()
                time.sleep(trace_s)
                run.trace_stop()
            done = [s for s in telemetry.get_spans() if s.end is not None]
            spans = [s for s in done if t_open <= s.start < t_close]
            trace_spans = [s for s in done if s.start >= t_close - 5.0]
            out, _ = child.communicate(
                timeout=float(mix["deadline_ms"]) / 1e3 + 60.0)
            if child.returncode != 0:
                raise RuntimeError(
                    f"loadgen exited {child.returncode}: "
                    f"{(''.join(said) + out)[-2000:]}")
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
            sampler.stop()
        with open(result_path) as f:
            records = json.load(f)
        st = _client_stats(records, t_open, t_close, mix)
        st.update(t_open=t_open, window_s=t_close - t_open,
                  compiles=compiles, spans=spans,
                  trace_spans=trace_spans,
                  gauges=[g for t, g in sampler.samples
                          if t_open <= t < t_close])
        return st


LADDER = tuple(range(90, 99)) + (98.5, 99, 99.5)
GATE = 99                 # the percentile ``itl_p99_ms`` reads
EDGE_NEAR = 0.03          # half a point either side within 3% of it
STALLED_TIMES = 2.0       # a gap over twice the median carried a stall
STALLED_MIN_PCT = 8.0


def gap_ladder(gaps) -> dict:
    """Where the gate sits among the token gaps: the 90th to 99.5th
    percentiles point by point (and the 98.5th), the largest gap, the share of gaps over
    twice their median (those that carried a prefill), and whether the
    gate is off an edge.  Prefill lengths are padded to rungs, so the
    stalled gaps form plateaus, one a rung; a percentile on the border
    between two jumps with the seed, one inside a plateau reads a device
    time.  The 99th lies inside the widest rung's plateau while that
    holds a point and a half of the gaps or more."""
    gaps = sorted(gaps)       # once: ``quantile`` sorts what it is given
    rungs = {p: quantile(gaps, p / 100.0) for p in LADDER}
    stalled = share_over(gaps, STALLED_TIMES)
    near = max(abs(rungs[p] / rungs[GATE] - 1.0)
               for p in (GATE - 0.5, GATE + 0.5))
    return {"ladder_ms": {f"p{p}": round(v, 3) for p, v in rungs.items()},
            "max_ms": round(gaps[-1], 3),
            "stalled_gap_share_pct": round(stalled, 3),
            "half_point_off_gate": round(near, 5),
            "off_edge": stalled >= STALLED_MIN_PCT and near <= EDGE_NEAR}


def summary(st: dict) -> dict:
    """The readings a window gives, by name."""
    if not st["ttft"] or not st["gaps"]:
        raise RuntimeError("no request completed in the window")
    occ = [g["serving_slot_occupancy"] for g in st["gauges"]]
    return {
        "ttft_p50_ms": quantile(st["ttft"], 0.5),
        "ttft_p95_ms": quantile(st["ttft"], 0.95),
        "itl_p50_ms": quantile(st["gaps"], 0.5),
        "itl_p95_ms": quantile(st["gaps"], 0.95),
        "itl_p99_ms": quantile(st["gaps"], 0.99),
        "served_tokens_per_s": st["served_tokens"] / st["window_s"],
        "late_p99_ms": quantile(st["late"], 0.99),
        "slot_occupancy": sum(occ) / len(occ) if occ else float("nan"),
    }


def run_cell(run) -> int:
    cell, args = run.cell, run.args
    seconds = float(args.seconds)
    served = Served(run)
    try:
        st = served.window(cell.mix, args.seed, seconds, trace=run.trace_on)
    finally:
        served.close()
    setup_s = st["t_open"] - run.t_start
    run.say_phases()
    attempted, failed = len(st["due"]), len(st["bad"])
    for r in st["bad"][:5]:
        run.say(f"failed request {r['index']}: {r['outcome']} "
                f"{r.get('finish')} {r.get('detail')}")
    wrong = [r for r in st["due"] if r["outcome"] == "ok"
             and not (r["complete"] and r["stream_matches_summary"])]
    sm = summary(st)
    run.say(f"window {st['window_s']:.3f} s: {attempted} requests due, {failed} "
            f"failed, {st['completed']} completed in it, {len(st['gaps'])} "
            f"token gaps; " + ", ".join(
                f"{k} {v:.3f}" for k, v in sm.items())
            + f", compiles in window {st['compiles']}")
    run.say("token gaps " + json.dumps(gap_ladder(st["gaps"])))
    spans = st["spans"]
    ctx = {
        "run": run, "cfg": cell.cfg, "mix": cell.mix, "trace": run.trace,
        "spans": spans, "trace_spans": st["trace_spans"],
        "gauges": st["gauges"], "engine": served.gen,
        "clients": {"ttft": st["ttft"], "itl": st["gaps"],
                    "late": st["late"], "front_overhead": st["overhead"]},
        "values": {"compiles_in_window": st["compiles"]},
        "counts": {"requests_due": attempted, "failed": failed,
                   "completed": st["completed"],
                   "token_gaps": len(st["gaps"]),
                   "served_tokens": st["served_tokens"],
                   "compiles_in_window": st["compiles"],
                   "prefill_spans": sum(s.name == "generation/prefill"
                                        for s in spans),
                   "decode_step_spans": sum(
                       s.name == "generation/decode_step" for s in spans)},
    }
    e2e = dict(sm, setup_s=setup_s)
    # (beside the set-up check's readings: answers of the window that
    # came whole but not as the summary line said; limit 0)
    run.check = dict(run.check or {}, window_answers_wrong=len(wrong))
    return run.finish(correct=served.correct and not wrong,
                      attempted=attempted, failed=failed, end_to_end=e2e,
                      ctx=ctx)
