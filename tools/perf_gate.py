#!/usr/bin/env python
"""Perf regression gate: fresh bench/op-bench reports vs the committed
trajectory, with noise-aware tolerances.

Makes the numbers load-bearing (ROADMAP item 5): a perf PR runs the
bench, then this gate compares the fresh report against baseline
``bench.py`` reports (and optionally an ``op_bench.py`` report against
``tools/op_bench_baseline.json``) and **exits nonzero on regression** —
a capacity or step-time regression fails loudly instead of shipping
silently.

Noise model: runs of byte-identical programs were seen to drift ±10%
(bench.py module docstring), and every bench
leg records its own window spread as ``stats.p10``/``stats.p90``.  The
per-leg tolerance is therefore::

    tol = max(--floor-tol,                     # cross-run chip drift
              (base.p90 - base.p10) / base.median,   # baseline's noise
              (new.p90  - new.p10)  / new.median)    # fresh run's noise

and a leg regresses when ``new.median < base.median * (1 - tol)``.
Legs are only compared on matching ``device_kind`` (a CPU smoke run
against a TPU baseline is a skip, not a pass or fail), and legs the
baseline flagged ``anomaly`` are skipped (a garbage baseline must not
gate anything).

Usage::

    python tools/perf_gate.py --report fresh.json --baseline base.json
        [--baseline older.json ...]         # trajectory: last match wins
        [--op-report ops.json [--op-baseline tools/op_bench_baseline.json]]
        [--floor-tol 0.10] [--op-threshold 1.5]
    python tools/perf_gate.py --smoke       # self-test on synthetic
        reports (no benchmark run) — wired into tier-1 via
        tests/test_lint.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FLOOR_TOL = 0.10        # cross-run chip drift floor (bench.py docstring)
OP_THRESHOLD = 1.5      # per-op regression ratio (check_op_bench.py)


def load_report(path: str) -> dict:
    """Load a bench JSON; unwrap the driver's capture envelope
    (``{"n", "cmd", "rc", "tail", "parsed": {...}}``) when present."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if "parsed" in doc and isinstance(doc["parsed"], dict) \
            and "value" in doc["parsed"]:
        return doc["parsed"]
    return doc


def extract_legs(doc: dict) -> Dict[str, dict]:
    """Flatten a bench report into ``{leg_name: leg_dict}``: the
    top-level flagship plus everything under ``legs``.  Legs that
    errored (``{"error": ...}``) or carry no ``value`` are dropped."""
    legs = {}
    if isinstance(doc.get("value"), (int, float)):
        legs["flagship"] = doc
    for name, leg in (doc.get("legs") or {}).items():
        if isinstance(leg, dict) and isinstance(leg.get("value"),
                                                (int, float)):
            legs[name] = leg
    return legs


def _noise(leg: dict) -> float:
    """Relative window spread from the leg's own recorded p10/p90
    (0 when the leg publishes no stats — e.g. the serving leg)."""
    st = leg.get("stats") or {}
    med = st.get("median") or 0.0
    p10, p90 = st.get("p10"), st.get("p90")
    if not med or p10 is None or p90 is None:
        return 0.0
    return max(float(p90) - float(p10), 0.0) / float(med)


def _median_of(leg: dict) -> float:
    st = leg.get("stats") or {}
    return float(st.get("median") or leg["value"])


def compare_leg(name: str, new: dict, base: dict,
                floor_tol: float) -> dict:
    """One leg's verdict: ``status`` in ``ok | regression | skipped``
    (+ the numbers behind it)."""
    res = {"leg": name}
    # sharded-serving correctness rule, checked before EVERY skip
    # (device-kind mismatch, anomalous baseline, anomalous fresh run):
    # mp=2 weight-sharded serving is bit-exact by construction, so a
    # False is a regression on any host — core contention or a garbage
    # baseline can hide throughput, never flip bytes
    if new.get("mp2_bit_exact") is False:
        res.update(status="regression",
                   reason="mp2 weight-sharded serving no longer "
                          "bit-exact vs the unsharded predictor")
        return res
    # router rollout-availability rule, also checked before every
    # skip: the rolling-restart contract is ZERO non-shed request
    # failures across the window — a failure is a correctness break
    # (drain or retry stopped working), which core contention can
    # slow down but never cause
    rollout = new.get("rollout")
    if isinstance(rollout, dict):
        failed = rollout.get("failed")
        if failed is None:
            # the window measured nothing (traffic thread died/hung):
            # a vacuous pass must not satisfy the zero-failure contract
            res.update(status="regression",
                       reason="rolling-restart window has no measured "
                              "failure count (traffic produced no "
                              "report)")
            return res
        if failed > 0:
            res.update(status="regression",
                       reason=f"rolling restart saw {failed} non-shed "
                              f"request failure(s) (contract: zero)")
            return res
        # torn-version rule (hard, like the failure rule above): a
        # response carrying an older weights_version after a newer one
        # was already visible on the same replica means the atomic
        # flip tore mid-swap.  The dedicated rollout leg must MEASURE
        # the count — missing there is a vacuous pass; plain
        # rolling-restart windows predate the check and simply don't
        # carry the key
        torn = rollout.get("torn_responses",
                           None if name == "rollout" else 0)
        if torn is None:
            res.update(status="regression",
                       reason="rollout leg has no measured torn-"
                              "version count (vacuous hot-swap "
                              "window)")
            return res
        if torn > 0:
            res.update(status="regression",
                       reason=f"hot swap served {torn} torn-version "
                              f"response(s) — an older weights_version "
                              f"after a newer one was visible "
                              f"(contract: zero)")
            return res
    # canary rollout rules, also checked before every skip: a CLEAN
    # canary that reverted means the burn-rate judge convicted a good
    # checkpoint (false positive — rollouts become un-shippable), and
    # a BAD canary whose revert took longer than the bound means the
    # judge is too slow to protect traffic.  Core contention can slow
    # a soak, never fabricate burn on a clean version
    canary = new.get("canary")
    if isinstance(canary, dict):
        fr = canary.get("false_reverts")
        if fr is None:
            res.update(status="regression",
                       reason="canary leg has no measured false-"
                              "revert count (vacuous soak: the clean "
                              "canary never ran)")
            return res
        if fr > 0:
            res.update(status="regression",
                       reason=f"{fr} clean canary rollout(s) were "
                              f"auto-reverted (burn-rate false "
                              f"positive; contract: zero)")
            return res
        lat = canary.get("revert_latency_s")
        bound = canary.get("revert_latency_bound_s")
        if canary.get("reverts"):
            # a bad canary was injected: the revert must be measured
            # and inside the leg's own bound
            if lat is None:
                res.update(status="regression",
                           reason="canary auto-revert happened but "
                                  "its latency went unmeasured "
                                  "(vacuous revert evidence)")
                return res
            if bound is not None and lat > bound:
                res.update(status="regression",
                           reason=f"canary auto-revert took "
                                  f"{lat:.1f}s, past the "
                                  f"{bound:.1f}s bound — the judge "
                                  f"is too slow to protect traffic")
                return res
    # chaos fault-containment rules, also checked before every skip:
    # a collateral (non-injected) failure or a poisoned request served
    # 200 is a correctness break — core contention can slow recovery,
    # never cause either
    if "collateral_failures" in new:
        cf = new.get("collateral_failures")
        if cf is None:
            res.update(status="regression",
                       reason="chaos run measured no collateral-"
                              "failure count (vacuous window)")
            return res
        if cf > 0:
            res.update(status="regression",
                       reason=f"chaos saw {cf} collateral (non-"
                              f"injected) request failure(s) "
                              f"(contract: zero)")
            return res
        leaks = new.get("poison_leaks")
        if leaks is None:
            # like the collateral rule: a dropped field must not read
            # as "zero leaks"
            res.update(status="regression",
                       reason="chaos run measured no poison-leak "
                              "count (vacuous window)")
            return res
        if leaks > 0:
            res.update(status="regression",
                       reason=f"{leaks} poisoned request(s) answered "
                              f"200 instead of failing (bisection "
                              f"containment leak)")
            return res
        # burn-rate alert contract (observability hard rule, like the
        # two above — no anomaly flag shields it): a fault window the
        # alert missed, a recovery it never cleared after, or a clean
        # scenario it paged on.  None is allowed — captures predate
        # the alerting layer
        alert_errors = new.get("alert_errors")
        if alert_errors:
            res.update(status="regression",
                       reason=f"chaos saw {alert_errors} burn-rate "
                              f"alert-contract violation(s) (missed "
                              f"fire / missed clear / false positive)")
            return res
        # disagg page-pool leak rule (hard, like collateral/leaks):
        # a live page surviving the drained storm means a refcount
        # path (export / adopt / failure) lost a decref — core
        # contention can slow the drain, never leak a page.  None is
        # allowed: captures predate the disagg scenario
        leaked_pages = new.get("leaked_pages")
        if leaked_pages:
            res.update(status="regression",
                       reason=f"chaos disagg_crash left "
                              f"{leaked_pages} KV page(s) live after "
                              f"the storm drained (refcount leak)")
            return res
        # embedding pin-leak rule (hard, like leaked_pages): a hot
        # row still pinned after the recsys storm drained means a
        # lookup path lost its unpin — core contention can slow the
        # drain, never leak a pin.  None is allowed: captures predate
        # the embedding_shard_crash scenario
        leaked_rows = new.get("leaked_rows")
        if leaked_rows:
            res.update(status="regression",
                       reason=f"chaos embedding_shard_crash left "
                              f"{leaked_rows} hot row(s) pinned after "
                              f"the storm drained (refcount leak)")
            return res
        # crash-forensics rule (hard, like collateral/leaks): every
        # induced death must be harvested and attributed — a death
        # the supervisor cannot explain means the flight recorder,
        # the kill-mark path, or the harvest broke.  Present-but-None
        # is a vacuous verdict (a death was never even booked) and
        # fails too; the key absent is allowed — captures predate the
        # forensics layer
        if "unexplained_deaths" in new:
            ud = new.get("unexplained_deaths")
            if ud is None:
                res.update(status="regression",
                           reason="chaos run measured no unexplained-"
                                  "death count (vacuous forensics: an "
                                  "induced death was never booked)")
                return res
            if ud > 0:
                res.update(status="regression",
                           reason=f"chaos saw {ud} unexplained replica "
                                  f"death(s) — died rc>0 with no "
                                  f"postmortem artifact (contract: "
                                  f"zero)")
                return res
        # usage-conservation rule (hard, like collateral/leaks): the
        # per-tenant cost vectors must sum EXACTLY to the global
        # counters — tolerance 0, through a SIGKILL-respawn.  Present-
        # but-None is a vacuous verdict (the scenario ran but could
        # not measure conservation) and fails too; the key absent is
        # allowed — captures predate the usage observatory
        if "usage_conservation_delta" in new:
            ucd = new.get("usage_conservation_delta")
            if ucd is None:
                res.update(status="regression",
                           reason="chaos run measured no usage-"
                                  "conservation delta (vacuous: per-"
                                  "tenant attribution never verified)")
                return res
            if ucd != 0:
                res.update(status="regression",
                           reason=f"per-tenant usage does not conserve:"
                                  f" delta {ucd} against the global "
                                  f"counters (contract: exactly zero)")
                return res
        # noisy-neighbor attribution floor (hard): the hog tenant's
        # booked cost share must be at least 90% of its client-side
        # share — a tenant header dropped on any hop folds the hog
        # into the default tenant and collapses this ratio.  None is
        # vacuous (unmeasured) and fails; absent is allowed
        if "hog_attribution_ratio" in new:
            har = new.get("hog_attribution_ratio")
            if har is None:
                res.update(status="regression",
                           reason="chaos noisy_neighbor measured no "
                                  "hog attribution ratio (vacuous: "
                                  "excess cost never attributed)")
                return res
            if har < 0.9:
                res.update(status="regression",
                           reason=f"hog attribution ratio {har} below "
                                  f"the 0.9 floor — excess cost was "
                                  f"not booked to the noisy tenant")
                return res
        # heavy-hitter sketch memory bound (hard): no replica may ever
        # hold more than top_k tracked vectors (+1 for ~other) no
        # matter the tenant cardinality.  None is vacuous and fails;
        # absent is allowed
        if "sketch_violations" in new:
            sv = new.get("sketch_violations")
            if sv is None:
                res.update(status="regression",
                           reason="chaos run measured no sketch-bound "
                                  "verdict (vacuous: memory bound "
                                  "never checked)")
                return res
            if sv > 0:
                res.update(status="regression",
                           reason=f"{sv} replica(s) violated the "
                                  f"heavy-hitter sketch memory bound "
                                  f"(contract: <= top_k + 1 vectors)")
                return res
        # the harness's own verdict: a scenario that errored (watchdog
        # never fired, no poisoned request reached a model, victim
        # never respawned) means a containment mechanism went
        # unexercised or dead — counts alone can pass vacuously
        if new.get("harness_ok") is False or new.get("errors"):
            detail = new.get("errors") or "harness_ok=false"
            res.update(status="regression",
                       reason=f"chaos harness reported scenario "
                              f"errors: {detail}")
            return res
    # disagg vacuous-A/B rule, also checked before every skip: a leg
    # that carries the ratio key but measured None means the A/B's
    # decode grid never stepped — an empty measurement must not read
    # as "no regression" on any host
    if "disagg_vs_colocated_p99" in new \
            and new.get("disagg_vs_colocated_p99") is None:
        res.update(status="regression",
                   reason="disagg leg has no measured decode-step "
                          "p99 ratio (vacuous A/B: the decode grid "
                          "never stepped)")
        return res
    # speculative-decode hard rules, also checked before every skip:
    # core contention can slow the verify chunk (the tokens/sec ratio
    # honestly sits under 1.0 on core-bound hosts — that is what the
    # anomaly flag and the baseline-armed collapse rule are for), but
    # it can never leak a page, unbalance the rollback counters, or
    # lower greedy-argmax acceptance on a deterministic workload
    if "spec_tokens_proposed" in new:
        sl = new.get("leaked_pages")
        if sl is None:
            res.update(status="regression",
                       reason="spec leg measured no leaked-page count "
                              "(vacuous drain: the pool was never "
                              "checked after rejected drafts)")
            return res
        if sl > 0:
            res.update(status="regression",
                       reason=f"spec decode left {sl} KV page(s) live "
                              f"after drain (rejected-draft rollback "
                              f"refcount leak)")
            return res
        prop = new.get("spec_tokens_proposed")
        acc = new.get("spec_tokens_accepted")
        drafts = new.get("spec_drafts")
        rb = new.get("spec_rollbacks")
        if None in (prop, acc, drafts, rb):
            res.update(status="regression",
                       reason="spec leg is missing draft/accept/"
                              "rollback counters (vacuous speculation "
                              "window)")
            return res
        if acc > prop:
            res.update(status="regression",
                       reason=f"spec accepted {acc} draft tokens out "
                              f"of {prop} proposed — the acceptance "
                              f"bookkeeping overcounts")
            return res
        if rb > drafts:
            res.update(status="regression",
                       reason=f"spec rolled back {rb} drafts but only "
                              f"{drafts} were issued — the rollback "
                              f"bookkeeping overcounts")
            return res
        ar = new.get("acceptance_rate")
        ar_floor = new.get("acceptance_floor")
        if ar_floor is not None:
            if ar is None:
                res.update(status="regression",
                           reason="spec leg declares an acceptance "
                                  "floor but measured no acceptance "
                                  "rate (vacuous: the drafter never "
                                  "fired)")
                return res
            if ar < float(ar_floor):
                res.update(status="regression",
                           reason=f"spec acceptance rate {ar} under "
                                  f"the {ar_floor} floor on the "
                                  f"repetition-heavy workload (the "
                                  f"drafter or verifier broke)")
                return res
    # recsys embedding-tier hard rules, also checked before every
    # skip: the clean bench keeps every shard alive, so a degraded
    # lookup is a correctness break (a gather failed mid-leg), and a
    # present-but-None count is a vacuous window — core contention
    # can slow lookups, never degrade them.  The hot-row hit-rate
    # floor rides the leg: under it the cache is dead
    # (hashing/eviction broke) even when throughput keeps up, and no
    # anomaly flag shields either rule
    if "degraded_lookups" in new:
        dl = new.get("degraded_lookups")
        if dl is None:
            res.update(status="regression",
                       reason="recsys leg measured no degraded-lookup "
                              "count (vacuous window: the embedding "
                              "tier never booked its counters)")
            return res
        if dl > 0:
            res.update(status="regression",
                       reason=f"recsys bench saw {dl} degraded "
                              f"lookup(s) with every shard alive "
                              f"(contract: zero)")
            return res
        hr_floor = new.get("hit_floor")
        if hr_floor is not None:
            hr = (new.get("hit_rate") or {}).get("hot")
            if hr is None:
                res.update(status="regression",
                           reason="recsys leg declares a hot-row hit-"
                                  "rate floor but measured no hot-"
                                  "phase hit rate (vacuous: the cache "
                                  "was never probed)")
                return res
            if hr < float(hr_floor):
                res.update(status="regression",
                           reason=f"recsys hot-row hit rate {hr} "
                                  f"under the {hr_floor} floor on the "
                                  f"zipfian hot workload (the hot-row "
                                  f"cache is dead)")
                return res
    nk, bk = new.get("device_kind"), base.get("device_kind")
    if nk is not None and bk is not None and nk != bk:
        res.update(status="skipped",
                   reason=f"device_kind {nk!r} != baseline {bk!r}")
        return res
    if base.get("anomaly"):
        res.update(status="skipped",
                   reason=f"baseline flagged anomalous: "
                          f"{base['anomaly']}")
        return res
    new_med, base_med = _median_of(new), _median_of(base)
    tol = max(floor_tol, _noise(base), _noise(new))
    threshold = base_med * (1.0 - tol)
    res.update(base_median=round(base_med, 2),
               new_median=round(new_med, 2),
               ratio=round(new_med / base_med, 4) if base_med else None,
               tolerance=round(tol, 4),
               threshold=round(threshold, 2))
    if new.get("anomaly"):
        # an anomalous fresh number can't prove health — but it also
        # must not fail the gate on a noisy window; surface it loudly
        res.update(status="skipped",
                   reason=f"fresh run flagged anomalous: "
                          f"{new['anomaly']}")
        return res
    res["status"] = "regression" if new_med < threshold else "ok"
    # decode-leg extra: the leg's headline is continuous-batching
    # tokens/sec, but the scheduler's reason to exist is beating its
    # own FIFO static baseline — if the fresh speedup drops below 1.0
    # while the baseline had the win, the fast path regressed even when
    # raw tokens/sec kept up (e.g. the static path got faster because
    # the continuous path stopped reclaiming slots)
    sp_new = new.get("speedup_vs_static")
    sp_base = base.get("speedup_vs_static")
    if res["status"] == "ok" and sp_new is not None \
            and sp_base is not None and sp_new < 1.0 <= sp_base:
        res.update(status="regression",
                   reason=f"speedup_vs_static collapsed to {sp_new} "
                          f"(baseline {sp_base})")
    # sharded-serving extras: the replica-group engine's contract is
    # dp=4 at >= 2x the single-chip qps AT NO WORSE p99 — raw qps can
    # keep up (e.g. the single-chip baseline got slower too) while the
    # dp win quietly collapses, so both ratios gate explicitly when the
    # baseline proved them on this device kind
    sg_new = new.get("speedup_vs_single")
    sg_base = base.get("speedup_vs_single")
    if res["status"] == "ok" and sg_new is not None \
            and sg_base is not None and sg_new < 2.0 <= sg_base:
        res.update(status="regression",
                   reason=f"speedup_vs_single fell to {sg_new} "
                          f"(< 2x dp contract; baseline {sg_base})")
    p99r_new = new.get("p99_vs_single")
    p99r_base = base.get("p99_vs_single")
    if res["status"] == "ok" and p99r_new is not None \
            and p99r_base is not None \
            and p99r_new > 1.0 + tol >= p99r_base:
        res.update(status="regression",
                   reason=f"dp p99 now {p99r_new}x the single-chip "
                          f"p99 (was {p99r_base}x; tol {tol})")
    # router-leg extra: the fleet tier's contract is >= 2x closed-loop
    # qps at 4 replicas vs 1 — raw qps can track the baseline while
    # the scaling itself quietly collapses (e.g. the router started
    # serializing on one replica), so the ratio gates explicitly when
    # the baseline proved it on this device kind
    s4_new = new.get("speedup_4v1")
    s4_base = base.get("speedup_4v1")
    if res["status"] == "ok" and s4_new is not None \
            and s4_base is not None and s4_new < 2.0 <= s4_base:
        res.update(status="regression",
                   reason=f"speedup_4v1 fell to {s4_new} (< 2x fleet "
                          f"scaling contract; baseline {s4_base})")
    # chaos-leg extra: availability under fault must clear the
    # committed floor.  Unlike the collateral rule this respects the
    # anomaly skip above — a core-bound host genuinely slows recovery
    # windows, which honestly costs availability
    floor = new.get("availability_floor")
    if res["status"] == "ok" and floor is not None \
            and new_med < float(floor):
        res.update(status="regression",
                   reason=f"availability {new_med}% under the "
                          f"{floor}% chaos budget")
    # spec-decode extra: once a baseline proved speculative decode
    # beats the plain grid step on a device kind, a fresh ratio under
    # 1.0 means the speedup collapsed (verify got slower than the K+1
    # steps it replaces) even when raw tokens/sec keeps up — arms only
    # where the baseline had the win, like the other speedup rules
    # (core-bound CPU smoke captures honestly sit under 1.0)
    svp_new = new.get("spec_vs_plain_tokens")
    svp_base = base.get("spec_vs_plain_tokens")
    if res["status"] == "ok" and svp_new is not None \
            and svp_base is not None and svp_new < 1.0 <= svp_base:
        res.update(status="regression",
                   reason=f"spec_vs_plain_tokens collapsed to "
                          f"{svp_new} (baseline {svp_base}: "
                          f"speculation beat the plain grid step)")
    # disagg-leg extras: the disaggregated pipeline's reason to exist
    # is decode-step p99 under the mixed workload.  (a) A leg that
    # carries the key but measured nothing is vacuous — the A/B's
    # decode grid never stepped, which no skip may shield; (b) once a
    # baseline proved the p99 win (ratio <= 1.0) on this device kind,
    # a fresh ratio collapsing past 1.0+tol is a regression even when
    # raw tokens/sec keeps up (mirrors the dp p99 rule)
    dvp = new.get("disagg_vs_colocated_p99")
    if dvp is not None:
        dvp_base = base.get("disagg_vs_colocated_p99")
        # arm strictly on dvp_base <= 1.0 (the baseline PROVED the
        # win), not <= 1.0+tol — a baseline inside the noise gap
        # never proved anything and must not flap the gate
        if res["status"] == "ok" and dvp_base is not None \
                and dvp > 1.0 + tol and dvp_base <= 1.0:
            res.update(status="regression",
                       reason=f"disagg decode-step p99 now {dvp}x "
                              f"colocated (was {dvp_base}x; tol "
                              f"{tol}) — the handoff stopped paying "
                              f"for itself")
    return res


def compare_bench(new_doc: dict, base_docs: List[dict],
                  floor_tol: float = FLOOR_TOL) -> dict:
    """Gate a fresh bench report against the baseline trajectory.

    For each leg in the fresh report, the baseline is the LAST given
    document carrying that leg (pass baselines oldest→newest); earlier
    medians are reported as ``trajectory`` context.  A leg present in
    a baseline but missing from the fresh report is a regression (a
    silently-vanished leg must not pass)."""
    new_legs = extract_legs(new_doc)
    results = []
    seen = set()
    base_legsets = [extract_legs(d) for d in base_docs]
    for name, new_leg in new_legs.items():
        base_leg, trajectory = None, []
        for legs in base_legsets:
            if name in legs:
                base_leg = legs[name]
                trajectory.append(_median_of(legs[name]))
        if base_leg is None:
            results.append({"leg": name, "status": "new",
                            "new_median": round(_median_of(new_leg), 2)})
            continue
        seen.add(name)
        res = compare_leg(name, new_leg, base_leg, floor_tol)
        if len(trajectory) > 1:
            res["trajectory"] = [round(t, 2) for t in trajectory]
        results.append(res)
    for legs in base_legsets:
        for name in legs:
            if name not in new_legs and name not in seen:
                seen.add(name)
                results.append({"leg": name, "status": "regression",
                                "reason": "leg missing from fresh "
                                          "report"})
    ok = all(r["status"] != "regression" for r in results)
    return {"ok": ok, "floor_tol": floor_tol, "legs": results}


def compare_ops(new: dict, base: dict,
                threshold: float = OP_THRESHOLD) -> dict:
    """Per-op gate (same policy as tools/check_op_bench.py): fail on
    ratio > threshold or a newly-failing op; skip entirely on a
    device_kind mismatch."""
    if new.get("device_kind") != base.get("device_kind"):
        return {"ok": True, "skipped": True,
                "reason": f"device_kind {new.get('device_kind')!r} != "
                          f"baseline {base.get('device_kind')!r}"}
    regressions, missing = [], []
    for name, b_us in (base.get("ops") or {}).items():
        r_us = (new.get("ops") or {}).get(name)
        if r_us is None:
            missing.append(name)
            continue
        ratio = r_us / b_us if b_us else 0.0
        if ratio > threshold:
            regressions.append({"op": name, "base_us": b_us,
                                "new_us": r_us,
                                "ratio": round(ratio, 3)})
    return {"ok": not regressions and not missing,
            "threshold": threshold, "regressions": regressions,
            "missing": missing}


# ---------------------------------------------------------------------------
# smoke mode: prove the gate logic on committed fixtures (no bench run)
# ---------------------------------------------------------------------------

def _degrade(doc: dict, factor: float) -> dict:
    """A synthetically slower copy of a bench report: every leg's value
    and window stats scaled by ``factor``."""
    out = json.loads(json.dumps(doc))
    for leg in extract_legs(out).values():
        leg["value"] = leg["value"] * factor
        for k in ("median", "p10", "p90", "min", "max"):
            if k in (leg.get("stats") or {}):
                leg["stats"][k] = leg["stats"][k] * factor
    return out


def smoke_trajectory() -> List[dict]:
    """Two synthetic reports in the shape ``bench.py`` prints (a
    flagship plus its seq512 leg), older first.  Made-up values on a
    made-up device: the repo holds no capture of this installation."""
    def leg(metric, median):
        return {"metric": metric, "value": median, "unit": "samples/sec",
                "device_kind": "smoke-device", "anomaly": None,
                "stats": {"windows": 6, "steps_per_window": 5,
                          "median": median, "p10": median * 0.99,
                          "p90": median * 1.01, "min": median * 0.985,
                          "max": median * 1.015}}

    docs = []
    for scale in (0.95, 1.0):
        doc = leg("smoke_flagship", 1000.0 * scale)
        doc["legs"] = {"seq512": leg("smoke_seq512", 300.0 * scale)}
        docs.append(doc)
    return docs


def run_smoke() -> int:
    """Assert the gate's pass/fail behavior against synthetic reports
    (:func:`smoke_trajectory`) + the op_bench_baseline.json fixture.
    Returns 0 when every assertion holds (tier-1 wires this via
    tests/test_lint.py)."""
    docs = smoke_trajectory()
    latest = docs[-1]
    checks = []

    def check(name, cond, detail=""):
        checks.append((name, bool(cond), detail))

    # unchanged tree: the latest capture gated against the full
    # trajectory (itself last) must pass
    r = compare_bench(latest, docs)
    check("unchanged-tree passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    # a 30% slowdown must fail (far past the 10% drift floor + spread)
    r = compare_bench(_degrade(latest, 0.70), docs)
    check("30%-degraded fails", not r["ok"])
    # a 3% wiggle is inside the noise floor: must NOT flap
    r = compare_bench(_degrade(latest, 0.97), docs)
    check("3%-wiggle passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    # a vanished leg must fail
    pruned = json.loads(json.dumps(latest))
    if pruned.get("legs"):
        pruned["legs"].pop(sorted(pruned["legs"])[0], None)
        r = compare_bench(pruned, docs)
        check("missing-leg fails", not r["ok"])
    # device-kind mismatch must skip, not fail
    other = json.loads(json.dumps(latest))
    for leg in extract_legs(other).values():
        leg["device_kind"] = "TPU v9000"
    r = compare_bench(other, docs)
    check("device-mismatch skips", r["ok"] and any(
        x["status"] == "skipped" for x in r["legs"]))

    # decode leg (synthetic until a BENCH_r* capture carries it): the
    # generic noise-aware gate applies, plus the speedup-collapse rule
    decode_leg = {
        "metric": "llama_decode_tokens_per_sec_per_chip",
        "value": 2500.0, "unit": "tokens/sec/chip",
        "device_kind": "cpu",
        "stats": {"rounds": 3, "median": 2500.0, "p10": 2300.0,
                  "p90": 2700.0, "min": 2250.0, "max": 2750.0},
        "speedup_vs_static": 2.4,
    }
    with_decode = json.loads(json.dumps(latest))
    with_decode.setdefault("legs", {})["llama_decode"] = decode_leg
    r = compare_bench(with_decode, docs + [with_decode])
    check("decode self-compare passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    r = compare_bench(_degrade(with_decode, 0.70), docs + [with_decode])
    check("decode 30%-degraded fails", not r["ok"])
    collapsed = json.loads(json.dumps(with_decode))
    collapsed["legs"]["llama_decode"]["speedup_vs_static"] = 0.8
    r = compare_bench(collapsed, docs + [with_decode])
    check("decode speedup-collapse fails", not r["ok"] and any(
        x["status"] == "regression" and "speedup" in x.get("reason", "")
        for x in r["legs"]))

    # disagg leg (synthetic until a BENCH_r* capture carries it):
    # generic noise gate + the decode-step p99 collapse rule (arms
    # only where the baseline proved the < 1.0 win) + the
    # vacuous-None hard rule
    disagg_leg = {
        "metric": "llama_disagg_tokens_per_sec",
        "value": 1900.0, "unit": "tokens/sec",
        "device_kind": "cpu",
        "stats": {"rounds": 3, "median": 1900.0, "p10": 1780.0,
                  "p90": 2050.0, "min": 1750.0, "max": 2100.0},
        "colocated_tokens_per_sec": 1850.0,
        "disagg_vs_colocated_tokens": 1.03,
        "disagg_vs_colocated_p99": 0.62,
        "p99_step_ms": 3.1, "colocated_p99_step_ms": 5.0,
        "handoffs": 48,
    }
    with_disagg = json.loads(json.dumps(latest))
    with_disagg.setdefault("legs", {})["llama_disagg"] = disagg_leg
    r = compare_bench(with_disagg, docs + [with_disagg])
    check("disagg self-compare passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    r = compare_bench(_degrade(with_disagg, 0.70),
                      docs + [with_disagg])
    check("disagg 30%-degraded fails", not r["ok"])
    p99_collapse = json.loads(json.dumps(with_disagg))
    p99_collapse["legs"]["llama_disagg"]["disagg_vs_colocated_p99"] \
        = 1.6
    r = compare_bench(p99_collapse, docs + [with_disagg])
    check("disagg p99-collapse fails", not r["ok"] and any(
        x["status"] == "regression"
        and "decode-step p99" in x.get("reason", "")
        for x in r["legs"]))
    # ...but a > 1.0 ratio must NOT flap when the baseline never
    # proved the win (core-bound CPU smoke captures) — 1.05 sits in
    # the (1.0, 1.0+tol] noise gap, the sharpest non-proof
    never_won_d = json.loads(json.dumps(with_disagg))
    never_won_d["legs"]["llama_disagg"]["disagg_vs_colocated_p99"] \
        = 1.05
    r = compare_bench(p99_collapse, docs + [never_won_d])
    check("disagg >1.0 p99 vs >1.0 baseline passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    vacuous_d = json.loads(json.dumps(with_disagg))
    vacuous_d["legs"]["llama_disagg"]["disagg_vs_colocated_p99"] = None
    r = compare_bench(vacuous_d, docs + [with_disagg])
    check("disagg vacuous-None fails", not r["ok"] and any(
        x["status"] == "regression"
        and "vacuous A/B" in x.get("reason", "") for x in r["legs"]))

    # spec-decode leg (synthetic capable-host fixture, like the
    # sharded one: core-bound CPU captures flag the speedup anomalous,
    # so the >1.0 ratio is proven on fixture numbers): generic noise
    # gate + the acceptance floor / rollback balance / leaked pages
    # hard rules (which no anomaly flag shields) + the
    # spec-vs-plain collapse rule (which arms only where the baseline
    # proved the win)
    spec_leg = {
        "metric": "llama_spec_decode_tokens_per_sec_per_chip",
        "value": 2600.0, "unit": "tokens/sec/chip",
        "device_kind": "cpu",
        "stats": {"rounds": 3, "median": 2600.0, "p10": 2450.0,
                  "p90": 2750.0, "min": 2400.0, "max": 2800.0},
        "plain_tokens_per_sec": 1900.0,
        "spec_vs_plain_tokens": 1.37,
        "acceptance_rate": 0.62, "acceptance_floor": 0.3,
        "spec_drafts": 400, "spec_tokens_proposed": 1500,
        "spec_tokens_accepted": 930, "spec_rollbacks": 210,
        "leaked_pages": 0,
    }
    with_spec = json.loads(json.dumps(latest))
    with_spec.setdefault("legs", {})["llama_spec_decode"] = spec_leg
    r = compare_bench(with_spec, docs + [with_spec])
    check("spec self-compare passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    r = compare_bench(_degrade(with_spec, 0.70), docs + [with_spec])
    check("spec 30%-degraded fails", not r["ok"])
    low_accept = json.loads(json.dumps(with_spec))
    low_accept["legs"]["llama_spec_decode"]["acceptance_rate"] = 0.05
    # an anomaly flag must NOT shield a dead drafter
    low_accept["legs"]["llama_spec_decode"]["anomaly"] = \
        "core-bound host"
    r = compare_bench(low_accept, docs + [with_spec])
    check("spec acceptance-floor breach fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "acceptance rate" in x.get("reason", "")
              for x in r["legs"]))
    vac_accept = json.loads(json.dumps(with_spec))
    vac_accept["legs"]["llama_spec_decode"]["acceptance_rate"] = None
    r = compare_bench(vac_accept, docs + [with_spec])
    check("spec vacuous-acceptance fails", not r["ok"] and any(
        x["status"] == "regression"
        and "vacuous" in x.get("reason", "") for x in r["legs"]))
    spec_collapse = json.loads(json.dumps(with_spec))
    spec_collapse["legs"]["llama_spec_decode"]["spec_vs_plain_tokens"] \
        = 0.8
    r = compare_bench(spec_collapse, docs + [with_spec])
    check("spec slower-than-plain collapse fails", not r["ok"] and any(
        x["status"] == "regression"
        and "spec_vs_plain_tokens" in x.get("reason", "")
        for x in r["legs"]))
    # ...but a sub-1.0 ratio must NOT flap when the baseline never
    # proved the win (core-bound CPU smoke captures)
    never_won_s = json.loads(json.dumps(with_spec))
    never_won_s["legs"]["llama_spec_decode"]["spec_vs_plain_tokens"] \
        = 0.9
    r = compare_bench(spec_collapse, docs + [never_won_s])
    check("spec sub-1.0 vs sub-1.0 baseline passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    imbalance = json.loads(json.dumps(with_spec))
    imbalance["legs"]["llama_spec_decode"]["spec_tokens_accepted"] \
        = 1600
    imbalance["legs"]["llama_spec_decode"]["anomaly"] = \
        "core-bound host"
    r = compare_bench(imbalance, docs + [with_spec])
    check("spec accept>propose imbalance fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "overcounts" in x.get("reason", "")
              for x in r["legs"]))
    rb_imbalance = json.loads(json.dumps(with_spec))
    rb_imbalance["legs"]["llama_spec_decode"]["spec_rollbacks"] = 500
    r = compare_bench(rb_imbalance, docs + [with_spec])
    check("spec rollback>draft imbalance fails", not r["ok"] and any(
        x["status"] == "regression"
        and "rollback bookkeeping" in x.get("reason", "")
        for x in r["legs"]))
    page_leak_s = json.loads(json.dumps(with_spec))
    page_leak_s["legs"]["llama_spec_decode"]["leaked_pages"] = 2
    page_leak_s["legs"]["llama_spec_decode"]["anomaly"] = \
        "core-bound host"
    r = compare_bench(page_leak_s, docs + [with_spec])
    check("spec leaked-pages fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "refcount leak" in x.get("reason", "")
              for x in r["legs"]))
    vac_leak = json.loads(json.dumps(with_spec))
    vac_leak["legs"]["llama_spec_decode"]["leaked_pages"] = None
    r = compare_bench(vac_leak, docs + [with_spec])
    check("spec vacuous-leak-count fails", not r["ok"] and any(
        x["status"] == "regression"
        and "vacuous drain" in x.get("reason", "")
        for x in r["legs"]))

    # recsys leg (synthetic until a BENCH_r* capture carries it):
    # generic noise gate + the degraded-lookup hard zero + the hot-row
    # hit-rate floor (both of which no anomaly flag shields)
    recsys_leg = {
        "metric": "recsys_closed_loop_qps",
        "value": 1800.0, "unit": "requests/sec", "device_kind": "cpu",
        "stats": {"rounds": 3, "median": 1800.0, "p10": 1700.0,
                  "p90": 1900.0, "min": 1650.0, "max": 1950.0},
        "p99_ms": 18.0,
        "hit_rate": {"hot": 0.82, "cold": 0.41}, "hit_floor": 0.5,
        "degraded_lookups": 0,
    }
    with_rec = json.loads(json.dumps(latest))
    with_rec.setdefault("legs", {})["wide_deep_recsys"] = recsys_leg
    r = compare_bench(with_rec, docs + [with_rec])
    check("recsys self-compare passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    r = compare_bench(_degrade(with_rec, 0.70), docs + [with_rec])
    check("recsys 30%-degraded fails", not r["ok"])
    degraded_rec = json.loads(json.dumps(with_rec))
    degraded_rec["legs"]["wide_deep_recsys"]["degraded_lookups"] = 3
    # an anomaly flag must NOT shield a degraded-lookup break
    degraded_rec["legs"]["wide_deep_recsys"]["anomaly"] = \
        "core-bound host"
    r = compare_bench(degraded_rec, docs + [with_rec])
    check("recsys degraded-lookups fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "degraded lookup" in x.get("reason", "")
              for x in r["legs"]))
    vac_degraded = json.loads(json.dumps(with_rec))
    vac_degraded["legs"]["wide_deep_recsys"]["degraded_lookups"] = None
    r = compare_bench(vac_degraded, docs + [with_rec])
    check("recsys vacuous-degraded-count fails", not r["ok"] and any(
        x["status"] == "regression"
        and "vacuous window" in x.get("reason", "")
        for x in r["legs"]))
    dead_cache = json.loads(json.dumps(with_rec))
    dead_cache["legs"]["wide_deep_recsys"]["hit_rate"]["hot"] = 0.3
    r = compare_bench(dead_cache, docs + [with_rec])
    check("recsys dead-hot-row-cache fails", not r["ok"] and any(
        x["status"] == "regression"
        and "hot-row hit rate" in x.get("reason", "")
        for x in r["legs"]))
    vac_hit = json.loads(json.dumps(with_rec))
    vac_hit["legs"]["wide_deep_recsys"]["hit_rate"]["hot"] = None
    r = compare_bench(vac_hit, docs + [with_rec])
    check("recsys vacuous-hit-rate fails", not r["ok"] and any(
        x["status"] == "regression"
        and "never probed" in x.get("reason", "") for x in r["legs"]))
    # chaos embedding pin-leak rule rides the chaos leg's counters
    # (synthetic leg: no checked-in capture carries one yet)
    chaos_rec = json.loads(json.dumps(latest))
    chaos_rec.setdefault("legs", {})["chaos"] = {
        "metric": "chaos_availability_pct", "value": 100.0,
        "unit": "percent", "device_kind": "cpu",
        "stats": {"rounds": 1, "median": 100.0, "p10": 100.0,
                  "p90": 100.0, "min": 100.0, "max": 100.0},
        "collateral_failures": 0, "poison_leaks": 0,
        "leaked_rows": 2,
    }
    r = compare_bench(chaos_rec, docs + [chaos_rec])
    check("chaos leaked-rows fails", not r["ok"] and any(
        x["status"] == "regression"
        and "pinned after" in x.get("reason", "")
        for x in r["legs"]))

    # sharded-serving leg (synthetic capable-host fixture: the 2-core
    # CI sim flags its own captures anomalous, so the >=2x dp contract
    # is proven here on fixture numbers): generic noise gate + the
    # speedup-vs-single floor + the p99 rule + the bit-exactness rule
    sharded_leg = {
        "metric": "sharded_serving_dp4_closed_loop_qps",
        "value": 4000.0, "unit": "requests/sec", "device_kind": "cpu",
        "n_devices": 8,
        "stats": {"rounds": 3, "median": 4000.0, "p10": 3800.0,
                  "p90": 4200.0, "min": 3750.0, "max": 4250.0},
        "p99_ms": 14.0, "single_qps": 1540.0, "single_p99_ms": 15.0,
        "speedup_vs_single": 2.6, "p99_vs_single": 0.93,
        "mp2_bit_exact": True,
    }
    with_sharded = json.loads(json.dumps(latest))
    with_sharded.setdefault("legs", {})["sharded_serving"] = sharded_leg
    r = compare_bench(with_sharded, docs + [with_sharded])
    check("sharded self-compare passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    r = compare_bench(_degrade(with_sharded, 0.70),
                      docs + [with_sharded])
    check("sharded 30%-degraded fails", not r["ok"])
    collapsed = json.loads(json.dumps(with_sharded))
    collapsed["legs"]["sharded_serving"]["speedup_vs_single"] = 1.4
    r = compare_bench(collapsed, docs + [with_sharded])
    check("sharded dp-speedup-collapse fails", not r["ok"] and any(
        x["status"] == "regression"
        and "speedup_vs_single" in x.get("reason", "")
        for x in r["legs"]))
    worse_p99 = json.loads(json.dumps(with_sharded))
    worse_p99["legs"]["sharded_serving"]["p99_vs_single"] = 1.8
    r = compare_bench(worse_p99, docs + [with_sharded])
    check("sharded worse-p99 fails", not r["ok"] and any(
        x["status"] == "regression" and "p99" in x.get("reason", "")
        for x in r["legs"]))
    inexact = json.loads(json.dumps(with_sharded))
    inexact["legs"]["sharded_serving"]["mp2_bit_exact"] = False
    # an anomaly flag must NOT shield a bit-exactness break
    inexact["legs"]["sharded_serving"]["anomaly"] = "core-bound host"
    r = compare_bench(inexact, docs + [with_sharded])
    check("sharded bit-exactness-break fails", not r["ok"] and any(
        x["status"] == "regression"
        and "bit-exact" in x.get("reason", "") for x in r["legs"]))
    # ...nor must an anomalous BASELINE (e.g. every capture from a
    # core-bound CI host) or a device-kind mismatch shield it
    anom_base = json.loads(json.dumps(with_sharded))
    anom_base["legs"]["sharded_serving"]["anomaly"] = "core-bound host"
    r = compare_bench(inexact, docs + [anom_base])
    check("sharded bit-exactness-break fails past anomalous baseline",
          not r["ok"])
    other_kind = json.loads(json.dumps(inexact))
    other_kind["legs"]["sharded_serving"]["device_kind"] = "TPU v9000"
    r = compare_bench(other_kind, docs + [with_sharded])
    check("sharded bit-exactness-break fails past device mismatch",
          not r["ok"])
    core_bound = json.loads(json.dumps(with_sharded))
    core_bound["legs"]["sharded_serving"]["anomaly"] = \
        "host has 2 cores for a 8-virtual-device CPU sim"
    core_bound["legs"]["sharded_serving"]["speedup_vs_single"] = 1.2
    r = compare_bench(core_bound, docs + [with_sharded])
    check("sharded core-bound capture skips", r["ok"] and any(
        x["leg"] == "sharded_serving" and x["status"] == "skipped"
        for x in r["legs"]))

    # router leg (synthetic capable-host fixture, like the sharded
    # one: the 2-core CI host flags its own captures anomalous, so the
    # >=2x-at-4-replicas and zero-rollout-failure contracts are proven
    # on fixture numbers): generic noise gate + the speedup_4v1 floor
    # + the rollout-failure rule (which no anomaly/mismatch shields)
    router_leg = {
        "metric": "router_fleet4_closed_loop_qps",
        "value": 3600.0, "unit": "requests/sec", "device_kind": "cpu",
        "stats": {"rounds": 3, "median": 3600.0, "p10": 3450.0,
                  "p90": 3750.0, "min": 3400.0, "max": 3800.0},
        "p99_ms": 16.0, "direct_qps": 1000.0, "direct_p99_ms": 15.0,
        "qps_by_replicas": {"1": 950.0, "2": 1880.0, "4": 3600.0},
        "speedup_4v1": 3.79, "p99_vs_direct": 1.07,
        "rollout": {"requests": 600, "ok": 588, "shed": 12,
                    "failed": 0, "rollout_s": 9.5},
    }
    with_router = json.loads(json.dumps(latest))
    with_router.setdefault("legs", {})["router"] = router_leg
    r = compare_bench(with_router, docs + [with_router])
    check("router self-compare passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    r = compare_bench(_degrade(with_router, 0.70), docs + [with_router])
    check("router 30%-degraded fails", not r["ok"])
    collapsed = json.loads(json.dumps(with_router))
    collapsed["legs"]["router"]["speedup_4v1"] = 1.5
    r = compare_bench(collapsed, docs + [with_router])
    check("router scaling-collapse fails", not r["ok"] and any(
        x["status"] == "regression"
        and "speedup_4v1" in x.get("reason", "") for x in r["legs"]))
    broken_rollout = json.loads(json.dumps(with_router))
    broken_rollout["legs"]["router"]["rollout"]["failed"] = 3
    # an anomaly flag must NOT shield a rollout-availability break
    broken_rollout["legs"]["router"]["anomaly"] = "core-bound host"
    r = compare_bench(broken_rollout, docs + [with_router])
    check("router rollout-failure fails", not r["ok"] and any(
        x["status"] == "regression"
        and "rolling restart" in x.get("reason", "")
        for x in r["legs"]))
    anom_router_base = json.loads(json.dumps(with_router))
    anom_router_base["legs"]["router"]["anomaly"] = "core-bound host"
    r = compare_bench(broken_rollout, docs + [anom_router_base])
    check("router rollout-failure fails past anomalous baseline",
          not r["ok"])
    vacuous = json.loads(json.dumps(with_router))
    vacuous["legs"]["router"]["rollout"] = {
        "requests": None, "ok": None, "shed": None, "failed": None,
        "error": "rollout traffic produced no report"}
    r = compare_bench(vacuous, docs + [with_router])
    check("router vacuous-rollout fails", not r["ok"] and any(
        x["status"] == "regression"
        and "no measured failure count" in x.get("reason", "")
        for x in r["legs"]))
    core_bound_router = json.loads(json.dumps(with_router))
    core_bound_router["legs"]["router"]["anomaly"] = \
        "host has 2 cores for 4 replica processes"
    core_bound_router["legs"]["router"]["speedup_4v1"] = 1.1
    r = compare_bench(core_bound_router, docs + [with_router])
    check("router core-bound capture skips", r["ok"] and any(
        x["leg"] == "router" and x["status"] == "skipped"
        for x in r["legs"]))

    # chaos leg (synthetic fixture like the router/sharded ones): the
    # generic noise gate applies, plus the collateral-failures /
    # poison-leak hard rules (which no anomaly or device mismatch
    # shields) and the availability floor (which the anomaly skip DOES
    # shield — core contention honestly slows recovery windows)
    chaos_leg = {
        "metric": "chaos_availability_pct",
        "value": 99.8, "unit": "%", "device_kind": "cpu",
        "stats": {"rounds": 1, "median": 99.8, "p10": 99.6,
                  "p90": 100.0, "min": 99.6, "max": 100.0},
        "availability_floor": 99.0,
        "collateral_failures": 0, "injected_failures": 9,
        "poison_leaks": 0, "p99_under_fault_ms": 45.0,
        "unexplained_deaths": 0,
        "usage_conservation_delta": 0,
        "hog_attribution_ratio": 0.97,
        "sketch_violations": 0,
        "requests": 960,
    }
    with_chaos = json.loads(json.dumps(latest))
    with_chaos.setdefault("legs", {})["chaos"] = chaos_leg
    r = compare_bench(with_chaos, docs + [with_chaos])
    check("chaos self-compare passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    collateral = json.loads(json.dumps(with_chaos))
    collateral["legs"]["chaos"]["collateral_failures"] = 1
    # an anomaly flag must NOT shield a containment break
    collateral["legs"]["chaos"]["anomaly"] = "core-bound host"
    r = compare_bench(collateral, docs + [with_chaos])
    check("chaos collateral-failure fails", not r["ok"] and any(
        x["status"] == "regression"
        and "collateral" in x.get("reason", "") for x in r["legs"]))
    anom_chaos_base = json.loads(json.dumps(with_chaos))
    anom_chaos_base["legs"]["chaos"]["anomaly"] = "core-bound host"
    r = compare_bench(collateral, docs + [anom_chaos_base])
    check("chaos collateral-failure fails past anomalous baseline",
          not r["ok"])
    vacuous_chaos = json.loads(json.dumps(with_chaos))
    vacuous_chaos["legs"]["chaos"]["collateral_failures"] = None
    r = compare_bench(vacuous_chaos, docs + [with_chaos])
    check("chaos vacuous-collateral fails", not r["ok"] and any(
        x["status"] == "regression"
        and "vacuous" in x.get("reason", "") for x in r["legs"]))
    leaked = json.loads(json.dumps(with_chaos))
    leaked["legs"]["chaos"]["poison_leaks"] = 2
    r = compare_bench(leaked, docs + [with_chaos])
    check("chaos poison-leak fails", not r["ok"] and any(
        x["status"] == "regression"
        and "poison" in x.get("reason", "") for x in r["legs"]))
    no_leak_field = json.loads(json.dumps(with_chaos))
    del no_leak_field["legs"]["chaos"]["poison_leaks"]
    r = compare_bench(no_leak_field, docs + [with_chaos])
    check("chaos missing-leak-count fails", not r["ok"] and any(
        x["status"] == "regression"
        and "poison-leak" in x.get("reason", "") for x in r["legs"]))
    page_leak = json.loads(json.dumps(with_chaos))
    page_leak["legs"]["chaos"]["leaked_pages"] = 3
    page_leak["legs"]["chaos"]["anomaly"] = "core-bound host"
    r = compare_bench(page_leak, docs + [with_chaos])
    check("chaos leaked-pages fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "refcount leak" in x.get("reason", "")
              for x in r["legs"]))
    alert_err = json.loads(json.dumps(with_chaos))
    alert_err["legs"]["chaos"]["alert_errors"] = 2
    alert_err["legs"]["chaos"]["anomaly"] = "core-bound host"
    r = compare_bench(alert_err, docs + [with_chaos])
    check("chaos alert-contract violation fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "burn-rate" in x.get("reason", "")
              for x in r["legs"]))
    unexplained = json.loads(json.dumps(with_chaos))
    unexplained["legs"]["chaos"]["unexplained_deaths"] = 1
    # forensics is a containment contract: no anomaly flag shields it
    unexplained["legs"]["chaos"]["anomaly"] = "core-bound host"
    r = compare_bench(unexplained, docs + [with_chaos])
    check("chaos unexplained-death fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "unexplained" in x.get("reason", "")
              for x in r["legs"]))
    vacuous_deaths = json.loads(json.dumps(with_chaos))
    vacuous_deaths["legs"]["chaos"]["unexplained_deaths"] = None
    r = compare_bench(vacuous_deaths, docs + [with_chaos])
    check("chaos vacuous-forensics fails", not r["ok"] and any(
        x["status"] == "regression"
        and "vacuous forensics" in x.get("reason", "")
        for x in r["legs"]))
    # usage-observatory hard rules: conservation hard-zeroes (and a
    # vacuous None fails), the hog attribution ratio has a 0.9 floor,
    # and the sketch memory bound hard-zeroes — none shielded by an
    # anomaly flag (attribution is a correctness contract, not perf)
    unconserved = json.loads(json.dumps(with_chaos))
    unconserved["legs"]["chaos"]["usage_conservation_delta"] = 3
    unconserved["legs"]["chaos"]["anomaly"] = "core-bound host"
    r = compare_bench(unconserved, docs + [with_chaos])
    check("chaos usage-conservation break fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "conserve" in x.get("reason", "")
              for x in r["legs"]))
    vacuous_usage = json.loads(json.dumps(with_chaos))
    vacuous_usage["legs"]["chaos"]["usage_conservation_delta"] = None
    r = compare_bench(vacuous_usage, docs + [with_chaos])
    check("chaos vacuous usage-conservation fails",
          not r["ok"] and any(
              x["status"] == "regression"
              and "vacuous" in x.get("reason", "")
              and "attribution" in x.get("reason", "")
              for x in r["legs"]))
    misattributed = json.loads(json.dumps(with_chaos))
    misattributed["legs"]["chaos"]["hog_attribution_ratio"] = 0.4
    r = compare_bench(misattributed, docs + [with_chaos])
    check("chaos hog-attribution floor fails", not r["ok"] and any(
        x["status"] == "regression"
        and "0.9 floor" in x.get("reason", "") for x in r["legs"]))
    vacuous_attr = json.loads(json.dumps(with_chaos))
    vacuous_attr["legs"]["chaos"]["hog_attribution_ratio"] = None
    r = compare_bench(vacuous_attr, docs + [with_chaos])
    check("chaos vacuous hog-attribution fails", not r["ok"] and any(
        x["status"] == "regression"
        and "never attributed" in x.get("reason", "")
        for x in r["legs"]))
    sketch_burst = json.loads(json.dumps(with_chaos))
    sketch_burst["legs"]["chaos"]["sketch_violations"] = 2
    r = compare_bench(sketch_burst, docs + [with_chaos])
    check("chaos sketch-bound violation fails", not r["ok"] and any(
        x["status"] == "regression"
        and "sketch" in x.get("reason", "") for x in r["legs"]))
    harness_err = json.loads(json.dumps(with_chaos))
    harness_err["legs"]["chaos"]["harness_ok"] = False
    harness_err["legs"]["chaos"]["errors"] = {
        "hang": "liveness watchdog never SIGKILLed the hung replica"}
    harness_err["legs"]["chaos"]["anomaly"] = "core-bound host"
    r = compare_bench(harness_err, docs + [with_chaos])
    check("chaos harness-error fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "harness" in x.get("reason", "") for x in r["legs"]))
    low_avail = json.loads(json.dumps(with_chaos))
    low_avail["legs"]["chaos"]["value"] = 98.2
    low_avail["legs"]["chaos"]["stats"] = {
        "rounds": 1, "median": 98.2, "p10": 98.0, "p90": 98.4}
    r = compare_bench(low_avail, docs + [with_chaos])
    check("chaos availability-floor fails", not r["ok"] and any(
        x["status"] == "regression"
        and "budget" in x.get("reason", "") for x in r["legs"]))
    low_avail_anom = json.loads(json.dumps(low_avail))
    low_avail_anom["legs"]["chaos"]["anomaly"] = "core-bound host"
    r = compare_bench(low_avail_anom, docs + [with_chaos])
    check("chaos core-bound low availability skips", r["ok"] and any(
        x["leg"] == "chaos" and x["status"] == "skipped"
        for x in r["legs"]))

    # rollout leg (synthetic fixture like the chaos one): generic
    # noise gate + the torn-version / false-revert / revert-latency
    # hard rules, which no anomaly flag or device mismatch shields
    rollout_leg = {
        "metric": "rollout_availability_pct",
        "value": 99.9, "unit": "%", "device_kind": "cpu",
        "stats": {"rounds": 1, "median": 99.9, "p10": 99.7,
                  "p90": 100.0, "min": 99.7, "max": 100.0},
        "availability_floor": 99.0,
        "rollout": {"failed": 0, "torn_responses": 0,
                    "swaps": 3, "converged": True},
        "canary": {"false_reverts": 0, "reverts": 1,
                   "revert_latency_s": 0.8,
                   "revert_latency_bound_s": 6.0,
                   "promotions": 1},
    }
    with_rollout = json.loads(json.dumps(latest))
    with_rollout.setdefault("legs", {})["rollout"] = rollout_leg
    r = compare_bench(with_rollout, docs + [with_rollout])
    check("rollout self-compare passes", r["ok"],
          json.dumps([x for x in r["legs"]
                      if x["status"] == "regression"]))
    torn = json.loads(json.dumps(with_rollout))
    torn["legs"]["rollout"]["rollout"]["torn_responses"] = 1
    torn["legs"]["rollout"]["anomaly"] = "core-bound host"
    r = compare_bench(torn, docs + [with_rollout])
    check("rollout torn-version fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "torn-version" in x.get("reason", "")
              for x in r["legs"]))
    no_torn = json.loads(json.dumps(with_rollout))
    del no_torn["legs"]["rollout"]["rollout"]["torn_responses"]
    r = compare_bench(no_torn, docs + [with_rollout])
    check("rollout missing-torn-count fails", not r["ok"] and any(
        x["status"] == "regression"
        and "vacuous" in x.get("reason", "") for x in r["legs"]))
    false_rev = json.loads(json.dumps(with_rollout))
    false_rev["legs"]["rollout"]["canary"]["false_reverts"] = 1
    false_rev["legs"]["rollout"]["anomaly"] = "core-bound host"
    r = compare_bench(false_rev, docs + [with_rollout])
    check("canary false-revert fails even when anomalous",
          not r["ok"] and any(
              x["status"] == "regression"
              and "false positive" in x.get("reason", "")
              for x in r["legs"]))
    vac_canary = json.loads(json.dumps(with_rollout))
    vac_canary["legs"]["rollout"]["canary"]["false_reverts"] = None
    r = compare_bench(vac_canary, docs + [with_rollout])
    check("canary vacuous-soak fails", not r["ok"] and any(
        x["status"] == "regression"
        and "vacuous soak" in x.get("reason", "") for x in r["legs"]))
    slow_rev = json.loads(json.dumps(with_rollout))
    slow_rev["legs"]["rollout"]["canary"]["revert_latency_s"] = 9.5
    r = compare_bench(slow_rev, docs + [with_rollout])
    check("canary slow-revert fails", not r["ok"] and any(
        x["status"] == "regression"
        and "too slow" in x.get("reason", "") for x in r["legs"]))
    unmeasured_rev = json.loads(json.dumps(with_rollout))
    unmeasured_rev["legs"]["rollout"]["canary"]["revert_latency_s"] \
        = None
    r = compare_bench(unmeasured_rev, docs + [with_rollout])
    check("canary unmeasured-revert fails", not r["ok"] and any(
        x["status"] == "regression"
        and "unmeasured" in x.get("reason", "") for x in r["legs"]))

    # op gate on its own committed baseline
    op_base_path = os.path.join(REPO, "tools", "op_bench_baseline.json")
    with open(op_base_path, encoding="utf-8") as f:
        op_base = json.load(f)
    check("op self-compare passes", compare_ops(op_base, op_base)["ok"])
    op_bad = json.loads(json.dumps(op_base))
    first = sorted(op_bad["ops"])[0]
    op_bad["ops"][first] *= 2.0
    check("op 2x-regression fails",
          not compare_ops(op_bad, op_base)["ok"])
    op_missing = json.loads(json.dumps(op_base))
    op_missing["ops"].pop(first)
    check("op newly-failing fails",
          not compare_ops(op_missing, op_base)["ok"])
    op_other = json.loads(json.dumps(op_base))
    op_other["device_kind"] = "TPU v9000"
    check("op device-mismatch skips",
          compare_ops(op_other, op_base).get("skipped") is True)

    failed = [c for c in checks if not c[1]]
    for name, okay, detail in checks:
        print(f"  [{'ok' if okay else 'FAIL'}] {name}"
              + (f" -- {detail}" if detail and not okay else ""))
    print(f"smoke: {len(checks) - len(failed)}/{len(checks)} gate-logic "
          f"checks passed")
    return 1 if failed else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--report", help="fresh bench.py JSON report")
    ap.add_argument("--baseline", action="append", default=[],
                    help="baseline BENCH_r*.json (repeatable, "
                         "oldest->newest; last match per leg wins)")
    ap.add_argument("--op-report", help="fresh tools/op_bench.py JSON")
    ap.add_argument("--op-baseline",
                    default=os.path.join(REPO, "tools",
                                         "op_bench_baseline.json"))
    ap.add_argument("--floor-tol", type=float, default=FLOOR_TOL,
                    help="minimum relative tolerance (cross-run chip "
                         "drift floor; default 0.10)")
    ap.add_argument("--op-threshold", type=float, default=OP_THRESHOLD)
    ap.add_argument("--json", action="store_true",
                    help="emit the full verdict as JSON on stdout")
    ap.add_argument("--smoke", action="store_true",
                    help="self-test the gate logic on committed "
                         "fixtures and exit (no benchmark run)")
    args = ap.parse_args(argv)

    if args.smoke:
        return run_smoke()
    if not args.report and not args.op_report:
        ap.error("need --report and/or --op-report (or --smoke)")

    verdict = {"ok": True}
    if args.report:
        if not args.baseline:
            ap.error("--report needs at least one --baseline")
        bench = compare_bench(load_report(args.report),
                              [load_report(p) for p in args.baseline],
                              args.floor_tol)
        verdict["bench"] = bench
        verdict["ok"] &= bench["ok"]
    if args.op_report:
        with open(args.op_report, encoding="utf-8") as f:
            new_ops = json.load(f)
        with open(args.op_baseline, encoding="utf-8") as f:
            base_ops = json.load(f)
        ops = compare_ops(new_ops, base_ops, args.op_threshold)
        verdict["ops"] = ops
        verdict["ok"] &= ops["ok"]

    if args.json:
        print(json.dumps(verdict, indent=1, sort_keys=True))
    else:
        for leg in (verdict.get("bench") or {}).get("legs", []):
            line = f"  {leg['leg']:12s} {leg['status']:10s}"
            if "new_median" in leg and "base_median" in leg:
                line += (f" new {leg['new_median']:>10} vs base "
                         f"{leg['base_median']:>10} "
                         f"(tol {leg.get('tolerance')})")
            if "reason" in leg:
                line += f" -- {leg['reason']}"
            print(line)
        ops = verdict.get("ops")
        if ops:
            if ops.get("skipped"):
                print(f"  ops: SKIP -- {ops['reason']}")
            else:
                for r in ops.get("regressions", []):
                    print(f"  op {r['op']}: {r['ratio']}x "
                          f"({r['base_us']} -> {r['new_us']} us) "
                          f"<< REGRESSION")
                if ops.get("missing"):
                    print(f"  ops newly failing: {ops['missing']}")
        print("GATE " + ("PASSED" if verdict["ok"] else "FAILED"))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
