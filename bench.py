"""Flagship benchmark: BERT-base MLM pretraining step throughput.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": ...,
   "stats": {...}, "device_kind": ..., "anomaly": null|str,
   "legs": {"seq512": {...}}, ...}

Recipe (the credible BERT pretraining setup): bf16 AMP (white-list
autocast incl. bf16 activation stream, fp32 master weights), Adam with
linear warmup + global-norm gradient clipping, masked-position MLM head
(vocab projection on the P masked tokens only — the standard
create_pretraining_data format), input stream staged through the
DataLoader's device-prefetch path (no cached-batch feeding).

Every number quoted in this docstring was taken before PR 1 on another
installation and has not been re-measured on this one; PERF.md holds
what has.

Attention per leg (tools/attn_microbench.py scoreboard, fwd+bwd,
v5e):
  * seq-128: unfused batched-matmul chain (fastest at short seq —
    1212 samples/s vs 889 xla-einsum vs 855 packed-pallas at b160/192).
  * seq>=512: the packed pallas flash kernels (flash_attention_qkv) —
    fwd AND bwd kernels (FA2-style recompute, O(S) memory) consuming
    the fused [B,S,3H] projection directly, zero layout copies.
    Attention-only fwd+bwd at B=32,H=12,D=64: S=1024 14.6ms vs 23.7
    unfused; S=2048 35.8 vs 77.4. In-model at S=512: 289 vs 159
    samples/s (the unfused path O(S²)-materializes and can't hold
    the batch); round-5 leg batch 80 (282 vs 276.7 at 64, x2 A/B).

The round-4 perf walk at seq-512 (each same-session A/B):
  145.6 (r3 scan-vjp bwd) -> 174 (kernel bwd) -> 182 (block tuning) ->
  186 (AMP white-list for the attention op) -> 196 (packed QKV kernels)
  -> 215 (batch 64) -> 289 (mul op lowered as direct dot_general —
  the reshape-to-2D formulation cost ~3 GB/step of layout copies).
Same fixes at seq-128: 853 -> 873 (u8 dropout bits) -> 934 (remat
dropout, key-only residual) -> 1212 (dot_general mul + batch 192).

Dispatch: per-step (BENCH_DISPATCH=window runs a lax.scan device loop —
parallel/sharded.py build_sharded_multistep — measured ~3% slower
because per-step dispatch pipelines fine and the scan's while-loop
boundary inhibits cross-step fusion).

Measurement discipline (round-2 postmortem: a driver capture once
published 28.5 samples/s for a run that reproduces at 606 — chip
contention that the bench could neither detect nor explain):
  * W windows of K steps, each ended by waiting for the window's last
    loss (one fence per window, not per step).
  * reports median/p10/p90/min/max over windows + device_kind.
  * anomaly detection: windows whose duration drags the window spread
    (max/min) above 1.25x are re-run (bounded budget) before any number
    is published; if the spread still exceeds 2x the whole measurement
    re-runs once; if still anomalous the JSON carries "anomaly":
    <reason> so a garbage number can never be published silently.
  * a leg that raises fails the run: nothing is retried, rebuilt or
    turned into an "error" field, and too few devices is an error.
  * a `*_per_chip` metric names a chip's rate: a run whose device is
    not a TPU prints its metrics under the platform's name instead.
  * cross-RUN drift: the shared v5e chip was observed wandering +-10%
    between runs with BYTE-IDENTICAL compiled programs (cost_analysis
    equal, 694..792 samples/s across one session) — comparisons between
    configs are only meaningful back-to-back, and regressions smaller
    than ~10% cannot be attributed to code without a same-run A/B.

Baseline: the north-star (BASELINE.json) is ERNIE/BERT-base pretraining at
>=90% of reported 8xV100 throughput, per chip. The reference repo publishes
no number in-tree (BASELINE.md); we use the widely reported ~105
samples/sec/GPU for BERT-base seq-128 fp16 pretraining on V100 as the
per-chip baseline. vs_baseline = our samples/sec/chip / 105.

Config via env: BENCH_SEQ (128|512), BENCH_BATCH (per-chip),
BENCH_ATTN (unfused|xla|pallas), BENCH_LEGS=0 to skip the seq-512 leg,
BENCH_DROPOUT, BENCH_DISPATCH.
Serving-tier legs each gate on their own env switch (BENCH_SERVING,
BENCH_RECSYS, BENCH_SHARDED, BENCH_ROUTER, BENCH_DECODE,
BENCH_SPEC, BENCH_DISAGG, BENCH_CHAOS, BENCH_ROLLOUT — 0 skips).
The fleet legs (router, chaos, rollout) spawn replica processes that
each need a chip, so they run first, before this process touches JAX;
on a TPU host chaos and rollout are refused (their parent halves use
JAX while replicas hold the chips).

Measured dead ends (same-session A/B): pallas fused-dropout kernel
with in-kernel PRNG at seq-128 (775 vs 847 — pallas_call boundaries
cost more fusion than the in-kernel bits save); windowed-scan dispatch
(-3%); packed kernel at seq-128 (855 vs 1212 unfused — grid overhead
dominates at tiny per-cell work).

Round-5 profile-proof that unfused attention is XLA-optimal at seq-128
(VERDICT r4 #2 alternative): (a) attention is ~4% of the model FLOPs at
S=128 (4*H*S of ~15.6M per-token-layer FLOPs), so even a free kernel
buys <4%; (b) attention-only fwd+bwd at the flagship shape
(B=192,H=12,S=128,D=64): unfused XLA 4.77 ms vs pallas flash 7.96 ms
(bq=bk=128, best legal config — d=64 heads fill only half of the
128-lane registers per cell, while XLA batches all heads into one big
MXU matmul); (c) the step-time profile puts >50% in the large fused
matmuls and ~14% in layout copies, not attention. A third experiment —
replacing the per-grad global-norm-clip reduces with one concat+vdot
fusion — also LOST (1190 vs 1205 samples/s, x2 each): the concat's
0.4 GB materialization beats the ~200 small-reduce overhead it saves
(kept as PT_FUSED_GLOBAL_CLIP=1 opt-in in clip.py).

Known deviation from the reference recipe: the flash-attention path folds
out attention-probability dropout (output dropout kept) — reported in the
JSON as "deviations".
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

BASELINE_SAMPLES_PER_SEC_PER_CHIP = 105.0

WARMUP_WINDOWS = 2
WINDOWS = 6
STEPS_PER_WINDOW = 5

RERUN_SPREAD = 1.25       # window spread that triggers per-window re-runs
RERUN_BUDGET = 4          # max per-window re-runs per measurement
ANOMALY_SPREAD = 2.0      # spread that still flags after re-runs


def chip_name(name, device):
    """A `*_per_chip` metric (or `/chip` unit) names a chip's rate.
    Only a TPU run may carry it; anywhere else the same leg reports
    under the platform it ran on, so a CPU smoke number can never be
    read as a device metric."""
    if device.platform == "tpu":
        return name
    return name.replace("_per_chip", f"_on_{device.platform}").replace(
        "/chip", f"/{device.platform}-device")


def measure_windows(run_window, fence, state, *, n_windows,
                    rerun_spread=RERUN_SPREAD, rerun_budget=RERUN_BUDGET):
    """Time n_windows calls of run_window, each ended by fence(fetches).

    run_window(state) -> (state, fetches); fence(fetches) -> float loss
    (it waits for the window's last step).  Returns
    (dts, state, loss, n_reruns).  Exceptions propagate.
    Outlier policy (VERDICT r4 weak #3 — a 1.54x spread sailed through
    the old 2x-only gate): after the initial pass, the slowest window is
    re-timed while max/min spread exceeds rerun_spread, bounded by
    rerun_budget.
    """
    def one_window(state):
        t0 = time.perf_counter()
        state, fetches = run_window(state)
        loss = fence(fetches)
        return time.perf_counter() - t0, state, loss

    dts, loss = [], None
    for _ in range(n_windows):
        dt, state, loss = one_window(state)
        dts.append(dt)

    n_reruns = 0
    while (max(dts) / max(min(dts), 1e-9) > rerun_spread
           and n_reruns < rerun_budget):
        worst = dts.index(max(dts))  # slowest window = largest duration
        dt, state, loss = one_window(state)
        # keep the better timing: both time the same compiled program, so
        # a contention blip during the re-run must not replace a valid
        # measurement with a worse one
        dts[worst] = min(dts[worst], dt)
        n_reruns += 1
    return dts, state, loss, n_reruns


def measure_leg(rw, fence, state, *, B, n_chips):
    """Shared windowed-measurement harness for every bench leg: runs
    measure_windows (with its per-window outlier re-runs), classifies
    spread anomalies, and re-runs the whole measurement once before
    letting an anomalous number out.  Returns
    (per_chip, rates, spread, loss, anomaly, total_reruns, telemetry) —
    `telemetry` embeds a monitor.publish() counter snapshot plus a
    per-step duration histogram (paddle_tpu/telemetry.py Histogram
    p50/p95/p99) over this leg's measured windows, so every BENCH_*.json
    carries the observability trail, not just wall-clock."""
    total_reruns = 0
    for _attempt in range(2):
        dts, state, loss, n_reruns = measure_windows(
            rw, fence, state, n_windows=WINDOWS)
        total_reruns += n_reruns
        rates = [B * STEPS_PER_WINDOW / dt for dt in dts]
        med = float(np.median(rates))
        spread = max(rates) / max(min(rates), 1e-9)
        per_chip = med / n_chips
        anomaly = None
        if spread > ANOMALY_SPREAD:
            anomaly = (f"window spread {spread:.2f}x > {ANOMALY_SPREAD}x "
                       f"after {total_reruns} window re-runs "
                       f"(chip contention?): {sorted(rates)}")
        if anomaly is None:
            break  # clean measurement; else re-run once before publishing
    telemetry = leg_telemetry(dts)
    return per_chip, rates, spread, loss, anomaly, total_reruns, telemetry


def leg_telemetry(dts):
    """Per-leg telemetry block: cumulative monitor counters at leg end +
    a fixed-bucket step-duration histogram over the leg's own windows
    (fresh per leg — step times from one config must not pollute the
    percentiles of the next)."""
    from paddle_tpu.monitor import monitor as _monitor
    from paddle_tpu.telemetry import Histogram

    hist = Histogram("bench_step_ms")
    for dt in dts:
        hist.observe(dt * 1e3 / STEPS_PER_WINDOW)
    return {"monitor": dict(_monitor.publish()),
            "step_ms": hist.summary()}


def leg_stats(rates, n_chips, spread, reruns):
    """The published per-leg stats block (same fields for every leg)."""
    return {
        "windows": WINDOWS, "steps_per_window": STEPS_PER_WINDOW,
        "median": round(float(np.median(rates)) / n_chips, 2),
        "p10": round(float(np.percentile(rates, 10)) / n_chips, 2),
        "p90": round(float(np.percentile(rates, 90)) / n_chips, 2),
        "min": round(min(rates) / n_chips, 2),
        "max": round(max(rates) / n_chips, 2),
        "spread": round(spread, 3),
        "window_reruns": reruns,
    }


def bert_train_flops_per_sample(seq, vocab, hidden, layers_n, inter,
                                n_pred):
    """Analytic matmul FLOPs for one BERT MLM training sample.

    Per token, per layer: QKV proj 6H^2, attn scores+PV 4*H*S, out proj
    2H^2, FFN 4*H*I (each matmul = 2mk per output elem). MLM head runs on
    the n_pred gathered positions only: (2H^2 + 2*H*V) per prediction.
    Train = 3x forward (bwd ~ 2x fwd matmul FLOPs).
    """
    per_layer = 6 * hidden ** 2 + 2 * hidden ** 2 + 4 * hidden * seq \
        + 4 * hidden * inter
    head = 2 * hidden ** 2 + 2 * hidden * vocab
    fwd = layers_n * per_layer * seq + head * n_pred
    return 3.0 * fwd


def _efficiency_block(per_chip, flops_per_sample, manifest, device,
                      samples_per_exec):
    """The shared-cost-module efficiency fields every leg publishes:
    ``mfu`` (analytic model FLOPs — comparable across the BENCH_r*
    trajectory), ``hbm_peak_bytes`` / ``bw_util`` / ``xla_flops``
    (from the compiled executable's XLA manifest; None when the
    backend exposes no analysis), and the peak table actually used.
    A device the peak table does not know has no peak, so its ``mfu``
    and ``bw_util`` are None (single source for device_kind -> peak
    flops/bw: paddle_tpu/costmodel.py)."""
    from paddle_tpu import costmodel

    def r4(x):
        return None if x is None else round(x, 4)

    peaks = costmodel.device_peaks(device)
    out = {
        "mfu": r4(costmodel.mfu(per_chip * flops_per_sample, device)),
        "model_tflops_per_sample": round(flops_per_sample / 1e12, 4),
        "peak_tflops": None if peaks["peak_flops"] is None
        else round(peaks["peak_flops"] / 1e12, 1),
        "peak_source": peaks["source"],
        "hbm_peak_bytes": None,
        "bw_util": None,
    }
    if manifest:
        out["hbm_peak_bytes"] = manifest.get("peak_hbm_bytes")
        out["xla_flops_per_sample"] = round(
            manifest.get("flops", 0.0) / max(samples_per_exec, 1), 1)
        ba = manifest.get("bytes_accessed")
        if ba:
            bytes_per_sample = ba / max(samples_per_exec, 1)
            out["bw_util"] = r4(costmodel.bw_util(
                per_chip * bytes_per_sample, device))
    return out


def _fence(fetches):
    """End a window: wait for its last loss and insist it is finite."""
    loss = float(np.asarray(fetches[0]).reshape(-1)[0])
    if not np.isfinite(loss):
        raise RuntimeError(f"non-finite loss {loss}")
    return loss


def _make_host_batches(B, S, V, max_pred, n_distinct=4):
    rng = np.random.RandomState(0)
    host = []
    for _ in range(n_distinct):
        pos = np.sort(
            np.stack([rng.choice(S, max_pred, replace=False)
                      for _ in range(B)]), axis=1).astype("int64")
        host.append({
            "input_ids": rng.randint(0, V, (B, S)).astype("int64"),
            "token_type_ids": np.zeros((B, S), "int64"),
            "attn_mask": np.ones((B, S), "float32"),
            "mlm_positions": pos,
            "mlm_labels": rng.randint(0, V, (B, max_pred)).astype("int64"),
            "mlm_weights": np.ones((B, max_pred), "float32"),
        })
    return host


def _window_stream(feed_names, B, S, V, max_pred, mesh, k):
    """Endless stream of device-staged windows: each item is a tuple of
    [k, B, ...] arrays (k steps stacked), dp-sharded on the batch dim.

    Host batches are generated up front (host RNG off the timed path) and
    cycled; every yield is already on device via the DataLoader's
    double-buffer staging (reader.device_prefetch).
    """
    import itertools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.reader import device_prefetch

    host = _make_host_batches(B, S, V, max_pred, n_distinct=4)
    windows = []
    for w in range(len(host)):
        chunk = [host[(w + i) % len(host)] for i in range(k)]
        windows.append(tuple(
            np.stack([c[n] for c in chunk]) for n in feed_names))
    sh = NamedSharding(mesh, P(None, "dp"))
    stream = itertools.cycle(windows)
    return device_prefetch(stream, depth=2, device=sh)


def _step_stream(feed_names, B, S, V, max_pred, mesh):
    import itertools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.reader import device_prefetch

    host = _make_host_batches(B, S, V, max_pred, n_distinct=4)
    sh = NamedSharding(mesh, P("dp"))
    stream = (tuple(b[n] for n in feed_names)
              for b in itertools.cycle(host))
    return device_prefetch(stream, depth=2, device=sh)


def _attn_for(seq):
    """Default attention impl per sequence length (BENCH_ATTN overrides).

    unfused wins at 128; the pallas flash kernels win at >=512 (see
    module docstring scoreboard).
    """
    env = os.environ.get("BENCH_ATTN")
    choice = env if env else ("unfused" if seq < 512 else "pallas")
    table = {"1": True, "pallas": True, "0": False, "unfused": False,
             "xla": "xla"}
    if choice not in table:
        raise SystemExit(f"bench: unknown BENCH_ATTN={choice!r}; valid: "
                         "unfused | xla | pallas")
    return table[choice]


def build_bert_train_programs(cfg, *, learning_rate=None):
    """The flagship training recipe as (main, startup, feed_names, loss,
    bf16_stream): ``build_bert_pretrain(**cfg)`` under bf16 AMP with the
    activation-stream white list, Adam + global-norm clip.
    ``learning_rate=None`` is the recipe's 10 000-step linear warm-up to
    1e-4; ``chip_smoke.py`` passes a constant, because ten steps into
    that warm-up nothing moves."""
    import paddle_tpu as pt
    from paddle_tpu import clip, optimizer
    from paddle_tpu.contrib import mixed_precision
    from paddle_tpu.models import build_bert_pretrain

    main_p, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main_p, startup):
        feed_names, outs = build_bert_pretrain(**cfg)
        lr = learning_rate
        if lr is None:
            lr = pt.layers.linear_lr_warmup(1e-4, warmup_steps=10000,
                                            start_lr=0.0, end_lr=1e-4)
        opt = optimizer.AdamOptimizer(
            learning_rate=lr,
            grad_clip=clip.GradientClipByGlobalNorm(1.0)
            if os.environ.get("BENCH_CLIP", "1") == "1" else None)
        # bf16 activation stream: embeddings/layernorm/residual adds join
        # the white list (BENCH_BF16_STREAM=0 for the conservative
        # matmul-only autocast).  Master weights stay f32 either way; the
        # step is HBM-bound, so halving activation bytes is the lever.
        extra_white = []
        if os.environ.get("BENCH_BF16_STREAM", "1") == "1":
            extra_white = ["lookup_table", "lookup_table_v2", "layer_norm",
                           "elementwise_add", "elementwise_mul", "dropout",
                           "gelu", "relu", "scale", "transpose2",
                           "reshape2", "gather_nd", "squeeze2", "unsqueeze2",
                           "flash_attention", "flash_attention_qkv"]
            if os.environ.get("BENCH_BF16_SOFTMAX", "1") == "1":
                extra_white.append("softmax")
        opt = mixed_precision.decorate(
            opt, dtype="bfloat16",
            amp_lists=mixed_precision.AutoMixedPrecisionLists(
                custom_white_list=extra_white) if extra_white else None)
        opt.minimize(outs["loss"])
    return main_p, startup, feed_names, outs["loss"], bool(extra_white)


def run_config(seq, batch_per_chip, *, attn=None, dropout=0.1):
    """Build + measure one config; returns the result dict."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu import costmodel
    from paddle_tpu.parallel import (dp_mesh, build_sharded_step,
                                     build_sharded_multistep)

    n_chips = jax.device_count()
    device = jax.devices()[0]
    device_kind = getattr(device, "device_kind", str(device))
    mesh = dp_mesh(n_chips)
    # per-step is the measured default (windowed lax.scan dispatch was ~3%
    # slower — the While boundary inhibits cross-step fusion; VERDICT r4
    # weak #8)
    per_step_dispatch = os.environ.get("BENCH_DISPATCH", "step") == "step"

    B = batch_per_chip * n_chips
    max_pred = max(1, int(round(0.15 * seq)))
    hidden = int(os.environ.get("BENCH_HIDDEN", "768"))
    use_flash = _attn_for(seq) if attn is None else attn
    cfg = dict(batch_size=B, seq_len=seq, vocab_size=30522,
               hidden=hidden,
               num_layers=int(os.environ.get("BENCH_LAYERS", "12")),
               num_heads=max(1, hidden // 64),
               max_predictions=max_pred,
               use_flash=use_flash,
               dropout=dropout)
    cfg["intermediate"] = 4 * cfg["hidden"]
    main_p, startup, feed_names, loss_var, bf16_stream = \
        build_bert_train_programs(cfg)

    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)

    if per_step_dispatch:
        fn, mut_in, const_in, _ = build_sharded_step(
            main_p, feed_names, [loss_var.name], mesh)
        batches = _step_stream(feed_names, B, seq, cfg["vocab_size"],
                               max_pred, mesh)
    else:
        fn, mut_in, const_in, _ = build_sharded_multistep(
            main_p, feed_names, [loss_var.name], mesh,
            STEPS_PER_WINDOW)
        batches = _window_stream(feed_names, B, seq, cfg["vocab_size"],
                                 max_pred, mesh, STEPS_PER_WINDOW)
    mut_vals = tuple(scope.find_var(n) for n in mut_in)
    const_vals = tuple(scope.find_var(n) for n in const_in)

    # AOT-compile at the concrete first batch: same single XLA compile,
    # but the executable's cost/memory manifest becomes readable
    # (hbm_peak_bytes / bw_util in the published JSON)
    probe = next(batches)
    fn, manifest = costmodel.aot_compile(fn, probe, mut_vals, const_vals,
                                         np.int32(1))
    samples_per_exec = B if per_step_dispatch else B * STEPS_PER_WINDOW

    def run_window(step, mut_vals):
        if per_step_dispatch:
            for _ in range(STEPS_PER_WINDOW):
                step += 1
                fetches, mut_vals, _ = fn(next(batches), mut_vals,
                                          const_vals, np.int32(step))
        else:
            fetches, mut_vals, _ = fn(next(batches), mut_vals, const_vals,
                                      np.int32(step))
            step += STEPS_PER_WINDOW
        return step, mut_vals, fetches

    # warmup (compile + first dispatches), fenced
    step = 0
    for _ in range(WARMUP_WINDOWS):
        step, mut_vals, fetches = run_window(step, mut_vals)
    _fence(fetches)

    def rw(state):
        step, mut_vals = state
        step, mut_vals, fetches = run_window(step, mut_vals)
        return (step, mut_vals), fetches

    state = (step, mut_vals)
    (per_chip, rates, spread, loss, anomaly, total_reruns,
     telemetry) = measure_leg(rw, _fence, state, B=B, n_chips=n_chips)

    flops = bert_train_flops_per_sample(
        seq, cfg["vocab_size"], cfg["hidden"], cfg["num_layers"],
        cfg["intermediate"], max_pred)
    result = {
        "value": round(per_chip, 2),
        "unit": chip_name("samples/sec/chip", device),
        # the V100 baseline is a per-chip rate too
        "vs_baseline": round(per_chip / BASELINE_SAMPLES_PER_SEC_PER_CHIP,
                             3) if device.platform == "tpu" else None,
    }
    result.update(_efficiency_block(per_chip, flops, manifest, device,
                                    samples_per_exec))
    result.update({
        "stats": leg_stats(rates, n_chips, spread, total_reruns),
        "config": {"seq": seq, "batch_per_chip": batch_per_chip,
                   "max_predictions": max_pred, "n_chips": n_chips,
                   "amp": "bfloat16",
                   "bf16_stream": bf16_stream,
                   "attention": {True: "pallas", False: "unfused"}.get(
                       use_flash, use_flash),
                   "dispatch": "step" if per_step_dispatch else "window",
                   "head": "masked_gather"},
        "device_kind": device_kind,
        "final_loss": round(loss, 4),
        "anomaly": anomaly,
        "telemetry": telemetry,
        "deviations": (["flash attention folds out attention-probability "
                        "dropout (output dropout kept)"]
                       if use_flash is True and dropout else []),
    })
    return result


# ---------------------------------------------------------------------------
# ResNet-50 leg: the second tracked BASELINE config (ImageNet CNN training)
# ---------------------------------------------------------------------------

# analytic fwd matmul FLOPs for ResNet-50 at 224x224 (the standard ~4.1
# GFLOPs/inference figure); train = 3x fwd.  Conv FLOPs scale with the
# spatial area, so other image sizes scale by (size/224)^2.
RESNET50_FWD_FLOPS_224 = 4.089e9


def resnet50_train_flops_per_sample(image_size):
    return 3.0 * RESNET50_FWD_FLOPS_224 * (image_size / 224.0) ** 2


def _resnet_stream(B, image_size, mesh):
    import itertools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.reader import device_prefetch

    rng = np.random.RandomState(0)
    host = [(rng.rand(B, 3, image_size, image_size).astype("float32"),
             rng.randint(0, 1000, (B, 1)).astype("int64"))
            for _ in range(4)]
    sh = NamedSharding(mesh, P("dp"))
    return device_prefetch(itertools.cycle(host), depth=2, device=sh)


def run_resnet50(batch_per_chip=None, image_size=224):
    """ResNet-50 ImageNet training throughput: bf16 AMP (conv/matmul
    white list), momentum + L2-style global clip off (the PaddleClas
    recipe uses piecewise lr + momentum), measured with the same
    windowed/anomaly harness as the BERT flagship."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu import costmodel, optimizer
    from paddle_tpu.contrib import mixed_precision
    from paddle_tpu.models import build_resnet_train
    from paddle_tpu.parallel import dp_mesh, build_sharded_step

    n_chips = jax.device_count()
    device = jax.devices()[0]
    device_kind = getattr(device, "device_kind", str(device))
    mesh = dp_mesh(n_chips)
    if batch_per_chip is None:
        batch_per_chip = int(os.environ.get("BENCH_RESNET_BATCH", "64"))
    B = batch_per_chip * n_chips

    main_p, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main_p, startup):
        feed_names, outs = build_resnet_train(
            batch_size=B, depth=50, image_size=image_size, class_num=1000)
        opt = optimizer.MomentumOptimizer(0.1, momentum=0.9)
        opt = mixed_precision.decorate(opt, dtype="bfloat16")
        opt.minimize(outs["loss"])

    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    fn, mut_in, const_in, _ = build_sharded_step(
        main_p, feed_names, [outs["loss"].name], mesh)
    batches = _resnet_stream(B, image_size, mesh)
    mut_vals = tuple(scope.find_var(n) for n in mut_in)
    const_vals = tuple(scope.find_var(n) for n in const_in)
    probe = next(batches)
    fn, manifest = costmodel.aot_compile(fn, probe, mut_vals, const_vals,
                                         np.int32(1))

    def run_window(step, mut_vals):
        for _ in range(STEPS_PER_WINDOW):
            step += 1
            fetches, mut_vals, _ = fn(next(batches), mut_vals, const_vals,
                                      np.int32(step))
        return step, mut_vals, fetches

    step = 0
    for _ in range(WARMUP_WINDOWS):
        step, mut_vals, fetches = run_window(step, mut_vals)
    _fence(fetches)

    def rw(state):
        step, mut_vals = state
        step, mut_vals, fetches = run_window(step, mut_vals)
        return (step, mut_vals), fetches

    state = (step, mut_vals)
    (per_chip, rates, spread, loss, anomaly, total_reruns,
     telemetry) = measure_leg(rw, _fence, state, B=B, n_chips=n_chips)

    flops = resnet50_train_flops_per_sample(image_size)
    result = {
        "metric": chip_name(
            "resnet50_imagenet_train_samples_per_sec_per_chip", device),
        "value": round(per_chip, 2),
        "unit": chip_name("samples/sec/chip", device),
    }
    result.update(_efficiency_block(per_chip, flops, manifest, device,
                                    samples_per_exec=B))
    result.update({
        "stats": leg_stats(rates, n_chips, spread, total_reruns),
        "config": {"depth": 50, "image_size": image_size,
                   "batch_per_chip": batch_per_chip, "n_chips": n_chips,
                   "amp": "bfloat16", "optimizer": "momentum"},
        "device_kind": device_kind,
        "final_loss": round(loss, 4),
        "anomaly": anomaly,
        "telemetry": telemetry,
    })
    return result


# ---------------------------------------------------------------------------
# Serving leg: dynamic-batching engine throughput vs serial batch-1
# ---------------------------------------------------------------------------

def _load_serving_loadgen():
    """tools/ is scripts, not a package — load the loadgen by path."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "serving_loadgen.py")
    spec = importlib.util.spec_from_file_location("serving_loadgen", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_serving():
    """Serving throughput leg (`legs.serving`): an in-process
    dynamic-batching ServingEngine under the closed-loop loadgen
    (tools/serving_loadgen.py) vs. the same predictor driven serially at
    batch 1 — the speedup IS the batching+pool win.  An open-loop pass
    at ~60% of the measured closed-loop rate reports latency at a
    steady offered load.  Sized by BENCH_SERVING_{FEAT,HIDDEN,DEPTH,
    REQUESTS,WORKERS,MAX_BATCH}."""
    from paddle_tpu.serving import ServingEngine

    lg = _load_serving_loadgen()
    # weight-heavy MLP: batch-1 inference is memory-bound on streaming
    # the weights, so micro-batching amortizes exactly what serial pays
    # per request (measured CPU: ~7-9x closed-loop vs serial batch-1)
    feat = int(os.environ.get("BENCH_SERVING_FEAT", "256"))
    hidden = int(os.environ.get("BENCH_SERVING_HIDDEN", "2048"))
    depth = int(os.environ.get("BENCH_SERVING_DEPTH", "4"))
    n_req = int(os.environ.get("BENCH_SERVING_REQUESTS", "256"))
    workers = int(os.environ.get("BENCH_SERVING_WORKERS", "2"))
    max_batch = int(os.environ.get("BENCH_SERVING_MAX_BATCH", "8"))

    predictor, shapes = lg.build_synthetic(feat, hidden, depth)
    make_feed = lg.feed_maker(shapes, rows=1)

    # serial batch-1 baseline on the same (warmed) predictor
    predictor.warmup({n: (1,) + s for n, s in shapes.items()})
    n_serial = max(n_req // 4, 32)
    t0 = time.perf_counter()
    for i in range(n_serial):
        predictor.run(make_feed(i))
    serial_s = time.perf_counter() - t0
    serial_qps = n_serial / serial_s

    engine = ServingEngine(predictor.clone(), workers=workers,
                           max_batch=max_batch, max_delay_ms=2.0,
                           queue_cap=4 * n_req, deadline_ms=60000.0,
                           warmup_shapes=shapes)
    try:
        closed = lg.run_closed_loop(engine, make_feed, n_req,
                                    concurrency=2 * max_batch)
        open_rep = lg.run_open_loop(engine, make_feed,
                                    qps=max(closed["qps"] * 0.6, 50.0),
                                    duration_s=2.0)
    finally:
        engine.close()
    return {
        "metric": "serving_closed_loop_qps",
        "value": closed["qps"],
        "unit": "requests/sec",
        "serial_batch1_qps": round(serial_qps, 2),
        "speedup_vs_serial": round(closed["qps"] / serial_qps, 3),
        "closed": closed,
        "open": open_rep,
        "config": {"feat": feat, "hidden": hidden, "depth": depth,
                   "requests": n_req, "workers": workers,
                   "max_batch": max_batch},
    }


def run_recsys():
    """Recommender-serving leg (`legs.wide_deep_recsys`): closed-loop
    qps of the Wide&Deep small-feed path — sparse id slots through the
    ep-sharded embedding tier (hot-row cache in front of per-shard AOT
    gather executables) + dense floats through the serving net — under
    zipfian ids at two skews.  The hot skew is the production shape
    (its hit rate must clear the committed floor, carried in-leg as
    ``hit_floor``); the cold skew publishes the cache's sensitivity to
    skew.  ``degraded_lookups`` must stay 0 — every shard is alive for
    the whole leg, so a degraded row means the gather path broke (the
    gate hard-zeroes it).  The gather-path efficiency block reads
    flops/bytes off the largest compiled gather signature's XLA
    manifest through the shared cost module.  Sized by BENCH_RECSYS_
    {SLOTS,DENSE,VOCAB,DIM,SHARDS,CACHE_ROWS,REQUESTS,MAX_BATCH,
    ROUNDS,ZIPF_HOT,ZIPF_COLD,HIT_FLOOR}."""
    import jax

    from paddle_tpu.serving import ServingEngine, batcher
    from paddle_tpu.serving.embedding import build_recsys_predictor

    lg = _load_serving_loadgen()
    env = os.environ.get
    slots = int(env("BENCH_RECSYS_SLOTS", "26"))
    dense = int(env("BENCH_RECSYS_DENSE", "13"))
    vocab = int(env("BENCH_RECSYS_VOCAB", "100000"))
    dim = int(env("BENCH_RECSYS_DIM", "8"))
    shards = int(env("BENCH_RECSYS_SHARDS", "4"))
    cache_rows = int(env("BENCH_RECSYS_CACHE_ROWS", "4096"))
    n_req = int(env("BENCH_RECSYS_REQUESTS", "384"))
    max_batch = int(env("BENCH_RECSYS_MAX_BATCH", "64"))
    rounds = int(env("BENCH_RECSYS_ROUNDS", "3"))
    zipf_hot = float(env("BENCH_RECSYS_ZIPF_HOT", "1.2"))
    zipf_cold = float(env("BENCH_RECSYS_ZIPF_COLD", "0.8"))
    hit_floor = float(env("BENCH_RECSYS_HIT_FLOOR", "0.5"))
    # feed pool wide enough that the distinct-id working set overflows
    # the hot-row cache — otherwise both skews cache fully and the
    # hot/cold contrast (the leg's reason for two phases) is muted
    pool = int(env("BENCH_RECSYS_FEED_POOL", "512"))

    pred, shapes = build_recsys_predictor(
        num_sparse=slots, num_dense=dense, vocab=vocab, embed_dim=dim,
        shards=shards, cache_rows=cache_rows)
    # thousands-of-QPS small feeds ride the fan-in bucket ladder: tight
    # pow2 rungs at the small end where recsys batches actually land
    buckets = batcher.fanin_bucket_sizes(max_batch)
    engine = ServingEngine(pred, workers=2, max_batch=max_batch,
                           buckets=buckets, max_delay_ms=2.0,
                           queue_cap=4 * n_req, deadline_ms=60000.0,
                           warmup_shapes=shapes)
    cache = pred.table.cache
    t_wall = [0.0]

    def phase(skew, seed):
        make_feed = lg.recsys_feed_maker(slots, dense, vocab,
                                         zipf=skew, rows=1, seed=seed,
                                         pool_size=pool)
        # untimed warm round: pays the gather-pad + bucket compiles so
        # the measured rounds see steady state (the p10/p90 spread is
        # the gate's noise floor — a compile round would drown it)
        lg.run_closed_loop(engine, make_feed, n_req,
                           concurrency=2 * max_batch)
        # per-phase hit rate = hit delta over probe delta from a cold
        # cache, so neither the warm round's residency nor the other
        # skew's can pollute it
        cache.flush()
        s0 = cache.stats()
        reps = [lg.run_closed_loop(engine, make_feed, n_req,
                                   concurrency=2 * max_batch)
                for _ in range(rounds)]
        t_wall[0] += sum(r["wall_s"] for r in reps)
        s1 = cache.stats()
        probes = (s1["hits"] - s0["hits"]) \
            + (s1["misses"] - s0["misses"])
        hr = round((s1["hits"] - s0["hits"]) / probes, 4) \
            if probes else None
        return reps, hr

    try:
        hot_reps, hot_hr = phase(zipf_hot, seed=0)
        cold_reps, cold_hr = phase(zipf_cold, seed=1)
    finally:
        engine.close()

    hot_qps = [r["qps"] for r in hot_reps]
    med = float(np.median(hot_qps))
    emb = pred.embedding_stats()
    rows_per_sec = round(emb["counters"]["rows"] / max(t_wall[0], 1e-9),
                         1)
    # gather-path efficiency: rows/sec against the largest compiled
    # signature's manifest.  The gather is a pure memory op, so
    # bw_util is the meaningful number (mfu ~0 by construction)
    ginfo = pred.table.gather_cache_info()
    manifests = ginfo.get("manifests") or {}
    gather = {"compiled": ginfo.get("compiled"),
              "signatures": ginfo.get("signatures")}
    if manifests:
        sig = max(manifests, key=lambda k: int(k.rsplit("pad", 1)[1]))
        man = manifests[sig]
        pad = int(sig.rsplit("pad", 1)[1])
        flops_per_row = (man.get("flops") or 0.0) / pad
        gather["signature"] = sig
        gather["manifest"] = man
        if man:
            gather["efficiency"] = _efficiency_block(
                rows_per_sec, flops_per_row, man, jax.devices()[0],
                samples_per_exec=pad)
    device = jax.devices()[0]
    return {
        "metric": "recsys_closed_loop_qps",
        "value": round(med, 2),
        "unit": "requests/sec",
        "device_kind": getattr(device, "device_kind", str(device)),
        "stats": {"rounds": rounds, "median": round(med, 2),
                  "p10": round(float(np.percentile(hot_qps, 10)), 2),
                  "p90": round(float(np.percentile(hot_qps, 90)), 2),
                  "min": round(min(hot_qps), 2),
                  "max": round(max(hot_qps), 2)},
        "p99_ms": float(np.median(
            [r["latency_ms"].get("p99", 0.0) for r in hot_reps])),
        "hit_rate": {"hot": hot_hr, "cold": cold_hr},
        "hit_floor": hit_floor,
        "degraded_lookups": emb["counters"]["degraded"],
        "rows_per_sec": rows_per_sec,
        "qps_rounds": {"hot": hot_qps,
                       "cold": [r["qps"] for r in cold_reps]},
        "gather": gather,
        "embedding": emb,
        "closed_hot": hot_reps[-1],
        "config": {"slots": slots, "dense": dense, "vocab": vocab,
                   "dim": dim, "shards": shards,
                   "cache_rows": cache_rows, "requests": n_req,
                   "max_batch": max_batch, "rounds": rounds,
                   "buckets": list(buckets), "feed_pool": pool,
                   "zipf": {"hot": zipf_hot, "cold": zipf_cold}},
    }


# ---------------------------------------------------------------------------
# Sharded serving leg: dp replica groups + mp weight sharding (8-device sim)
# ---------------------------------------------------------------------------

def run_sharded_serving():
    """Sharded-serving leg (`legs.sharded_serving`): closed-loop qps of
    a :class:`~paddle_tpu.serving.ReplicaGroupEngine` at dp=2/4/8
    replica groups vs the single-chip ``ServingEngine`` baseline on an
    8-device mesh, plus an mp=2 weight-sharded group that must SERVE
    bit-exactly vs the unsharded predictor — the two contracts the
    sharded subsystem exists for (throughput multiplies with dp,
    capacity divides with mp, outputs never change).

    Per replica group the report carries fill (``avg_batch_rows``) and
    the group's own predict-latency p50/p99 (``ServingEngine.
    worker_health``).  The mp=2 group needs two devices: fewer is an
    error (on the CPU, force host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before
    starting); dp sizes past the device count are reported as skipped.
    On a host with fewer cores than devices the dp sweep is core-bound,
    so the leg flags ``anomaly`` — measured honestly, never gated
    (perf_gate skips anomalous legs; the >=2x dp=4 rule binds on
    capable hosts).
    Sized by BENCH_SHARDED_{FEAT,HIDDEN,DEPTH,REQUESTS,MAX_BATCH,
    ROUNDS,DP}."""
    import jax

    from paddle_tpu.serving import ReplicaGroupEngine, ServingEngine

    if len(jax.devices()) < 2:
        raise RuntimeError(
            f"sharded-serving leg needs at least 2 devices (its mp=2 "
            f"group), jax sees {len(jax.devices())}")
    lg = _load_serving_loadgen()
    env = os.environ.get
    feat = int(env("BENCH_SHARDED_FEAT", "64"))
    hidden = int(env("BENCH_SHARDED_HIDDEN", "256"))
    depth = int(env("BENCH_SHARDED_DEPTH", "2"))
    n_req = int(env("BENCH_SHARDED_REQUESTS", "96"))
    max_batch = int(env("BENCH_SHARDED_MAX_BATCH", "4"))
    rounds = int(env("BENCH_SHARDED_ROUNDS", "3"))
    dp_list = tuple(int(x) for x in
                    env("BENCH_SHARDED_DP", "2,4,8").split(","))

    predictor, shapes = lg.build_synthetic(feat, hidden, depth)
    make_feed = lg.feed_maker(shapes, rows=1)
    devices = jax.devices()
    engine_kw = dict(max_batch=max_batch, max_delay_ms=1.0,
                     queue_cap=4 * n_req, deadline_ms=60000.0,
                     warmup_shapes=shapes)

    # mp=2: a weight-sharded group must serve byte-identical outputs —
    # the "model bigger than a chip" leg's correctness contract
    ref = [predictor.run(make_feed(i))[0] for i in range(16)]
    mp_eng = ReplicaGroupEngine(predictor, groups=1, mp=2, **engine_kw)
    try:
        got = [mp_eng.predict(make_feed(i))[0] for i in range(16)]
        mp2_exact = all(np.array_equal(r, g)
                        for r, g in zip(ref, got))
        mp_health = _group_summaries(mp_eng.worker_health())
    finally:
        mp_eng.close()

    def closed(engine):
        return lg.run_closed_loop(engine, make_feed, n_req,
                                  concurrency=4 * max_batch)

    # single-chip baseline: one worker, one device — what dp=4 must 2x
    eng = ServingEngine(predictor.clone(), workers=1, **engine_kw)
    try:
        single_reps = [closed(eng) for _ in range(rounds)]
    finally:
        eng.close()
    single_qps = [r["qps"] for r in single_reps]
    single_med = float(np.median(single_qps))
    single_p99 = float(np.median(
        [r["latency_ms"].get("p99") or 0.0 for r in single_reps]))

    sweep = {}
    for g in dp_list:
        if g * 1 > len(devices):
            sweep[str(g)] = {"skipped": f"needs {g} devices, have "
                                        f"{len(devices)}"}
            continue
        eng = ReplicaGroupEngine(predictor, groups=g, mp=1, **engine_kw)
        try:
            reps = [closed(eng) for _ in range(rounds)]
            health = eng.worker_health()
        finally:
            eng.close()
        qps = [r["qps"] for r in reps]
        sweep[str(g)] = {
            "groups": g,
            "qps_median": round(float(np.median(qps)), 2),
            "qps_rounds": [round(q, 2) for q in qps],
            "p99_ms": float(np.median(
                [r["latency_ms"].get("p99") or 0.0 for r in reps])),
            "speedup_vs_single": round(
                float(np.median(qps)) / max(single_med, 1e-9), 3),
            "per_group": _group_summaries(health),
        }

    head = "4" if "4" in sweep and "qps_median" in sweep["4"] \
        else next((k for k in sweep if "qps_median" in sweep[k]), None)
    head_leg = sweep[head] if head else {"qps_rounds": [0.0],
                                         "qps_median": 0.0,
                                         "p99_ms": None}
    rates = head_leg["qps_rounds"]
    out = {
        "metric": f"sharded_serving_dp{head}_closed_loop_qps",
        "value": head_leg["qps_median"],
        "unit": "requests/sec",
        "device_kind": getattr(devices[0], "device_kind",
                               str(devices[0])),
        "n_devices": len(devices),
        "stats": {
            "rounds": rounds,
            "median": head_leg["qps_median"],
            "p10": round(float(np.percentile(rates, 10)), 2),
            "p90": round(float(np.percentile(rates, 90)), 2),
            "min": round(min(rates), 2),
            "max": round(max(rates), 2),
        },
        "p99_ms": head_leg["p99_ms"],
        "single_qps": round(single_med, 2),
        "single_p99_ms": round(single_p99, 3),
        "speedup_vs_single": head_leg.get("speedup_vs_single", 0.0),
        "p99_vs_single": round(
            (head_leg["p99_ms"] or 0.0) / max(single_p99, 1e-9), 3),
        "mp2_bit_exact": bool(mp2_exact),
        "mp2_groups": mp_health,
        "dp_sweep": sweep,
        "config": {"feat": feat, "hidden": hidden, "depth": depth,
                   "requests": n_req, "max_batch": max_batch,
                   "rounds": rounds, "dp": list(dp_list)},
    }
    cores = os.cpu_count() or 1
    if cores < len(devices):
        # 8 virtual devices multiplexed onto fewer host cores: every
        # replica group contends for the same ALUs, so dp cannot
        # multiply throughput here no matter how healthy the engine is
        out["anomaly"] = (
            f"host has {cores} cores for a {len(devices)}-virtual-"
            f"device CPU sim; dp replica scaling is core-bound and "
            f"speedup_vs_single is not meaningful")
    return out


def _group_summaries(health):
    """The per-group slice of ``worker_health`` the leg publishes:
    fill + the group's own latency percentiles + status."""
    out = []
    for h in health:
        pm = h.get("predict_ms") or {}
        out.append({"worker": h["worker"], "mesh": h.get("mesh"),
                    "devices": h.get("devices"),
                    "batches": h["batches"],
                    "avg_batch_rows": h.get("avg_batch_rows"),
                    "predict_ms_p50": pm.get("p50"),
                    "predict_ms_p99": pm.get("p99"),
                    "status": h.get("status")})
    return out


# ---------------------------------------------------------------------------
# Router leg: fleet front-end scaling + rolling-restart availability
# ---------------------------------------------------------------------------

def _refuse_on_tpu_host(leg):
    """Fleet legs spawn replica processes, and on a TPU host each of
    them needs a chip, which a parent that has initialised JAX holds
    (``FleetSupervisor.start`` refuses such a parent, so the router leg
    runs before this process touches JAX).  The chaos and rollout legs'
    parent halves themselves run JAX programs while the replicas are
    up: refused on a TPU host."""
    from paddle_tpu.serving.fleet import local_tpu_chips

    if local_tpu_chips():
        raise RuntimeError(
            f"bench leg {leg!r} is not brought up on a TPU host: its "
            f"parent half runs JAX programs while the replicas hold "
            f"the chips.  Set BENCH_{leg.upper()}=0")


def run_router():
    """Fleet-router leg (`legs.router`): closed-loop qps through the
    router tier at N=1/2/4 replica server PROCESSES vs the busiest
    replica driven direct (no router hop — the hop's overhead is the
    N=1 delta), plus a **rolling-restart availability pass**: open-loop
    traffic runs through the router while `FleetSupervisor.
    rolling_restart()` drains and replaces every replica one at a
    time — the pass publishes served/shed/failed counts and the
    perf gate fails any capture with a non-shed failure in the
    window.  Replica processes spawn via the fleet supervisor
    (stable ports, warmup-gated readiness), so the measured scaling
    includes real process/socket costs, not thread-pool costs.
    On hosts with fewer cores than replicas the sweep is core-bound
    and the leg flags `anomaly` (honestly measured, not gated).
    Sized by BENCH_ROUTER_{FEAT,HIDDEN,DEPTH,REQUESTS,MAX_BATCH,
    ROUNDS,REPLICAS}."""
    import threading

    from paddle_tpu.serving import FleetSupervisor, Router, RouterServer

    lg = _load_serving_loadgen()
    env = os.environ.get
    feat = int(env("BENCH_ROUTER_FEAT", "64"))
    hidden = int(env("BENCH_ROUTER_HIDDEN", "256"))
    depth = int(env("BENCH_ROUTER_DEPTH", "2"))
    n_req = int(env("BENCH_ROUTER_REQUESTS", "192"))
    max_batch = int(env("BENCH_ROUTER_MAX_BATCH", "8"))
    rounds = int(env("BENCH_ROUTER_ROUNDS", "3"))
    n_list = tuple(int(x) for x in
                   env("BENCH_ROUTER_REPLICAS", "1,2,4").split(","))
    n_max = max(n_list)

    make_feed = lg.feed_maker({"x": (feat,)}, rows=1)
    fleet = FleetSupervisor(
        replicas=n_max,
        replica_argv=["--feat", str(feat), "--hidden", str(hidden),
                      "--depth", str(depth),
                      "--max-batch", str(max_batch),
                      "--max-delay-ms", "2.0",
                      "--queue-cap", str(4 * n_req),
                      "--deadline-ms", "60000"])
    try:
        urls = fleet.wait_ready(timeout_s=300)

        # direct single-replica baseline: the router hop removed
        direct_reps = [lg.run_closed_loop_http(
            urls[0], make_feed, n_req, concurrency=2 * max_batch)
            for _ in range(rounds)]
        direct_qps = float(np.median([r["qps"] for r in direct_reps]))
        direct_p99 = float(np.median(
            [r["latency_ms"].get("p99") or 0.0 for r in direct_reps]))

        sweep = {}
        for n in n_list:
            router = Router(urls[:n], poll_interval_ms=100.0)
            server = RouterServer(router).start()
            try:
                router.poll_once()
                reps = [lg.run_closed_loop_http(
                    server.url, make_feed, n_req,
                    concurrency=2 * max_batch * n)
                    for _ in range(rounds)]
            finally:
                server.close()
            qps = [r["qps"] for r in reps]
            sweep[str(n)] = {
                "replicas": n,
                "qps_median": round(float(np.median(qps)), 2),
                "qps_rounds": [round(q, 2) for q in qps],
                "p99_ms": float(np.median(
                    [r["latency_ms"].get("p99") or 0.0 for r in reps])),
                "failed": int(sum(r["failed"] for r in reps)),
            }

        # rolling-restart availability: open-loop traffic through the
        # router across the WHOLE rollout window (back-to-back windows
        # until rolling_restart returns — a fixed duration could end
        # before a slow host finishes rolling and the tail of the
        # rollout would see no offered load, passing the zero-failure
        # contract vacuously); non-shed failures must be zero (gated
        # by tools/perf_gate.py)
        router = Router(urls, poll_interval_ms=100.0)
        server = RouterServer(router).start()
        rollout_rep = {}
        try:
            router.poll_once()
            target_qps = max(sweep[str(n_max)]["qps_median"] * 0.4, 20.0)
            window_s = float(env("BENCH_ROUTER_ROLLOUT_S", "10"))
            box = {"reps": [], "error": None, "last_end": None}
            stop = threading.Event()

            def _traffic():
                try:
                    while not stop.is_set():
                        box["reps"].append(lg.run_open_loop_http(
                            server.url, make_feed, qps=target_qps,
                            duration_s=window_s))
                        box["last_end"] = time.perf_counter()
                except Exception as e:  # noqa: BLE001 — recorded as
                    # a coverage failure below, never swallowed
                    box["error"] = f"{type(e).__name__}: {e}"

            t = threading.Thread(target=_traffic, daemon=True)
            t.start()
            time.sleep(0.5)  # traffic flowing before the rollout
            t_roll0 = time.perf_counter()
            fleet.rolling_restart(ready_timeout_s=180)
            t_roll1 = time.perf_counter()
            roll_s = t_roll1 - t_roll0
            stop.set()
            t.join(timeout=window_s + 60.0)
            reps = box["reps"]
            # covered: the traffic loop was still producing windows
            # when the rollout finished (its final window necessarily
            # ends after stop is set, i.e. after t_roll1)
            covered = (reps and box["error"] is None
                       and not t.is_alive()
                       and box["last_end"] is not None
                       and box["last_end"] >= t_roll1)
            if not covered:
                # the window measured NOTHING (or not the whole
                # rollout) — failed stays None, which the perf gate
                # treats as a regression (a vacuous pass must not
                # satisfy the zero-failure contract)
                rollout_rep = {
                    "requests": None, "ok": None, "shed": None,
                    "failed": None,
                    "error": box["error"]
                    or "rollout traffic did not cover the window",
                    "rollout_s": round(roll_s, 3),
                    "windows": len(reps),
                }
            else:
                def _tot(key):
                    return int(sum(r.get(key) or 0 for r in reps))
                rollout_rep = {
                    "requests": _tot("requests"),
                    "ok": _tot("ok"), "shed": _tot("shed"),
                    "failed": _tot("failed"),
                    "rollout_s": round(roll_s, 3),
                    "target_qps": round(target_qps, 2),
                    "windows": len(reps),
                    "p99_ms": max(
                        ((r.get("latency_ms") or {}).get("p99") or 0.0)
                        for r in reps),
                }
        finally:
            server.close()
    finally:
        fleet.close()

    head = sweep[str(n_max)]
    rates = head["qps_rounds"]
    # n1 None (replica count 1 not swept) must propagate as None:
    # a fabricated 0.0 speedup or 100% overhead would trip the
    # perf-gate collapse rule on a number that was never measured
    n1 = sweep.get("1", {}).get("qps_median")
    import jax  # the fleet is closed: this process may take a device now

    out = {
        "metric": f"router_fleet{n_max}_closed_loop_qps",
        "value": head["qps_median"],
        "unit": "requests/sec",
        "device_kind": getattr(jax.devices()[0], "device_kind",
                               str(jax.devices()[0])),
        "stats": {
            "rounds": rounds,
            "median": head["qps_median"],
            "p10": round(float(np.percentile(rates, 10)), 2),
            "p90": round(float(np.percentile(rates, 90)), 2),
            "min": round(min(rates), 2),
            "max": round(max(rates), 2),
        },
        "p99_ms": head["p99_ms"],
        "direct_qps": round(direct_qps, 2),
        "direct_p99_ms": round(direct_p99, 3),
        "router_overhead_pct": round(
            (1.0 - n1 / direct_qps) * 100.0, 2)
        if n1 and direct_qps else None,
        "qps_by_replicas": {k: v["qps_median"]
                            for k, v in sweep.items()},
        "speedup_4v1": round(head["qps_median"] / n1, 3)
        if n1 else None,
        "p99_vs_direct": round(
            (head["p99_ms"] or 0.0) / max(direct_p99, 1e-9), 3),
        "rollout": rollout_rep,
        "sweep": sweep,
        "config": {"feat": feat, "hidden": hidden, "depth": depth,
                   "requests": n_req, "max_batch": max_batch,
                   "rounds": rounds, "replicas": list(n_list)},
    }
    cores = os.cpu_count() or 1
    if cores < n_max + 1:
        # N replica processes PLUS the router process multiplexed onto
        # fewer host cores: the sweep contends for the same ALUs, so
        # replica scaling cannot show — measured honestly, never gated
        out["anomaly"] = (
            f"host has {cores} cores for {n_max} replica processes + "
            f"the router; fleet scaling is core-bound and speedup_4v1 "
            f"is not meaningful")
    return out


# ---------------------------------------------------------------------------
# Decode leg: KV-cached continuous batching tokens/sec vs static batch drain
# ---------------------------------------------------------------------------

def run_decode():
    """Autoregressive decode leg (`legs.llama_decode`) — the tracked
    Llama BASELINE config's first captured number (VERDICT.md gap).

    A KV-cached :class:`~paddle_tpu.serving.GenerationEngine` under the
    closed-loop generation loadgen (tools/serving_loadgen.py): requests
    draw long-tail output lengths (chat-style 75/25 short/long
    bimodal mix by default), the slot grid decodes
    every sequence at O(1)/token against donated per-slot caches, and
    finished sequences free their slot immediately.  The SAME engine
    with ``continuous=False`` (FIFO head-run: claim only into a fully
    drained grid) is the measured baseline — the speedup is the
    continuous-batching win at equal-or-better p99 (both p99s
    published; the headline ``value`` is continuous tokens/sec/chip).

    Efficiency: decode-step MFU = the decode executable's XLA manifest
    FLOPs x the measured grid step rate over the chip peak
    (costmodel), plus cache HBM bytes and the manifest's peak HBM.
    Sized by BENCH_DECODE_{VOCAB,HIDDEN,LAYERS,HEADS,KV_HEADS,INTER,
    SLOTS,MAX_SEQ,REQUESTS,OUT_MEAN,OUT_MAX,OUT_DIST} — CPU smoke
    defaults; a chip run sizes it to the Llama-2-7B proxy."""
    from paddle_tpu.serving import GenerationEngine

    lg = _load_serving_loadgen()
    env = os.environ.get
    vocab = int(env("BENCH_DECODE_VOCAB", "256"))
    hidden = int(env("BENCH_DECODE_HIDDEN", "64"))
    layers_n = int(env("BENCH_DECODE_LAYERS", "2"))
    heads = int(env("BENCH_DECODE_HEADS", "4"))
    kv_heads = int(env("BENCH_DECODE_KV_HEADS", str(heads)))
    inter = int(env("BENCH_DECODE_INTER", str(2 * hidden)))
    slots = int(env("BENCH_DECODE_SLOTS", "8"))
    max_seq = int(env("BENCH_DECODE_MAX_SEQ", "160"))
    n_req = int(env("BENCH_DECODE_REQUESTS", "48"))
    # decode-dominated defaults: chat-style bimodal outputs (75% short
    # / 25% long at mean 32 — the grid's longest sequence runs ~3.3x
    # the mean, the static batch-drain penalty; pure geometric caps at
    # ~2.7x and noise on a shared host eats the margin) over short
    # prompts, so tokens/sec measures the scheduler, not prefill
    # dispatch overhead
    out_mean = float(env("BENCH_DECODE_OUT_MEAN", "32"))
    out_max = int(env("BENCH_DECODE_OUT_MAX", "128"))
    out_dist = env("BENCH_DECODE_OUT_DIST", "bimodal")
    # clamp to what the engine can admit (largest default prefill
    # bucket = max_seq with one decode position reserved): an over-long
    # prompt is a submit-time ValueError, which the loadgen counts as
    # failed — an undercounted tokens/sec, not an error
    prompt_max = min(int(env("BENCH_DECODE_PROMPT_MAX", "8")),
                     max_seq - 1)
    model = dict(vocab_size=vocab, hidden=hidden, num_layers=layers_n,
                 num_heads=heads, num_kv_heads=kv_heads,
                 intermediate=inter)
    make_prompt = lg.prompt_maker(vocab, 4, prompt_max, out_mean,
                                  out_max, dist=out_dist)

    rounds = int(env("BENCH_DECODE_ROUNDS", "3"))

    def one_mode(continuous, n_rounds):
        """One engine, ``n_rounds`` measurement passes (first pass
        includes no compile — warmup() runs first).  Per-round
        tokens/sec feed the stats block the perf gate's noise model
        reads (serving throughput on a shared host wobbles well past
        the 10% drift floor)."""
        eng = GenerationEngine(model, num_slots=slots,
                               max_seq_len=max_seq,
                               max_new_tokens=out_max,
                               continuous=continuous,
                               queue_cap=4 * n_req,
                               deadline_ms=600000.0)
        eng.warmup()
        try:
            reps = [lg.run_closed_loop_generate(eng, make_prompt, n_req,
                                                concurrency=4 * slots)
                    for _ in range(n_rounds)]
            extras = {"decode_mfu": eng.decode_mfu(),
                      "manifest": eng.decode_manifest(),
                      "kv_cache_bytes": eng.kv_cache_bytes,
                      "slot_reclaims":
                          eng.stats()["counters"]["slot_reclaims"]}
        finally:
            eng.close()
        return reps, extras

    import jax

    device = jax.devices()[0]
    # both modes run the SAME number of rounds and compare medians:
    # serving throughput on a shared host wobbles enough that a
    # single-round static baseline dominates the speedup's noise
    static_reps, _static_extras = one_mode(False, rounds)
    cont_reps, extras = one_mode(True, rounds)
    rates = [r["tokens_per_sec"] for r in cont_reps]
    static_rates = [r["tokens_per_sec"] for r in static_reps]
    tps = float(np.median(rates))
    tps_static = float(np.median(static_rates))
    static_rep = static_reps[
        static_rates.index(sorted(static_rates)[len(static_rates) // 2])]
    cont_rep = cont_reps[rates.index(sorted(rates)[len(rates) // 2])]
    manifest = extras["manifest"] or {}
    return {
        "metric": chip_name(
            "llama_decode_tokens_per_sec_per_chip", device),
        "value": round(tps, 2),
        "unit": chip_name("tokens/sec/chip", device),
        "device_kind": getattr(device, "device_kind", str(device)),
        "stats": {
            "rounds": rounds,
            "median": round(tps, 2),
            "p10": round(float(np.percentile(rates, 10)), 2),
            "p90": round(float(np.percentile(rates, 90)), 2),
            "min": round(min(rates), 2),
            "max": round(max(rates), 2),
        },
        "p99_ms": cont_rep["latency_ms"].get("p99"),
        "static_tokens_per_sec": round(tps_static, 2),
        "static_stats": {
            "rounds": rounds,
            "median": round(tps_static, 2),
            "p10": round(float(np.percentile(static_rates, 10)), 2),
            "p90": round(float(np.percentile(static_rates, 90)), 2),
        },
        "static_p99_ms": static_rep["latency_ms"].get("p99"),
        "speedup_vs_static": round(tps / max(tps_static, 1e-9), 3),
        "decode_mfu": extras["decode_mfu"],
        "hbm_peak_bytes": manifest.get("peak_hbm_bytes"),
        "xla_flops_per_step": manifest.get("flops"),
        "kv_cache_bytes": extras["kv_cache_bytes"],
        "slot_reclaims": extras["slot_reclaims"],
        "closed": cont_rep,
        "static": static_rep,
        "config": {"vocab": vocab, "hidden": hidden, "layers": layers_n,
                   "heads": heads, "kv_heads": kv_heads, "inter": inter,
                   "slots": slots, "max_seq": max_seq,
                   "requests": n_req, "out_mean": out_mean,
                   "out_max": out_max, "out_dist": out_dist,
                   "prompt_max": prompt_max, "rounds": rounds},
    }


def run_spec_decode():
    """Speculative-vs-plain decode A/B (`legs.llama_spec_decode`):
    the SAME engine config (slots/pages/prefix reuse all equal)
    run twice per workload, differing only in ``speculate`` — the
    n-gram self-drafter + one-chunk verifier vs the one-token grid
    step.  Greedy argmax acceptance is bit-exact, so this leg gates
    *throughput shape*, not correctness (the exactness gates live in
    tests/test_spec_decode.py and the chaos ``spec_storm`` leg).

    Two workloads, acceptance rate reported for each: the
    repetition-heavy ``shared-prefix`` chat shape (fixed header +
    short random tail; greedy decode on the tiny bench model settles
    into cyclic continuations the prompt-lookup drafter predicts,
    while the random header feeds it spurious short-gram matches —
    measured acceptance lands near 0.3) carries the headline
    tokens/sec and the ``acceptance_floor`` gate, a TRIPWIRE set
    well under the measured rate: acceptance is deterministic given
    config (greedy argmax + history-only drafting), so a rate under
    the floor means the drafter or verifier broke, not that the chip
    was busy.  The ``mixed`` long-prompt/short-chat shape is the
    control — published, not floor-gated.
    ``spec_vs_plain_tokens`` is collapse-gated like
    ``speedup_vs_static`` (only where a baseline proved the win: on
    core-bound CPU hosts verify-chunk compute competes with the grid
    step and the ratio may sit under 1.0 — that is an anomaly flag,
    never a hard fail).  ``leaked_pages`` (pool live pages after
    drain + prefix flush, max over all four runs) and the rollback
    counter balance are hard-zeroed in tools/perf_gate.py on every
    host.  Sized by BENCH_SPEC_{VOCAB,HIDDEN,LAYERS,HEADS,KV_HEADS,
    INTER,SLOTS,MAX_SEQ,PAGE_TOKENS,PAGES,TOKENS,NGRAM,PREFIX,
    TAIL_MAX,LONG_TOKENS,REQUESTS,OUT_MEAN,OUT_MAX,ROUNDS,
    ACCEPT_FLOOR}."""
    from paddle_tpu.serving import GenerationEngine

    lg = _load_serving_loadgen()
    env = os.environ.get
    vocab = int(env("BENCH_SPEC_VOCAB", "256"))
    hidden = int(env("BENCH_SPEC_HIDDEN", "64"))
    layers_n = int(env("BENCH_SPEC_LAYERS", "2"))
    heads = int(env("BENCH_SPEC_HEADS", "4"))
    kv_heads = int(env("BENCH_SPEC_KV_HEADS", str(heads)))
    inter = int(env("BENCH_SPEC_INTER", str(2 * hidden)))
    slots = int(env("BENCH_SPEC_SLOTS", "8"))
    max_seq = int(env("BENCH_SPEC_MAX_SEQ", "256"))
    page_tokens = int(env("BENCH_SPEC_PAGE_TOKENS", "16"))
    num_pages = int(env("BENCH_SPEC_PAGES",
                        str(slots * max_seq // page_tokens + 1)))
    spec_tokens = int(env("BENCH_SPEC_TOKENS", "4"))
    spec_ngram = int(env("BENCH_SPEC_NGRAM", "3"))
    prefix_tokens = int(env("BENCH_SPEC_PREFIX", "64"))
    tail_max = int(env("BENCH_SPEC_TAIL_MAX", "8"))
    long_tokens = int(env("BENCH_SPEC_LONG_TOKENS", "96"))
    n_req = int(env("BENCH_SPEC_REQUESTS", "32"))
    out_mean = float(env("BENCH_SPEC_OUT_MEAN", "32"))
    out_max = int(env("BENCH_SPEC_OUT_MAX", "96"))
    rounds = int(env("BENCH_SPEC_ROUNDS", "3"))
    accept_floor = float(env("BENCH_SPEC_ACCEPT_FLOOR", "0.15"))
    model = dict(vocab_size=vocab, hidden=hidden, num_layers=layers_n,
                 num_heads=heads, num_kv_heads=kv_heads,
                 intermediate=inter)
    workloads = {
        "shared-prefix": lg.prompt_maker(
            vocab, 4, tail_max, out_mean, out_max, dist="bimodal",
            prompt_dist="shared-prefix", prefix_tokens=prefix_tokens),
        "mixed": lg.prompt_maker(
            vocab, 4, tail_max, out_mean, out_max, dist="bimodal",
            prompt_dist="mixed", long_tokens=long_tokens),
    }

    def one_mode(speculate, make_prompt):
        kw = dict(page_tokens=page_tokens, num_pages=num_pages,
                  prefix_reuse=True)
        if speculate:
            kw.update(speculate=True, spec_tokens=spec_tokens,
                      spec_ngram=spec_ngram)
        eng = GenerationEngine(model, num_slots=slots,
                               max_seq_len=max_seq,
                               max_new_tokens=out_max,
                               queue_cap=4 * n_req,
                               deadline_ms=600000.0, **kw)
        eng.warmup()
        try:
            reps = [lg.run_closed_loop_generate(eng, make_prompt,
                                                n_req,
                                                concurrency=2 * slots)
                    for _ in range(rounds)]
            st = eng.stats()
            # the hard-zero input: after the closed loop drains, the
            # only legitimate page holder is the prefix index — flush
            # it and anything still live is a leak (a rejected draft
            # whose rollback under-released, exactly what the
            # refcount discipline must never allow)
            if eng._prefix is not None:
                eng._prefix.flush()
            leaked = eng.stats()["paged"]["pages_live"]
            extras = {
                "p99_step_ms": st["decode_step_ms"].get("p99"),
                "p99_verify_ms": st["spec_verify_ms"].get("p99"),
                "speculate": st["speculate"],
                "leaked_pages": int(leaked),
            }
        finally:
            eng.close()
        return reps, extras

    def ab(make_prompt):
        plain_reps, plain_x = one_mode(False, make_prompt)
        spec_reps, spec_x = one_mode(True, make_prompt)
        rates = [r["tokens_per_sec"] for r in spec_reps]
        plain_rates = [r["tokens_per_sec"] for r in plain_reps]
        spec_rep = spec_reps[
            rates.index(sorted(rates)[len(rates) // 2])]
        plain_rep = plain_reps[
            plain_rates.index(
                sorted(plain_rates)[len(plain_rates) // 2])]
        return {
            "rates": rates,
            "plain_rates": plain_rates,
            "spec_rep": spec_rep,
            "plain_rep": plain_rep,
            "spec_x": spec_x,
            "plain_x": plain_x,
        }

    import jax

    device = jax.devices()[0]
    runs = {name: ab(mk) for name, mk in workloads.items()}
    head = runs["shared-prefix"]
    rates = head["rates"]
    tps = float(np.median(rates))
    tps_plain = float(np.median(head["plain_rates"]))
    leaked = max(r["spec_x"]["leaked_pages"] for r in runs.values())
    leaked = max(leaked, max(r["plain_x"]["leaked_pages"]
                             for r in runs.values()))

    def wl_summary(r):
        sp = r["spec_x"]["speculate"]
        return {
            "tokens_per_sec": round(
                float(np.median(r["rates"])), 2),
            "plain_tokens_per_sec": round(
                float(np.median(r["plain_rates"])), 2),
            "spec_vs_plain_tokens": round(
                float(np.median(r["rates"]))
                / max(float(np.median(r["plain_rates"])), 1e-9), 3),
            "acceptance_rate": sp["acceptance_rate"],
            "drafts": sp["drafts"],
            "tokens_proposed": sp["tokens_proposed"],
            "tokens_accepted": sp["tokens_accepted"],
            "rollbacks": sp["rollbacks"],
            "p99_verify_ms": r["spec_x"]["p99_verify_ms"],
        }

    sp = head["spec_x"]["speculate"]
    return {
        "metric": chip_name(
            "llama_spec_decode_tokens_per_sec_per_chip", device),
        "value": round(tps, 2),
        "unit": chip_name("tokens/sec/chip", device),
        "device_kind": getattr(device, "device_kind", str(device)),
        "stats": {
            "rounds": rounds,
            "median": round(tps, 2),
            "p10": round(float(np.percentile(rates, 10)), 2),
            "p90": round(float(np.percentile(rates, 90)), 2),
            "min": round(min(rates), 2),
            "max": round(max(rates), 2),
        },
        "plain_tokens_per_sec": round(tps_plain, 2),
        "spec_vs_plain_tokens": round(tps / max(tps_plain, 1e-9), 3),
        # headline acceptance = the repetition-heavy workload the
        # drafter is built for; the floor arms the perf_gate rule
        "acceptance_rate": sp["acceptance_rate"],
        "acceptance_floor": accept_floor,
        "spec_drafts": sp["drafts"],
        "spec_tokens_proposed": sp["tokens_proposed"],
        "spec_tokens_accepted": sp["tokens_accepted"],
        "spec_rollbacks": sp["rollbacks"],
        "leaked_pages": leaked,
        # client-observed inter-token gap: accepted tokens replay in a
        # burst per verify, so spec p99 reflects the verify cadence
        "p99_intertoken_ms":
            head["spec_rep"]["inter_token_ms"].get("p99"),
        "plain_p99_intertoken_ms":
            head["plain_rep"]["inter_token_ms"].get("p99"),
        "p99_verify_ms": head["spec_x"]["p99_verify_ms"],
        "p99_step_ms": head["spec_x"]["p99_step_ms"],
        "plain_p99_step_ms": head["plain_x"]["p99_step_ms"],
        "p99_ms": head["spec_rep"]["latency_ms"].get("p99"),
        "plain_p99_ms": head["plain_rep"]["latency_ms"].get("p99"),
        "workloads": {name: wl_summary(r)
                      for name, r in runs.items()},
        "closed": head["spec_rep"],
        "plain": head["plain_rep"],
        "config": {"vocab": vocab, "hidden": hidden,
                   "layers": layers_n, "heads": heads,
                   "kv_heads": kv_heads, "inter": inter,
                   "slots": slots, "max_seq": max_seq,
                   "page_tokens": page_tokens, "num_pages": num_pages,
                   "spec_tokens": spec_tokens,
                   "spec_ngram": spec_ngram,
                   "prefix_tokens": prefix_tokens,
                   "tail_max": tail_max, "long_tokens": long_tokens,
                   "requests": n_req, "out_mean": out_mean,
                   "out_max": out_max, "rounds": rounds},
    }


def run_disagg():
    """Disaggregated-vs-colocated A/B (`legs.llama_disagg`) on the
    MIXED long-prompt/short-chat workload at equal chip count: the
    disagg arm runs 1 prefill-role + 1 decode-role GenerationEngine
    chained by the in-process KV-segment handoff (DisaggPair); the
    colocated arm runs 2 'both'-role engines splitting the same
    requests.  Headline `value` is disagg tokens/sec; the gated ratio
    is **decode-step p99** disagg / colocated (`disagg_vs_colocated_
    p99`, < 1.0 = the long-prompt bursts stopped stalling decode —
    the reason the subsystem exists).  On a compute-saturated CPU
    smoke host both arms share 2 cores, so the ratio is captured
    honestly and the perf_gate collapse rule arms only where a
    baseline proved the win (like every other speedup rule).  Sized
    by BENCH_DISAGG_{VOCAB,HIDDEN,LAYERS,HEADS,KV_HEADS,INTER,SLOTS,
    MAX_SEQ,PAGE_TOKENS,CHUNK,LONG_TOKENS,LONG_FRAC,TAIL_MAX,
    REQUESTS,OUT_MEAN,OUT_MAX,ROUNDS,TRANSPORT}."""
    import threading

    from paddle_tpu.ops.registry import reset_op_seed
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.serving.disagg import (DeviceTransport, DisaggPair,
                                           HostBytesTransport)

    lg = _load_serving_loadgen()
    env = os.environ.get
    vocab = int(env("BENCH_DISAGG_VOCAB", "256"))
    hidden = int(env("BENCH_DISAGG_HIDDEN", "64"))
    layers_n = int(env("BENCH_DISAGG_LAYERS", "2"))
    heads = int(env("BENCH_DISAGG_HEADS", "4"))
    kv_heads = int(env("BENCH_DISAGG_KV_HEADS", str(heads)))
    inter = int(env("BENCH_DISAGG_INTER", str(2 * hidden)))
    slots = int(env("BENCH_DISAGG_SLOTS", "8"))
    max_seq = int(env("BENCH_DISAGG_MAX_SEQ", "256"))
    page_tokens = int(env("BENCH_DISAGG_PAGE_TOKENS", "16"))
    chunk = int(env("BENCH_DISAGG_CHUNK", "0"))
    long_tokens = int(env("BENCH_DISAGG_LONG_TOKENS", "96"))
    long_frac = float(env("BENCH_DISAGG_LONG_FRAC", "0.25"))
    tail_max = int(env("BENCH_DISAGG_TAIL_MAX", "8"))
    n_req = int(env("BENCH_DISAGG_REQUESTS", "48"))
    out_mean = float(env("BENCH_DISAGG_OUT_MEAN", "12"))
    out_max = int(env("BENCH_DISAGG_OUT_MAX", "32"))
    rounds = int(env("BENCH_DISAGG_ROUNDS", "3"))
    transport_kind = env("BENCH_DISAGG_TRANSPORT", "device")
    model = dict(vocab_size=vocab, hidden=hidden, num_layers=layers_n,
                 num_heads=heads, num_kv_heads=kv_heads,
                 intermediate=inter)
    make_prompt = lg.prompt_maker(vocab, 4, tail_max, out_mean,
                                  out_max, dist="bimodal",
                                  prompt_dist="mixed",
                                  long_frac=long_frac,
                                  long_tokens=long_tokens)
    kw = dict(num_slots=slots, max_seq_len=max_seq,
              max_new_tokens=out_max, queue_cap=4 * n_req,
              deadline_ms=600000.0, page_tokens=page_tokens, prefill_chunk=chunk,
              prefix_reuse=False)

    def build(role):
        # identical weights across every engine: the op-seed counter
        # resets so each startup replays the same init sequence
        reset_op_seed()
        eng = GenerationEngine(model, role=role, **kw)
        eng.warmup()
        return eng

    def drive(submit_target, n):
        return lg.run_closed_loop_generate(submit_target, make_prompt,
                                           n, concurrency=2 * slots)

    def colocated_arm():
        a, b = build("both"), build("both")
        try:
            reps_pair = []
            for _ in range(rounds):
                box = {}

                def run_half(key, eng):
                    box[key] = drive(eng, n_req // 2)

                ta = threading.Thread(target=run_half, args=("a", a))
                tb = threading.Thread(target=run_half, args=("b", b))
                t0 = time.perf_counter()
                ta.start(), tb.start()
                ta.join(), tb.join()
                wall = time.perf_counter() - t0
                toks = (box["a"]["generated_tokens"]
                        + box["b"]["generated_tokens"])
                reps_pair.append({"tokens_per_sec":
                                  round(toks / wall, 2)})
            p99s = [e.stats()["decode_step_ms"].get("p99")
                    for e in (a, b)]
            p99s = [p for p in p99s if p is not None]
            extras = {"p99_step_ms": max(p99s) if p99s else None,
                      "prefill_ms_mean":
                      np.mean([e.stats()["prefill_ms"].get("mean") or 0
                               for e in (a, b)])}
        finally:
            a.close(), b.close()
        return reps_pair, extras

    def disagg_arm():
        pre, dec = build("prefill"), build("decode")
        transport = HostBytesTransport() \
            if transport_kind == "bytes" else DeviceTransport()
        pair = DisaggPair(pre, dec, transport=transport)
        try:
            reps_pair = [drive(pair, n_req) for _ in range(rounds)]
            st = pair.stats()
            extras = {
                "p99_step_ms":
                    st["decode"]["decode_step_ms"].get("p99"),
                "prefill_ms_mean":
                    st["prefill"]["prefill_ms"].get("mean"),
                "handoffs": st["handoffs"],
                "handoff_ms_p50": st["handoff_ms_p50"],
                "transport": st["transport"],
                "transport_bytes": st["transport_bytes"],
                "segments_exported":
                    st["prefill"]["counters"]["segments_exported"],
                "segments_adopted":
                    st["decode"]["counters"]["segments_adopted"],
            }
        finally:
            pair.close()
        return reps_pair, extras

    import jax

    device = jax.devices()[0]
    coloc_reps, coloc_x = colocated_arm()
    dis_reps, dis_x = disagg_arm()
    rates = [r["tokens_per_sec"] for r in dis_reps]
    coloc_rates = [r["tokens_per_sec"] for r in coloc_reps]
    tps = float(np.median(rates))
    tps_coloc = float(np.median(coloc_rates))
    p99_d, p99_c = dis_x["p99_step_ms"], coloc_x["p99_step_ms"]
    ratio = round(p99_d / p99_c, 3) \
        if p99_d is not None and p99_c else None
    out = {
        "metric": "llama_disagg_tokens_per_sec",
        "value": round(tps, 2),
        "unit": "tokens/sec",
        "device_kind": getattr(device, "device_kind", str(device)),
        "stats": {
            "rounds": rounds,
            "median": round(tps, 2),
            "p10": round(float(np.percentile(rates, 10)), 2),
            "p90": round(float(np.percentile(rates, 90)), 2),
            "min": round(min(rates), 2),
            "max": round(max(rates), 2),
        },
        "colocated_tokens_per_sec": round(tps_coloc, 2),
        "disagg_vs_colocated_tokens": round(
            tps / max(tps_coloc, 1e-9), 3),
        # the gated headline: decode-step p99, disagg / colocated
        # (< 1.0 = prefill bursts no longer stall the decode grid)
        "disagg_vs_colocated_p99": ratio,
        "p99_step_ms": p99_d,
        "colocated_p99_step_ms": p99_c,
        "prefill_ms_mean": dis_x["prefill_ms_mean"],
        "colocated_prefill_ms_mean": coloc_x["prefill_ms_mean"],
        "handoffs": dis_x["handoffs"],
        "handoff_ms_p50": dis_x["handoff_ms_p50"],
        "transport": dis_x["transport"],
        "transport_bytes": dis_x["transport_bytes"],
        "segments_exported": dis_x["segments_exported"],
        "segments_adopted": dis_x["segments_adopted"],
        "closed": dis_reps[rates.index(
            sorted(rates)[len(rates) // 2])],
        "config": {"vocab": vocab, "hidden": hidden,
                   "layers": layers_n, "heads": heads,
                   "kv_heads": kv_heads, "inter": inter,
                   "slots": slots, "max_seq": max_seq,
                   "page_tokens": page_tokens, "chunk": chunk,
                   "long_tokens": long_tokens,
                   "long_frac": long_frac, "tail_max": tail_max,
                   "requests": n_req, "out_mean": out_mean,
                   "out_max": out_max, "rounds": rounds},
    }
    cores = os.cpu_count() or 1
    if cores < 4:
        out["anomaly"] = (
            f"host has {cores} cores for 2 engines x (scheduler + "
            f"dispatch) threads per arm; the disagg/colocated p99 "
            f"split is core-bound, not workload-bound")
    return out


# ---------------------------------------------------------------------------
# Chaos leg: availability under injected crash/hang/slow/poison faults
# ---------------------------------------------------------------------------

def run_chaos():
    """Fleet fault-containment leg (`legs.chaos`): tools/chaos.py's
    crash + hang + slow + poison scenarios against a live replica
    fleet under open-loop load through the router.  The headline
    ``value`` is non-poisoned availability % (injected damage
    included); the leg also publishes p99-under-fault and the
    injected-vs-collateral failure split.  `tools/perf_gate.py`
    HARD-fails any capture with collateral (non-injected) failures or
    poison leaks — no anomaly flag or device mismatch shields a
    containment break — and gates availability against the committed
    floor.  Sized by BENCH_CHAOS_{REPLICAS,QPS,DURATION_S,SCENARIOS}.
    On hosts with fewer cores than replicas+router the recoveries are
    core-bound and the leg flags `anomaly` (the containment rules
    still gate)."""
    import importlib.util

    import jax

    _refuse_on_tpu_host("chaos")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "chaos.py")
    spec = importlib.util.spec_from_file_location("chaos_bench", path)
    chaos = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chaos)

    env = os.environ.get
    replicas = int(env("BENCH_CHAOS_REPLICAS", "3"))
    qps = float(env("BENCH_CHAOS_QPS", "40"))
    duration_s = float(env("BENCH_CHAOS_DURATION_S", "6"))
    scenarios = tuple(s for s in env("BENCH_CHAOS_SCENARIOS",
                                     "baseline,crash,hang,slow,"
                                     "poison,disagg_crash,"
                                     "embedding_shard_crash,hot_swap"
                                     ).split(",")
                      if s)
    report = chaos.run_chaos(replicas=replicas, qps=qps,
                             duration_s=duration_s,
                             scenarios=scenarios,
                             availability_pct=99.0,
                             log=lambda *a: None)
    totals = report["totals"]
    out = {
        "metric": "chaos_availability_pct",
        "value": report["availability_pct"],
        "unit": "%",
        "device_kind": getattr(jax.devices()[0], "device_kind",
                               str(jax.devices()[0])),
        "availability_floor": report["availability_floor"],
        "collateral_failures": totals["collateral_failures"],
        "injected_failures": totals["injected_failures"],
        "poison_leaks": totals["poison_leaks"],
        "alert_errors": totals.get("alert_errors"),
        "leaked_pages": totals.get("leaked_pages"),
        "leaked_rows": totals.get("leaked_rows"),
        "p99_under_fault_ms": report["p99_under_fault_ms"],
        "requests": totals["requests"],
        "ok_requests": totals["ok"],
        "shed": totals["shed"],
        "scenarios": {
            name: {k: v for k, v in rep.items() if k != "notes"}
            for name, rep in report["scenarios"].items()},
        "harness_ok": report["ok"],
        "errors": report["errors"],
        "config": report["config"],
    }
    cores = os.cpu_count() or 1
    if cores < replicas + 1:
        out["anomaly"] = (
            f"host has {cores} cores for {replicas} replica processes "
            f"+ the router; recovery timing is core-bound (the "
            f"collateral/leak containment rules still gate)")
    return out


# ---------------------------------------------------------------------------
# Rollout leg: hot-swap discipline + canary auto-revert/promotion
# ---------------------------------------------------------------------------

def run_rollout():
    """Safe-rollout leg (`legs.rollout`): two live demonstrations,
    both hard-gated by `tools/perf_gate.py`.

    First the chaos harness's ``hot_swap`` scenario IS the
    measurement — a rolling ``FleetSupervisor.hot_swap`` under mixed
    open-loop ``/predict`` + ``/generate`` load, then a second rollout
    with one replica SIGKILLed mid-commit: zero non-shed failures
    outside the kill window (``rollout.failed``), zero torn-version
    responses (``rollout.torn_responses``), restart-fallback
    convergence, and bit-exact post-swap outputs.

    Then a canary double-feature through a live router: a CLEAN
    checkpoint must soak and promote with zero reverts
    (``canary.false_reverts`` — a burn-rate judge that convicts good
    weights makes rollouts un-shippable), and a NaN-poisoned
    checkpoint (every request 500s under
    ``FLAGS_serving_check_outputs``) must auto-revert on burn
    evidence inside the soak window (``canary.revert_latency_s``
    against ``revert_latency_bound_s``).  Sized by
    BENCH_ROLLOUT_{QPS,DURATION_S,SOAK_S,FEAT}."""
    import importlib.util
    import tempfile
    import threading

    import jax

    _refuse_on_tpu_host("rollout")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "chaos.py")
    spec = importlib.util.spec_from_file_location("chaos_rollout", path)
    chaos = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chaos)
    lg = _load_serving_loadgen()

    env = os.environ.get
    qps = float(env("BENCH_ROLLOUT_QPS", "25"))
    duration_s = float(env("BENCH_ROLLOUT_DURATION_S", "5"))
    soak_s = float(env("BENCH_ROLLOUT_SOAK_S", "6"))
    feat = int(env("BENCH_ROLLOUT_FEAT", "16"))

    # hot-swap discipline under fire (own fleet, own verdicts)
    cfg = {"qps": qps, "duration_s": duration_s, "feat": feat,
           "timeout_s": 15.0, "liveness_timeout_ms": 1500.0}
    rep = chaos._scenario_hot_swap(cfg, log=lambda *a: None)
    rep.pop("_records", None)
    notes = rep.get("notes") or {}
    swap_clean = notes.get("swap_clean") or {}
    swap_killed = notes.get("swap_killed") or {}
    rollout = {
        # collateral = failures OUTSIDE the SIGKILL window: the
        # zero-non-shed contract a clean swap must hold
        "failed": rep.get("collateral_failures"),
        "torn_responses": rep.get("torn_responses"),
        "injected_failures": rep.get("injected_failures"),
        "shed": rep.get("shed"),
        "requests": rep.get("requests"),
        "swaps": 2,
        "converged": bool(swap_clean.get("converged"))
        and bool(swap_killed.get("converged")),
        "clean_swap_s": swap_clean.get("duration_s"),
        "killed_swap_s": swap_killed.get("duration_s"),
        "fallbacks": swap_killed.get("fallbacks"),
        "bit_exact": notes.get("bit_exact"),
    }

    # canary: clean promote + poisoned auto-revert through a router
    from paddle_tpu.serving import (FleetSupervisor, Router,
                                    RouterServer)
    from paddle_tpu.serving.replica import build_synthetic_checkpoint

    workdir = tempfile.mkdtemp(prefix="bench-rollout-")
    dims = dict(feat=feat, hidden=16, depth=1, classes=8)
    ck_good = os.path.join(workdir, "ck_good")
    ck_bad = os.path.join(workdir, "ck_bad")
    build_synthetic_checkpoint(ck_good, seed=21, **dims)
    build_synthetic_checkpoint(ck_bad, seed=22, poison_nan=True,
                               **dims)
    argv = ["--feat", str(feat), "--hidden", "16", "--depth", "1",
            "--max-batch", "8", "--max-delay-ms", "2.0",
            "--queue-cap", "512"]
    sup = FleetSupervisor(
        replicas=3, replica_argv=argv,
        env={"FLAGS_serving_check_outputs": "1"},
        max_restarts=4, backoff_ms=100.0,
        workdir=os.path.join(workdir, "fleet"))
    server = None
    stop = threading.Event()
    canary = {}
    try:
        urls = sup.wait_ready(timeout_s=300)
        router = Router(urls, poll_interval_ms=100.0, stale_ms=2000.0,
                        eject_after=3)
        server = RouterServer(router).start()
        router.start()  # the poll loop drives the canary verdict
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            router.poll_once()
            if router.healthz()[1]["routable"] == len(urls):
                break
            time.sleep(0.2)
        make_feed = lg.feed_maker({"x": (feat,)}, rows=1)

        def pump():
            # steady traffic so the burn-rate judge has evidence;
            # short windows re-check `stop` between them
            while not stop.is_set():
                lg.run_open_loop_http(server.url, make_feed, qps=qps,
                                      duration_s=1.0, timeout_s=10.0,
                                      collectors=4)

        pump_t = threading.Thread(target=pump, daemon=True)
        pump_t.start()

        def soak(ck):
            router.canary(ck, fraction=0.34, soak_s=soak_s)
            deadline = time.monotonic() + 6.0 * soak_s + 60.0
            while time.monotonic() < deadline:
                st = router.canary_status()
                last = st.get("last") or {}
                if not st["active"] and last.get("state") in (
                        "reverted", "promoted"):
                    return last
                time.sleep(0.2)
            return {"state": "verdict_timeout"}

        clean = soak(ck_good)
        bad = soak(ck_bad)
        counters = router.canary_status()["counters"]
        reverted = bad.get("state") == "reverted"
        canary = {
            "false_reverts": (
                1 if clean.get("state") == "reverted"
                else 0 if clean.get("state") == "promoted"
                else None),  # vacuous soak: perf_gate fails it
            "promotions": counters.get("canary_promotions"),
            "reverts": 1 if reverted else 0,
            # detection + revert POSTs, start-of-soak to reverted:
            # the judge must beat the promotion clock
            "revert_latency_s": round(
                bad.get("soak_elapsed_s", 0.0)
                + bad.get("revert_latency_s", 0.0), 3)
            if reverted else None,
            "revert_latency_bound_s": soak_s,
            "revert_reason": bad.get("reason"),
            "clean_state": clean.get("state"),
            "bad_state": bad.get("state"),
        }
        if not reverted:
            canary["error"] = (f"poisoned canary did not revert: "
                               f"{bad}")
    finally:
        stop.set()
        if server is not None:
            server.close()
        sup.close()

    errors = {}
    if "error" in rep:
        errors["hot_swap"] = rep["error"]
    if "error" in canary:
        errors["canary"] = canary["error"]
    out = {
        "metric": "rollout_availability_pct",
        "value": rep.get("availability_pct"),
        "unit": "%",
        "device_kind": getattr(jax.devices()[0], "device_kind",
                               str(jax.devices()[0])),
        "stats": {"rounds": 1, "median": rep.get("availability_pct")},
        "availability_floor": 99.0,
        # top-level chaos-rule keys: the scenario's collateral /
        # poison verdicts ride the same perf_gate hard rules as the
        # chaos leg
        "collateral_failures": rep.get("collateral_failures"),
        "poison_leaks": rep.get("poison_leaks"),
        "p99_under_fault_ms": rep.get("p99_ms"),
        "rollout": rollout,
        "canary": canary,
        "harness_ok": not errors,
        "errors": errors,
        "config": {"qps": qps, "duration_s": duration_s,
                   "soak_s": soak_s, "feat": feat},
    }
    cores = os.cpu_count() or 1
    if cores < 4:
        out["anomaly"] = (
            f"host has {cores} cores for 3 replica processes + the "
            f"router; swap/soak timing is core-bound (the torn-"
            f"version / false-revert rules still gate)")
    return out


def main():
    import jax

    from paddle_tpu.compile_cache import ensure_compile_cache

    seq = int(os.environ.get("BENCH_SEQ", "128"))
    # batch sweeps on v5e (round-4 after the dot_general-mul +
    # remat-dropout fixes; round-5 re-sweep):
    # seq-128: 160 -> 934, 192 -> 1212, 224 -> 1128, 256 -> 1167
    #   (round-5: 160/192/208 all within noise at ~1205-1211 — flat
    #   plateau, 192 kept)
    # seq-512 (packed flash): 32 -> 196, 64 -> 289, 96 -> 284,
    #   128 -> 201; round-5 same-session: 80 -> 282 vs 64 -> 276.7 (x2)
    default_batch = 192 if seq < 512 else 80
    batch = int(os.environ.get("BENCH_BATCH", str(default_batch)))
    dropout = float(os.environ.get("BENCH_DROPOUT", "0.1"))
    want_legs = (os.environ.get("BENCH_LEGS", "1") == "1" and seq == 128
                 and "BENCH_HIDDEN" not in os.environ)

    def wanted(switch):
        return want_legs and os.environ.get(switch, "1") == "1"

    # A leg that raises fails the run.  The fleet legs come first: their
    # replica processes need the chips, which this process takes the
    # moment it touches JAX below (each is skipped with BENCH_<LEG>=0).
    legs = {}
    # router leg: fleet front-end scaling + rolling-restart availability
    if wanted("BENCH_ROUTER"):
        legs["router"] = run_router()
    # chaos leg: availability under injected crash/hang/slow/poison
    # faults against a live fleet
    if wanted("BENCH_CHAOS"):
        legs["chaos"] = run_chaos()
    # rollout leg: hot-swap discipline + canary auto-revert / promotion
    # against live fleets
    if wanted("BENCH_ROLLOUT"):
        legs["rollout"] = run_rollout()

    ensure_compile_cache()
    device = jax.devices()[0]
    out = {"metric": chip_name(
        "bert_base_mlm_train_samples_per_sec_per_chip", device)}
    out.update(run_config(seq, batch, dropout=dropout))

    if want_legs:
        # long-sequence leg: seq-512, pallas flash attention (VERDICT r3
        # #1 — the marquee long-context capability must carry a
        # published number).  Attention pinned to the packed flash
        # kernels: a BENCH_ATTN override meant for the seq-128 A/B would
        # otherwise leak in (unfused can't hold batch 64 at seq-512)
        legs["seq512"] = run_config(512, 80, attn=True, dropout=dropout)
    # second tracked BASELINE config: ResNet-50 ImageNet training
    # (BENCH_RESNET_BATCH sizes it)
    if wanted("BENCH_RESNET"):
        legs["resnet50"] = run_resnet50()
    # serving leg: dynamic-batching engine qps vs serial batch-1
    if wanted("BENCH_SERVING"):
        legs["serving"] = run_serving()
    # recommender-serving leg: ep-sharded embedding lookups + hot-row
    # cache under zipfian small feeds
    if wanted("BENCH_RECSYS"):
        legs["wide_deep_recsys"] = run_recsys()
    # sharded-serving leg: dp replica groups + mp weight sharding
    if wanted("BENCH_SHARDED"):
        legs["sharded_serving"] = run_sharded_serving()
    # decode leg: KV-cached continuous batching — the tracked Llama
    # BASELINE config
    if wanted("BENCH_DECODE"):
        legs["llama_decode"] = run_decode()
    # speculative-decode leg: n-gram self-drafts + one-chunk
    # verification vs plain decode
    if wanted("BENCH_SPEC"):
        legs["llama_spec_decode"] = run_spec_decode()
    # disaggregated prefill/decode A/B on the mixed workload
    if wanted("BENCH_DISAGG"):
        legs["llama_disagg"] = run_disagg()
    if legs:
        out["legs"] = legs

    print(json.dumps(out))


if __name__ == "__main__":
    main()
