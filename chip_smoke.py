#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process that owns the chip(s) drives the main path once through the
entry points a user calls, at the full width of the models below, with
random seeded weights:

* **train** — BERT-base (12 layers, hidden 768, 12 heads, FFN 3072, vocab
  30522) at sequence 512, the recipe ``paddle_tpu.models.bert`` builds
  (bf16 AMP white list, Adam + global-norm clip, masked-gather MLM head),
  built under
  ``program_guard``, initialised with ``Executor(TPUPlace()).run(startup)``
  and stepped through ``Executor.run`` on
  ``CompiledProgram(main).with_data_parallel(...)`` over every local chip.
  First the packed Pallas kernels run forward + backward at the phase's own
  shape against the einsum formulation; then ~10 steps on one fixed batch
  at a constant learning rate must give finite, falling losses, and the
  compiled step itself must contain the Mosaic custom call, on one chip
  and (the kernels per ``dp`` shard) on several.
* **serve** — ``serve(ServingEngine(...))`` with a paged
  ``GenerationEngine`` attached, answering HTTP ``/predict``, ``/generate``
  (prompts in different prefill buckets, one streamed), ``/healthz`` and
  ``/metrics``, then ``close()``.  The generation model is the repo's one
  decoder block at real widths — a smoke shape, NOT a supported
  configuration: hidden 2048, 16 heads of 128, SwiGLU FFN 8192, vocab
  50304, 16 layers, 8 slots x 2048 positions, float32.

* **slot state** (PR 34) — the paged-decode kernel at heads of 64 over a
  pool packed two heads a row, one convolution-state round trip and
  (PR 41) the two gated delta-rule kernels at 30 heads of 96 x 192
  against their XLA formulations with one delta-state round trip
  (prefill, three decode steps, the slot reused) through a two-slot engine.

* **an expert share** (PR 43) — the same two kernels with a log decay a
  key channel at 64 heads of 128 x 128, and the held experts' part of a
  layer (20 of a 320-wide router's experts) against a plain loop.

* **chunks over two page kinds** (PR 51) — the Pallas chunk attention
  kernel at 128 query over 8 KV heads of 128, a chunk of 512 rows at
  ``base`` 4096 with and without a window of 4096, against the einsum
  formulation; and a two-layer window + full decoder under the parallel
  LayerNorm block that prefills a 1,300-token prompt in chunks of 256:
  ``attention_lowered_chunk_pallas`` grows with every chunk program built,
  ``attention_lowered_chunk_reference`` stays 0, and the logits are the
  single-shot prefill's.

* **chunks over latent pages** (PR 56) — the chunk kernel over latent
  rows (``mla_chunk_attention``) at 128 heads of nope 128 + rope 64 over a
  latent of 512, 512 rows at ``base`` 4096, against "highest" einsums of
  the expanded arithmetic; and a two-layer all-latent decoder with a
  group-limited router (one whole group held, YaRN at ``mscale_all_dim``
  0.707) that prefills a 1,300-token prompt in chunks of 256 over latent
  pages: ``attention_lowered_latent_chunk`` +4, ``_latent_chunk_reference``
  +0, and the logits are the single-shot prefill's.

* **state-space layers** (PR 59) — the two SSD kernels
  (``ops/pallas/ssd.py``) at 64 heads of 64 over 128 state rows against
  the XLA formulations of ``ops/ssd_ops.py`` (the whole scan over a padded
  prompt, the step over 32 slots of which some are dead), and one
  state-space round trip through a two-slot engine under the family's four
  multipliers: ``ssd_lowered_pallas`` grows, ``ssd_lowered_reference``
  stays 0.

* **dense products that stop at the prompt's end** (PR 64) — a rung of
  2048 rows through a two-slot engine of two layers (grouped-query
  attention and a SwiGLU at hidden 2048 / 8192) whose prefill program
  multiplies only the 256-row segments that hold a prompt token (op
  ``mul_valid_rows``), at prompts of 600 and 2048 tokens, each against an
  engine on the same weights whose program has the plain products: the
  first token's logits and a decode step's (the pages written) within the
  tolerance, and the rows counted (``prefill_rows_run`` 768 + 2048,
  ``prefill_rows_skipped`` 1280).  Then (PR 65) a rung of 1024 rows at
  Mistral's layer shapes (hidden 4096 / 14336: under 2048 rows the rule
  takes the fused SwiGLU and the products of a weight of 4096 rows or
  more) at prompts of 593 and 1024 tokens, the same way (768 + 1024 run,
  256 skipped).

* **the program store** (PR 60) — first of all, before this process
  touches the chip: two child processes, one after the other, each build
  the same two-layer decoder (8 heads of 128 over 4 KV heads, two slots),
  warm its prefill rung and its decode program (both hold Pallas kernels)
  and answer one prompt.  The second must load every module the first
  stored (``program_store_hits`` > 0, ``_misses`` 0), compile nothing anew
  (``compile_cache_misses`` 0), book the lowering counters the first
  booked and return its logits to the bit.

Any failed check raises: the exit code is non-zero and no result line is
printed.  Without a TPU backend the script refuses to run (exit 2).  The
last line of stdout is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Nothing here is a measurement: the times printed are set-up and phase
wall times (compilation included), there to show the compile cache working
and the 1200 s limit being met — not rates.
"""
from __future__ import annotations

import gc
import hashlib
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

TRAIN = dict(seq=512, hidden=768, layers=12, heads=12, ffn=3072,
             vocab=30522, global_batch=16, steps=10, lr=1e-4)
SERVE = dict(hidden=2048, heads=16, ffn=8192, vocab=50304, layers=16,
             slots=8, max_seq=2048, page_tokens=16,
             # three prefill programs instead of the nine-rung default
             # ladder: a sub-128 bucket, a mid one, and the cache width
             prefill_buckets=(64, 512, 2048),
             prompt_lens=(40, 300, 1500), new_tokens=8,
             mlp=dict(feat=256, hidden=2048, depth=4, classes=16))

# One tolerance, as a share of the reference's largest magnitude.  bf16
# carries 8 mantissa bits, and the Pallas kernels feed the MXU at default
# precision, which rounds their f32 operands (scaled q, probabilities, ds)
# to bf16; under AMP the kernel's inputs and outputs are bf16 as well.
# Each rounding is <= 2^-8 relative; four of them bound forward and
# backward with room for the accumulation order.  Measured on a v5e
# (PR 21): kernels 0.0056, generation logits 0.0024 (the prefill kernel
# dominates), the XLA-only /predict MLP 1.3e-7.
TOL = 4 * 2.0 ** -8


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def say_startup_account():
    """Where this process's start-up went so far, by part and by program
    (``telemetry.startup_account()``), beside the cache's counters."""
    from paddle_tpu import telemetry
    from paddle_tpu.monitor import stat_get

    account = telemetry.startup_account()
    programs = account.pop("programs")
    asked = sum("cache_hit" in s.attrs
                for s in telemetry.get_spans(kept=True))
    say("start-up account (self seconds x spans): " + ", ".join(
        f"{part} {p['s']:.2f} x {p['n']}" for part, p in sorted(
            account.items(), key=lambda kv: -kv[1]["s"]))
        + f"; compile_cache_hits {stat_get('compile_cache_hits')}, "
        f"compile_cache_misses {stat_get('compile_cache_misses')}, "
        f"compile/backend spans that asked the cache {asked}; "
        f"program_store_hits {stat_get('program_store_hits')}, _misses "
        f"{stat_get('program_store_misses')}, _refused "
        f"{stat_get('program_store_refused')}")
    for *program, trace_s, lower_s, backend_s, hit in programs[:5]:
        say(f"  {telemetry.program_label(*program)}: trace {trace_s:.2f} "
            f"s, lower {lower_s:.2f} s, backend {backend_s:.2f} s, "
            f"cache_hit {hit}")


def attention_paths():
    from paddle_tpu.monitor import stat_get

    return {p: stat_get(f"attention_lowered_{p}")
            for p in ("pallas", "pallas_sharded", "blockwise", "ring", "xla",
                      "paged_decode", "paged_decode_reference")}


def paths_since(before):
    return {k: v - before[k] for k, v in attention_paths().items()
            if v - before[k]}


def pool_writes():
    """How the ``T > 1`` ``kv_pool_write`` ops lowered: whole pages, or a
    [Hkv, D] window a row (which re-lays the whole pool on a TPU)."""
    from paddle_tpu.monitor import stat_get

    return {k: stat_get(f"kv_pool_write_{k}") for k in ("pages", "rows")}


def attention_grads():
    """How the attention grad ops lowered: off the forward's saved output
    and softmax statistic, or as jax.vjp of the forward lowering."""
    from paddle_tpu.monitor import stat_get

    return {p: stat_get(f"attention_grad_{p}")
            for p in ("saved", "relowered")}


def dropout_draws():
    from paddle_tpu.monitor import stat_get

    return {p: stat_get(f"dropout_lowered_{p}")
            for p in ("hw_bits", "threefry")}


def overlap_builds():
    from paddle_tpu.monitor import stat_get

    return {p: stat_get(f"sharded_step_overlap_{p}") for p in ("on", "off")}


def check_dropout_shards(devices, seq, hidden):
    """One dropout site over ones, through ``build_sharded_step`` on a
    ``dp`` mesh of ``devices``: the shards' keep masks must differ (the
    draw runs per shard with the shard's index in its key), a step must
    repeat and the next must not."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.parallel import build_sharded_step, dp_mesh

    n = len(devices)
    shape = [2 * n, seq, hidden]
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        x = pt.data(name="x", shape=shape, append_batch_size=False)
        out = layers.dropout(x, dropout_prob=0.5,
                             dropout_implementation="upscale_in_train")
    fn, _, _, _ = build_sharded_step(main_p, ["x"], [out.name],
                                     dp_mesh(n, devices=devices))
    ones = np.ones(shape, "float32")

    def keep(step):
        (ov,), _, _ = fn((ones,), (), (), np.int32(step))
        return np.asarray(ov) != 0

    first, again, later = keep(1), keep(1), keep(2)
    check(abs(first.mean() - 0.5) < 0.01,
          f"dropout keep rate {first.mean():.4f} at p = 0.5")
    check((first == again).all() and 0.45 < (first == later).mean() < 0.55,
          "a dropout step does not repeat, or two steps share a mask")
    shards = np.split(first, n)
    agree = [float((shards[i] == shards[j]).mean())
             for i in range(n) for j in range(i + 1, n)]
    check(all(0.45 < a < 0.55 for a in agree),
          f"dp shards draw the same dropout mask: pairwise agreement {agree}")
    return agree


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def check_packed_kernels(batch, seq, hidden, heads, interpret=False):
    """flash_attention_packed / _packed_bias, forward + backward, against
    the einsum formulation at "highest" matmul precision."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention_packed,
        flash_attention_packed_bias)

    kq, kg = jax.random.split(jax.random.key(0))
    qkv = jax.random.normal(kq, (batch, seq, 3 * hidden),
                            jnp.float32).astype(jnp.bfloat16)
    g = jax.random.normal(kg, (batch, seq, hidden),
                          jnp.float32).astype(jnp.bfloat16)
    # the last three positions are padding, as an attention mask makes them
    bias = jnp.where(jnp.arange(seq)[None, :] < seq - 3, 0.0, -1e4) \
        * jnp.ones((batch, 1), jnp.float32)

    def reference(qkv, bias):
        x = qkv.astype(jnp.float32).reshape(
            batch, seq, 3, heads, hidden // heads)
        q, k, v = (jnp.moveaxis(x[:, :, i], 1, 2) for i in range(3))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hidden // heads)
        if bias is not None:
            s = s + bias[:, None, None, :]
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
        return jnp.moveaxis(o, 1, 2).reshape(batch, seq, hidden)

    worst = 0.0
    for name, b in (("flash_attention_packed", None),
                    ("flash_attention_packed_bias", bias)):
        def kernel(qkv, b=b):
            tail = (heads, False, None, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                    interpret)
            if b is None:
                return flash_attention_packed(qkv, *tail)
            return flash_attention_packed_bias(qkv, b, *tail)

        def fwd_bwd(f):
            def loss(qkv):
                out = f(qkv)
                return (out.astype(jnp.float32)
                        * g.astype(jnp.float32)).sum(), out
            (_, out), grad = jax.jit(
                jax.value_and_grad(loss, has_aux=True))(qkv)
            return out, grad

        out_k, grad_k = fwd_bwd(kernel)
        with jax.default_matmul_precision("highest"):
            out_r, grad_r = fwd_bwd(lambda x, b=b: reference(x, b))
        for what, got, ref in (("forward", out_k, out_r),
                               ("backward", grad_k, grad_r)):
            got = np.asarray(got.astype(jnp.float32))
            ref = np.asarray(ref.astype(jnp.float32))
            check(np.isfinite(got).all(), f"{name} {what} not finite")
            rel = float(np.abs(got - ref).max() / np.abs(ref).max())
            worst = max(worst, rel)
            check(rel <= TOL,
                  f"{name} {what} off the einsum reference by {rel:.4g} "
                  f"of its range (tolerance {TOL:.4g})")
    return worst


def check_packed_op(batch, seq, hidden, heads):
    """``flash_attention_qkv`` and its grad op through a program (the op's
    forward keeps the statistic, the grad op runs the backward kernels off
    it): ``Out`` and ``QKV@GRAD`` against the blockwise formulation the op
    lowers to off the chip, at "highest" matmul precision."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.ops.pallas.flash_attention import blockwise_attention

    rng = np.random.RandomState(2)
    feed = {"qkv": rng.randn(batch, seq, 3 * hidden).astype("float32"),
            "w": rng.randn(batch, seq, hidden).astype("float32"),
            "bias": np.where(np.arange(seq)[None, :] < seq - 3, 0.0, -1e4)
            * np.ones((batch, 1), "float32")}
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        x = layers.data("qkv", [batch, seq, 3 * hidden],
                        append_batch_size=False)
        x.stop_gradient = False
        bias = layers.data("bias", [batch, seq], append_batch_size=False)
        w = layers.data("w", [batch, seq, hidden], append_batch_size=False)
        out = layers.flash_attention_qkv(x, heads, bias=bias)
        pt.append_backward(
            layers.reduce_sum(layers.elementwise_mul(out, w)))
    got = pt.Executor(pt.TPUPlace()).run(
        main_p, feed=feed, fetch_list=[out.name, "qkv@GRAD"])

    def reference(qkv):
        t = qkv.reshape(batch, seq, 3, heads, hidden // heads)
        q, k, v = (jnp.moveaxis(t[:, :, i], 1, 2) for i in range(3))
        o, _ = blockwise_attention(q, k, v, bias=jnp.asarray(feed["bias"]))
        o = jnp.moveaxis(o, 1, 2).reshape(batch, seq, hidden)
        return (o * feed["w"]).sum(), o

    with jax.default_matmul_precision("highest"):
        (_, out_r), grad_r = jax.jit(
            jax.value_and_grad(reference, has_aux=True))(feed["qkv"])
    worst = 0.0
    for what, g, ref in (("Out", got[0], out_r), ("QKV@GRAD", got[1],
                                                  grad_r)):
        g, ref = np.asarray(g, "float32"), np.asarray(ref, "float32")
        check(np.isfinite(g).all(), f"flash_attention_qkv {what} not finite")
        rel = float(np.abs(g - ref).max() / np.abs(ref).max())
        worst = max(worst, rel)
        check(rel <= TOL,
              f"flash_attention_qkv {what} off the blockwise formulation "
              f"by {rel:.4g} of its range (tolerance {TOL:.4g})")
    return worst


def train_phase(cfg=TRAIN, on_chip=True):
    import jax

    import paddle_tpu as pt
    from paddle_tpu.models.bert import build_bert_train_programs

    devices = jax.devices()
    n = len(devices)
    B, S = cfg["global_batch"], cfg["seq"]
    check(B % n == 0, f"global batch {B} does not split over {n} devices")
    draws0 = dropout_draws()
    overlap0 = overlap_builds()

    t_phase = t0 = time.perf_counter()
    worst = check_packed_kernels(B // n, S, cfg["hidden"], cfg["heads"],
                                 interpret=not on_chip)
    say(f"train: packed kernels fwd+bwd at [{B // n}, {S}, "
        f"3x{cfg['hidden']}] bf16 within {worst:.4g} of the einsum "
        f"reference's range (tolerance {TOL:.4g}) "
        f"[{time.perf_counter() - t0:.1f} s]")
    t0 = time.perf_counter()
    grads0 = attention_grads()
    worst = check_packed_op(B // n, S, cfg["hidden"], cfg["heads"])
    op_grads = {k: v - grads0[k] for k, v in attention_grads().items()}
    check(op_grads == ({"saved": 1, "relowered": 0} if on_chip
                       else {"saved": 0, "relowered": 1}),
          f"the flash_attention_qkv grad op lowered as {op_grads}")
    say(f"train: flash_attention_qkv op, Out and QKV@GRAD through a "
        f"program, within {worst:.4g} of the blockwise formulation's range; "
        f"grad op lowered as {op_grads} "
        f"[{time.perf_counter() - t0:.1f} s]")
    paths0 = attention_paths()
    grads0 = attention_grads()

    max_pred = max(1, int(round(0.15 * S)))
    main_p, startup, feed_names, loss, _ = build_bert_train_programs(
        dict(batch_size=B, seq_len=S, vocab_size=cfg["vocab"],
             hidden=cfg["hidden"], num_layers=cfg["layers"],
             num_heads=cfg["heads"], intermediate=cfg["ffn"],
             max_predictions=max_pred, use_flash=True, dropout=0.1),
        learning_rate=cfg["lr"])
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    comp = pt.CompiledProgram(main_p).with_data_parallel(
        loss_name=loss.name)

    rng = np.random.RandomState(0)
    batch = {
        "input_ids": rng.randint(0, cfg["vocab"], (B, S)).astype("int64"),
        "token_type_ids": np.zeros((B, S), "int64"),
        "attn_mask": np.ones((B, S), "float32"),
        "mlm_positions": np.sort(np.stack(
            [rng.choice(S, max_pred, replace=False) for _ in range(B)]),
            axis=1).astype("int64"),
        "mlm_labels": rng.randint(0, cfg["vocab"],
                                  (B, max_pred)).astype("int64"),
        "mlm_weights": np.ones((B, max_pred), "float32"),
    }
    losses, t_first = [], None
    t0 = time.perf_counter()
    for _ in range(cfg["steps"]):
        out, = exe.run(comp, feed=batch, fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(out).reshape(-1)[0]))
        if t_first is None:
            t_first = time.perf_counter() - t0
            setup_s = time.perf_counter() - t_phase
    say(f"train: first step (compile included) {t_first:.1f} s, "
        f"{cfg['steps']} steps {time.perf_counter() - t0:.1f} s")
    say("train: losses " + " ".join(f"{x:.4f}" for x in losses))
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")

    # what XLA compiled, not the flag that asked for it
    executable = comp.executable
    feed_sh = executable.input_shardings[0][0][0]
    shard = feed_sh.shard_shape((B, S))
    check(shard[0] * n == B,
          f"batch not split over dp: per-device shard {shard} of {(B, S)}")
    mosaic = len(re.findall(r'custom_call_target="tpu_custom_call"',
                            executable.as_text()))
    paths = paths_since(paths0)
    grads = {k: v - grads0[k] for k, v in attention_grads().items()}
    if on_chip:
        # on more than one device the same kernels run per dp shard,
        # through shard_map (ops/attention_ops.py kernel_partition)
        check(mosaic and paths.get("pallas")
              and not paths.get("blockwise"),
              f"the compiled training step holds no Mosaic custom call: "
              f"the Pallas kernels were not lowered (paths {paths})")
        check(paths.get("pallas_sharded", 0)
              == (paths["pallas"] if n > 1 else 0),
              f"on {n} device(s) the kernels took the wrong route "
              f"(paths {paths})")
        # forward, dkv, dq a layer: the grad op reads the forward's saved
        # output and statistic and does not launch the forward again
        check(mosaic == 3 * cfg["layers"]
              and grads == {"saved": cfg["layers"], "relowered": 0},
              f"the compiled training step holds {mosaic} Mosaic custom "
              f"calls, not 3 a layer ({3 * cfg['layers']}); grad ops "
              f"lowered as {grads}")
    say(f"train: {n} device(s), per-device batch shard {shard}, Mosaic "
        f"custom calls in the compiled step: {mosaic}, attention lowered "
        f"as {paths}, its grad ops as {grads}")

    # across chips the weight matrices' gradients are reduced one by one,
    # asynchronously, under the weight-gradient matmuls that follow them
    # (parallel/sharded.py overlap_compiler_options); one chip's step is
    # compiled without the options
    overlap = {k: v - overlap0[k] for k, v in overlap_builds().items()}
    if on_chip:
        from tools.collective_schedule import collectives

        hidden_under = [r for r in collectives(executable.as_text())
                        if r["op"] == "all-reduce" and r["pair"]]
        check(overlap == ({"on": 1, "off": 0} if n > 1
                          else {"on": 0, "off": 1})
              and (len(hidden_under) >= 3 * cfg["layers"]) == (n > 1),
              f"on {n} device(s) the step was built with the overlap "
              f"options {overlap} and holds {len(hidden_under)} "
              f"asynchronous all-reduces")
        say(f"train: step built with the collective overlap options "
            f"{overlap}; {len(hidden_under)} asynchronous all-reduces in "
            f"the compiled step")

    # the dropout sites' mask bits come from XLA's bit generator: a draw a
    # site, of the shard's shape (ops/nn_ops.py _mask_route)
    sites = 1 + 2 * cfg["layers"]
    draws = {k: v - draws0[k] for k, v in dropout_draws().items()}
    check(draws == {"hw_bits": sites, "threefry": 0},
          f"{sites} dropout sites lowered as {draws}")
    drawn = re.findall(r"= (u8\[[\d,]+\])\S* rng-bit-generator\(",
                       executable.as_text())
    if on_chip:
        want = f"u8[{B // n},{S},{cfg['hidden']}]"
        check(len(drawn) >= sites and set(drawn) == {want},
              f"the compiled step's rng-bit-generator operations are "
              f"{sorted(set(drawn))} x {len(drawn)}, not {want} a site")
    say(f"train: dropout lowered as {draws}, rng-bit-generator in the "
        f"compiled step: {len(drawn)} of {sorted(set(drawn))}")
    if n > 1:
        agree = check_dropout_shards(devices, S, cfg["hidden"])
        say(f"train: dropout masks of the {n} dp shards agree pairwise on "
            + " ".join(f"{a:.3f}" for a in agree) + " of their elements")

    mem = [d.memory_stats() for d in devices]
    if all(mem):
        in_use = [m["bytes_in_use"] for m in mem]
        say("train: bytes_in_use per device after the steps: "
            + " ".join(str(b) for b in in_use))
        params = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                     for v in (scope.find_var(k)
                               for k in scope.local_var_names())
                     if hasattr(v, "shape"))
        check(min(in_use) >= params // 2,
              f"state is not on every device: {in_use} against "
              f"{params} bytes of replicated state")
    return {"losses": losses, "paths": paths, "mosaic": mosaic,
            "grads": grads, "dropout": draws, "devices": n, "setup_s": setup_s}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _http(url, doc=None, timeout=600):
    data = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def _mlp_predictor(feat, hidden, depth, classes):
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.inference import Predictor

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    startup.random_seed = main.random_seed = 0
    with pt.program_guard(main, startup):
        h = layers.data("x", [feat])
        names = [f"smoke_fc{i}" for i in range(depth)] + ["smoke_head"]
        for name in names:
            last = name == names[-1]
            h = layers.fc(h, classes if last else hidden,
                          act=None if last else "relu",
                          param_attr=f"{name}.w", bias_attr=f"{name}.b")
    scope = pt.Scope()
    pt.Executor(pt.TPUPlace()).run(startup, scope=scope)

    def reference(x):
        h = x.astype("float64")
        for name in names:
            h = h @ np.asarray(scope.find_var(f"{name}.w"), "float64") \
                + np.asarray(scope.find_var(f"{name}.b"), "float64")
            if name != names[-1]:
                h = np.maximum(h, 0.0)
        return h

    return Predictor(main, ["x"], [h], scope=scope), reference


def _forward_logits(gen, model, token_ids, seq):
    """Uncached causal forward over the engine's own weights through the
    einsum attention at "highest" precision: [len(token_ids), V]."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.models.llama import build_llama_forward

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        _feeds, fetches = build_llama_forward(
            1, seq, name=gen.name, attn_impl="xla", **model)
    padded = np.zeros((1, seq), "int64")
    padded[0, :len(token_ids)] = token_ids
    with jax.default_matmul_precision("highest"):
        out, = pt.Executor(pt.TPUPlace()).run(
            main, feed={"input_ids": padded},
            fetch_list=[fetches["logits"]], scope=gen.scope)
    return np.asarray(out)[0, :len(token_ids)]


def serve_phase(cfg=SERVE, on_chip=True):
    from paddle_tpu import promtext
    from paddle_tpu.serving import GenerationEngine, ServingEngine, serve
    from paddle_tpu.serving.streams import stream_writer

    paths0, writes0 = attention_paths(), pool_writes()
    streams0 = stream_writer.stats()
    model = dict(vocab_size=cfg["vocab"], hidden=cfg["hidden"],
                 num_layers=cfg["layers"], num_heads=cfg["heads"],
                 num_kv_heads=cfg["heads"], intermediate=cfg["ffn"])
    new = cfg["new_tokens"]
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, cfg["vocab"], (n,)).tolist()
               for n in cfg["prompt_lens"]]

    t0 = time.perf_counter()
    predictor, mlp_reference = _mlp_predictor(**cfg["mlp"])
    gen = GenerationEngine(
        model, num_slots=cfg["slots"], max_seq_len=cfg["max_seq"],
        prefill_buckets=cfg["prefill_buckets"], max_new_tokens=new,
        page_tokens=cfg["page_tokens"], prefill_chunk=0,
        prefix_reuse=False, attn_impl="auto", keep_logits=True, seed=0,
        deadline_ms=600000.0,
        # (held to the float32 uncached forward at TOL; what the engine's
        # own rule picks for this model, bfloat16, is ``dtype_phase``'s)
        dtype="float32")
    engine = ServingEngine(predictor, workers=1, max_batch=8,
                           max_delay_ms=2.0, deadline_ms=600000.0,
                           warmup_shapes={"x": (cfg["mlp"]["feat"],)})
    engine.attach_generator(gen)
    server = serve(engine)
    try:
        compiled = gen.warmup()
        setup_s = time.perf_counter() - t0
        say(f"serve: engines up, {compiled} generation programs + the "
            f"/predict buckets compiled; KV pool "
            f"{gen.kv_cache_bytes / 2 ** 30:.2f} GiB "
            f"[{setup_s:.1f} s set-up]")

        # /predict against a float64 numpy forward of the same weights
        x = rng.rand(3, cfg["mlp"]["feat"]).astype("float32")
        status, body = _http(server.url + "/predict",
                             {"inputs": {"x": x.tolist()}})
        got = np.asarray(json.loads(body)["outputs"][0], "float64")
        ref = mlp_reference(x)
        check(status == 200 and got.shape == ref.shape,
              f"/predict answered {status} with shape {got.shape}")
        rel = float(np.abs(got - ref).max() / np.abs(ref).max())
        check(np.isfinite(got).all() and rel <= TOL,
              f"/predict off the numpy reference by {rel:.4g} of its range")
        say(f"serve: /predict {got.shape} within {rel:.4g} of the float64 "
            f"reference's range (tolerance {TOL})")

        # /generate: one prompt per prefill bucket, the middle one
        # streamed, each against the in-process engine call
        buckets = set()
        for i, prompt in enumerate(prompts):
            ref = gen.generate(prompt, new)
            stream = i == 1
            status, body = _http(
                server.url + "/generate",
                {"prompt": prompt, "max_new_tokens": new,
                 "stream": stream})
            check(status == 200, f"/generate answered {status}")
            if stream:
                lines = [json.loads(ln) for ln in body.splitlines() if ln]
                tokens = [ln["token"] for ln in lines if "token" in ln]
                done = lines[-1]
                check(done.get("done") and done["tokens"] == tokens,
                      "streamed tokens disagree with the summary line")
            else:
                tokens = json.loads(body)["tokens"]
            check(len(tokens) == new and tokens == ref["tokens"],
                  f"/generate tokens {tokens} != in-process "
                  f"{ref['tokens']} (prompt of {len(prompt)})")
            logits = np.stack(ref["logits"])
            check(logits.shape == (new, cfg["vocab"])
                  and np.isfinite(logits).all(),
                  f"generation logits {logits.shape} not finite")
            buckets.add(min(b for b in gen.prefill_buckets
                            if b >= len(prompt)))
            say(f"serve: /generate prompt {len(prompt):4d} -> {tokens}"
                + (" (streamed)" if stream else ""))
        check(len(buckets) >= 2, f"prompts landed in one bucket {buckets}")

        # the paged prefill (Pallas) + cached decode against the uncached
        # einsum forward, on the short prompt
        ref = gen.generate(prompts[0], new)
        ids = prompts[0] + ref["tokens"]
        forward = _forward_logits(gen, model, ids, cfg["prefill_buckets"][0])
        want = forward[len(prompts[0]) - 1:len(ids) - 1]
        got = np.stack(ref["logits"])
        rel = float(np.abs(got - want).max() / np.abs(want).max())
        check(rel <= TOL,
              f"cached generation logits off the uncached forward by "
              f"{rel:.4g} of its range")
        say(f"serve: {new} steps of paged prefill + cached decode within "
            f"{rel:.4g} of the uncached einsum forward's range "
            f"(tolerance {TOL})")

        # two overlapping prompts: the second finishes its prefill with a
        # step of the first in flight and rides the step dispatched ahead
        # on its prefill's token as the device holds it, through the one
        # decode executable
        joined0 = gen.stats()["counters"]["decode_joiners_ahead"]
        pair = [gen.submit(p, new) for p in prompts[:2]]
        want = [gen.generate(p, new)["tokens"] for p in prompts[:2]]
        joined = gen.stats()["counters"]["decode_joiners_ahead"] - joined0
        compiled = gen._decode_exe.cache_info()["compiled"]
        check([f.result(600)["tokens"] for f in pair] == want
              and joined >= 1 and compiled == 1,
              f"a joiner did not ride the step ahead: {joined} joined, "
              f"{compiled} decode executables")
        say(f"serve: {joined} joiner rode the step ahead of the settle, "
            f"{compiled} decode executable")

        status, body = _http(server.url + "/healthz")
        health = json.loads(body)
        check(status == 200 and health.get("ready")
              and health["generation"]["counters"]["served"] >= 2 * len(
                  prompts), f"/healthz {status}: {health}")
        status, body = _http(server.url + "/metrics")
        text = body.decode()
        errors = promtext.validate_lines(text)
        check(status == 200 and not errors
              and "serving_generate_requests" in text,
              f"/metrics {status}: {errors[:3]}")
        say(f"serve: /healthz ready, /metrics {len(text.splitlines())} "
            f"valid exposition lines")
        # the streamed prompt's lines left through the one stream
        # writer: a wake-up a booking batch, its start and its summary
        writer = {k: v - streams0[k]
                  for k, v in health["generation"]["stream_writer"].items()}
        check(writer["lines"] == new and 0 < writer["wakeups"] <= new + 2
              and writer["would_block"] == 0 and writer["open"] == 0
              and "serving_stream_writer_wakeups" in text,
              f"the stream writer did not write the streamed prompt's "
              f"{new} lines: {writer}")
        say(f"serve: stream writer {writer}")
        paths = paths_since(paths0)
        say(f"serve: attention lowered as {paths}")
        # a prefill bucket of whole pages puts its prompt into every pool
        # page by page, and re-lays none (SERVE's buckets all are)
        writes = {k: v - writes0[k] for k, v in pool_writes().items()}
        pools = 2 * cfg["layers"]
        ragged = [b for b in gen.prefill_buckets if b % cfg["page_tokens"]]
        say(f"serve: prefill pool writes lowered as {writes}")
        check(writes["pages"] >= pools * len(buckets - set(ragged))
              and bool(writes["rows"]) == bool(ragged),
              f"a prefill of whole pages did not write its pools page by "
              f"page: {writes}")
        if on_chip:
            # the engine holds one device whatever the host has
            check(paths.get("pallas") and not paths.get("blockwise"),
                  f"prefill did not lower to the Pallas kernel: {paths}")
            check(paths.get("paged_decode")
                  and not paths.get("paged_decode_reference"),
                  f"the decode step did not lower to the paged-attention "
                  f"kernel: {paths}")
    finally:
        server.close()
    deadline = time.monotonic() + 30.0
    while threading.active_count() > 1 and time.monotonic() < deadline:
        time.sleep(0.1)
    alive = [t.name for t in threading.enumerate()
             if t is not threading.main_thread()]
    check(not alive, f"threads alive after close(): {alive}")
    say("serve: closed, only the main thread alive")
    return {"paths": paths, "writes": writes, "setup_s": setup_s}


# ---------------------------------------------------------------------------

SPARSE = dict(heads=28, kv_heads=4, head_dim=128, window=4096,
              page_tokens=16, contexts=(100, 4096, 4097, 8000),
              hidden=2560, experts=64, top_k=6, expert_width=768, slots=32,
              prefill_rung=512, rung_real_rows=300)


def sparse_window_phase(cfg=SPARSE):
    """The two kernels a sparse-expert, windowed decoder adds to a decode
    step, at that family's published shapes (28 query over 4 KV heads of
    128, window 4096; 64 experts, top 6, width 768 on hidden 2560): the
    windowed paged-decode kernel against the gather + einsum formulation,
    and the routed expert layer against a loop over all experts, both at
    "highest" precision: the layer at a decode step's 32 tokens and a
    prefill rung's 512 (both products on the Pallas grouped matmul:
    ``grouped_matmul_lowered_pallas`` +2 each, PR 50) and at the check
    engine's 2 (``grouped_matmul_lowered_ragged_dot`` +2); and (PR 54)
    the rung with 300 real rows and the check engine's two slots with one
    live, NaN in every row behind ``valid``: the real rows are the
    loop's, the others' ``out`` exactly 0, the counts the real rows'
    pairs, the products lowered as for the full rows; and (PR 57) that
    the layer built the gate and the routing weight as the kernels'
    epilogues (``grouped_matmul_epilogue_gate`` / ``_scale`` +1 each where
    the products are the kernel, +0 at the check engine's rows) and came
    back by the gather-sum (``moe_combine_gather`` +1).  The held
    share of such a layer (``held_first``) is checked at published widths
    in ``share_and_channel_phase``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.ops.decode_ops import _attend_cache, _gather_pages
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_decode_attention
    from paddle_tpu.parallel.moe import moe_routed_tokens

    key = jax.random.key(28)
    pt_, w, ctx = cfg["page_tokens"], cfg["window"], cfg["contexts"]
    B, NP = len(ctx), -(-max(ctx) // cfg["page_tokens"])
    shape = (B * NP + 1, cfg["kv_heads"], pt_, cfg["head_dim"])
    pk = jax.random.normal(jax.random.fold_in(key, 0), shape)
    pv = jax.random.normal(jax.random.fold_in(key, 1), shape)
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, cfg["heads"], 1, cfg["head_dim"]))
    bt = np.arange(1, B * NP + 1, dtype=np.int32).reshape(B, NP)
    pos = np.asarray([n - 1 for n in ctx], np.int32)
    for b, p in enumerate(pos):
        bt[b, :max(0, p - w + 1) // pt_] = 0   # slid out: the trash page
        bt[b, p // pt_ + 1:] = 0
    bt, pos = jnp.asarray(bt), jnp.asarray(pos)
    got = paged_decode_attention(q, pk, pv, bt, pos, window=w)
    with jax.default_matmul_precision("highest"):
        want = _attend_cache(q, _gather_pages(pk, bt),
                             _gather_pages(pv, bt), pos, None, w)
    rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    check(bool(jnp.isfinite(got).all()) and rel <= TOL,
          f"windowed paged decode off the gather formulation by {rel:.4g}")
    say(f"sparse: windowed paged decode, contexts {ctx} under window {w}, "
        f"within {rel:.4g} of the gather formulation (tolerance {TOL})")

    H, E, I, k = (cfg[n] for n in ("hidden", "experts", "expert_width",
                                   "top_k"))
    x, rx = (jax.random.normal(jax.random.fold_in(key, i),
                               (cfg["prefill_rung"], H)) for i in (3, 4))
    rw = jax.random.normal(jax.random.fold_in(key, 5), (H, E)) * 0.02
    gu = jax.random.normal(jax.random.fold_in(key, 6), (E, H, 2 * I)) * 0.02
    dn = jax.random.normal(jax.random.fold_in(key, 7), (E, I, H)) * 0.02
    hi = jax.lax.Precision.HIGHEST
    lowered = ("grouped_matmul_lowered_pallas",
               "grouped_matmul_lowered_ragged_dot")
    # ... and (PR 57) what the layer built round them: the gate and the
    # routing weight as the kernels' epilogues, the one gather-sum
    built = ("grouped_matmul_epilogue_gate", "grouped_matmul_epilogue_scale",
             "moe_combine_gather")
    # a decode step's rows, a prefill rung's, and the check engine's two
    # slots (fewer rows than the kernel's row block: the one ragged_dot)
    # ... and a rung and the two slots with rows behind ``valid``
    for tokens, real, kernels in (
            (cfg["slots"], None, 2), (cfg["prefill_rung"], None, 2),
            (cfg["prefill_rung"], cfg["rung_real_rows"], 2), (2, None, 0),
            (2, 1, 0)):
        before = [stat_get(n) for n in lowered + built]
        fed, live = [x[:tokens], rx[:tokens]], None
        if real is not None:
            live = jnp.arange(tokens) < real
            fed = [jnp.where(live[:, None], a, jnp.nan) for a in fed]
        got, counts, logits = jax.jit(
            lambda *a, valid: moe_routed_tokens(
                *a, top_k=k, precision=hi, valid=valid))(
                *fed, rw, gu, dn, valid=live)
        grew = [stat_get(n) - b for n, b in zip(lowered + built, before)]
        grew, fused = grew[:2], grew[2:]
        check(fused == [int(kernels == 2)] * 2 + [1],
              f"routed expert layer of {tokens} rows built "
              f"{dict(zip(built, fused))}, expected both epilogues exactly "
              f"where its products are the Pallas kernel, and the gather-sum")
        if real is not None:
            check(not bool(got[real:].any()),
                  f"routed expert layer of {tokens} rows, {real} real: a "
                  f"row behind them has an expert output")
            got, logits = got[:real], logits[:real]
        rows, tokens = tokens, tokens if real is None else real
        with jax.default_matmul_precision("highest"):
            top = jax.lax.top_k(logits, k)[1]
            chosen = jax.nn.one_hot(top, E, dtype=bool).any(1)
            wts = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), -1)
            h = jnp.einsum("nh,ehf->enf", x[:tokens], gu)
            y = jnp.einsum("eni,eih->enh",
                           jnp.maximum(h[..., :I], 0) * h[..., I:], dn)
            want = jnp.einsum("ne,enh->nh", jnp.where(chosen, wts, 0.0), y)
        rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        check(int(counts.sum()) == tokens * k and rel <= TOL,
              f"routed expert layer of {tokens} tokens off the loop over "
              f"all experts by {rel:.4g}, {int(counts.sum())} pairs placed")
        check(grew == [kernels, 2 - kernels],
              f"routed expert layer of {tokens} tokens lowered "
              f"{dict(zip(lowered, grew))}, expected {kernels} of its two "
              f"products on the Pallas kernel")
        say(f"sparse: {tokens} tokens in {rows} rows x top-{k} of {E} "
            f"experts, "
            f"{int((counts > 0).sum())} experts touched, nothing dropped, "
            f"within {rel:.4g} of the loop over all experts; "
            f"grouped_matmul_lowered_pallas +{grew[0]}, _ragged_dot "
            f"+{grew[1]}")


HYBRID = dict(heads=32, kv_heads=8, head_dim=64, page_tokens=16,
              contexts=(1, 70, 900, 3000), hidden=2048, conv_taps=3,
              prompt=70, steps=3)


def slot_state_phase(cfg=HYBRID):
    """What a decoder with gated short-convolution layers beside heads of
    64 adds (PR 34), at that family's published shapes: the paged-decode
    kernel at 32 query over 8 KV heads of 64, the pool packed two heads a
    128-lane row, against the gather + einsum formulation, with the two
    counters of the op inside a program; and one convolution-state round
    trip through a two-slot ``GenerationEngine`` of one conv layer and
    one attention layer at hidden 2048: a prefill, three decode steps,
    the slot taken again, each against the uncached forward."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.decode_ops import (_attend_cache, _gather_pages,
                                           pool_shape)
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, supported)
    from paddle_tpu.serving import GenerationEngine

    key = jax.random.key(34)
    pt_, ctx, D = cfg["page_tokens"], cfg["contexts"], cfg["head_dim"]
    B, NP = len(ctx), -(-max(ctx) // cfg["page_tokens"])
    shape = tuple(pool_shape(B * NP + 1, cfg["kv_heads"], pt_, D))
    check(shape[1:] == (cfg["kv_heads"] // 2, pt_, 128),
          f"heads of 64 are not packed two a row: pool {shape}")
    pk = jax.random.normal(jax.random.fold_in(key, 0), shape)
    pv = jax.random.normal(jax.random.fold_in(key, 1), shape)
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, cfg["heads"], 1, D))
    check(supported(q.shape, shape), "the paged kernel refuses head 64")
    bt = jnp.asarray(np.arange(1, B * NP + 1, dtype=np.int32).reshape(B, NP))
    pos = jnp.asarray([n - 1 for n in ctx], jnp.int32)
    got = paged_decode_attention(q, pk, pv, bt, pos)
    with jax.default_matmul_precision("highest"):
        want = _attend_cache(q, _gather_pages(pk, bt, D),
                             _gather_pages(pv, bt, D), pos)
    rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    check(bool(jnp.isfinite(got).all()) and rel <= TOL,
          f"head-64 paged decode off the gather formulation by {rel:.4g}")
    say(f"hybrid: paged decode at {cfg['heads']} / {cfg['kv_heads']} heads "
        f"of {D}, contexts {ctx}, within {rel:.4g} of the gather "
        f"formulation (tolerance {TOL})")

    conv = {"kind": "conv", "L_cache": cfg["conv_taps"], "bias": False}
    model = dict(vocab_size=4096, hidden=cfg["hidden"], num_layers=2,
                 num_heads=cfg["heads"], num_kv_heads=cfg["kv_heads"],
                 intermediate=4096, rms_norm_eps=1e-5, rope_base=1e6,
                 qk_norm=True, tie_head=True,
                 layer_pattern=[{"mixer": conv}, {"mixer": "attention"}])
    before = attention_paths()
    gen = GenerationEngine(model, num_slots=2, max_seq_len=256,
                           prefill_buckets=[128], page_tokens=pt_,
                           prefill_chunk=0, prefix_reuse=False,
                           speculate=False, keep_logits=True, eos_id=-1)
    try:
        gen.warmup()
        lowered = paths_since(before)
        check(lowered.get("paged_decode", 0) >= 1
              and not lowered.get("paged_decode_reference"),
              f"the head-64 decode step did not lower to the Pallas "
              f"kernel: {lowered}")
        rng = np.random.default_rng(34)
        worst = 0.0
        for n in (cfg["prompt"], 2):     # the second reuses slot 0
            prompt = rng.integers(1, 4096, n).tolist()
            res = gen.generate(prompt, cfg["steps"] + 1, timeout=600)
            check(res["slot"] == 0, f"request landed in slot {res['slot']}")
            seq = prompt + res["tokens"]
            want = _forward_logits(gen, model, seq, 128)[n - 1:n + cfg["steps"]]
            got = np.stack(res["logits"])
            rel = float(np.abs(got - want).max() / np.abs(want).max())
            worst = max(worst, rel)
            check(np.isfinite(got).all() and rel <= TOL,
                  f"conv-state round trip (prompt {n}) off the uncached "
                  f"forward by {rel:.4g}")
        writes = gen.stats()["counters"]["slot_state_writes"]
        check(writes == 2, f"{writes} prefills wrote a slot's state, not 2")
    finally:
        gen.close()
    say(f"hybrid: conv state through prefill, {cfg['steps']} decode steps "
        f"and a reused slot within {worst:.4g} of the uncached forward; "
        f"decode step on the Pallas kernel ({lowered})")


DELTA = dict(heads=30, key_dim=96, value_dim=192, conv=4, slots=32,
             seq=640, valid=600, hidden=3840, prompt=150, steps=3)


def _delta_kernels_against_xla(tag, seed, H, Dk, Dv, T, n_valid, n,
                               channel):
    """The two Pallas kernels of ``ops/pallas/gated_delta.py`` against the
    XLA formulations of ``ops/gated_delta_ops.py``: the whole scan over a
    padded prompt (``n_valid`` of ``T`` rows real) and the step over ``n``
    slots of which some are dead (their state and the trash row bit for
    bit what they were).  ``channel``: the log decay a vector a key
    channel, not a number a head."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_ops as gd
    from paddle_tpu.ops.pallas import gated_delta as kern

    key = jax.random.key(seed)
    gdim = (Dk,) if channel else ()

    def draw(i, *shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = unit(draw(0, 1, T, H, Dk)) * Dk ** -0.5, unit(draw(1, 1, T, H, Dk))
    v, g = draw(2, 1, T, H, Dv), -jnp.abs(draw(3, 1, T, H, *gdim))
    beta = 2 * jax.nn.sigmoid(draw(4, 1, T, H))
    valid = jnp.asarray([n_valid], jnp.int32)
    want_o, want_s = jax.jit(lambda *a: gd.chunked(*a, valid=valid))(
        q, k, v, g, beta)
    got_o, got_s = kern.chunk(q, k, v, g, beta, valid=valid)
    rel = max(float(jnp.abs(got_o - want_o).max() / jnp.abs(want_o).max()),
              float(jnp.abs(got_s - want_s).max() / jnp.abs(want_s).max()))
    check(bool(jnp.isfinite(got_o).all()) and rel <= TOL,
          f"gated_delta_chunk kernel off the scan by {rel:.4g}")
    state = draw(5, n + 1, H, Dk, Dv)
    live = jnp.asarray(np.arange(n) % 5 != 3, jnp.int32)
    sq, sk = unit(draw(6, n, H, Dk)) * Dk ** -0.5, unit(draw(7, n, H, Dk))
    sv, sg = draw(8, n, H, Dv), -jnp.abs(draw(9, n, H, *gdim))
    sb = 2 * jax.nn.sigmoid(draw(10, n, H))
    want_o, want_s = jax.jit(gd.step)(sq, sk, sv, sg, sb, state,
                                      live.astype(bool))
    got_o, got_s = kern.step(sq, sk, sv, sg, sb, state, live)
    on = np.asarray(live, bool)
    rel_step = max(
        float(np.abs(np.asarray(got_o - want_o))[on].max()
              / jnp.abs(want_o).max()),
        float(jnp.abs(got_s - want_s).max() / jnp.abs(want_s).max()))
    check(rel_step <= TOL,
          f"gated_delta_step kernel off the contractions by {rel_step:.4g}")
    check(bool(jnp.array_equal(got_s[:n][~on], state[:n][~on]))
          and bool(jnp.array_equal(got_s[n], state[n])),
          "gated_delta_step moved a dead slot's state or the trash row")
    say(f"{tag}: kernels at {H} heads of {Dk} x {Dv}"
        f"{', a decay a key channel' if channel else ''}: the whole scan "
        f"over {n_valid} of {T} rows within {rel:.4g} of the XLA form, the step "
        f"over {int(on.sum())} live of {n} slots within {rel_step:.4g} of "
        f"the contractions, dead slots untouched (tolerance {TOL})")


def delta_state_phase(cfg=DELTA):
    """What a decoder with gated delta-rule layers adds (PR 41), at that
    family's published head sizes (30 heads, keys of 96, values of 192):
    the two Pallas kernels of ``ops/pallas/gated_delta.py`` against the
    XLA formulations of ``ops/gated_delta_ops.py`` (the whole scan over a
    padded prompt, the step over 32 slots of which some are dead: their
    state and the trash row bit for bit what they were); and one
    delta-state round trip through a two-slot ``GenerationEngine`` of one
    delta layer and one full-attention layer at hidden 3840 (norms on the
    outputs, QK-norm over the projection, no rotary embedding): a prefill
    of more than two chunks, three decode steps, the slot taken again,
    each against the uncached forward, with the lowering counters."""
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.serving import GenerationEngine

    H, Dk, Dv = cfg["heads"], cfg["key_dim"], cfg["value_dim"]
    _delta_kernels_against_xla("delta", 41, H, Dk, Dv, cfg["seq"],
                               cfg["valid"], cfg["slots"], channel=False)

    delta = {"kind": "gated_delta", "key_heads": H, "value_heads": H,
             "key_dim": Dk, "value_dim": Dv, "conv": cfg["conv"],
             "neg_eigval": True}
    model = dict(vocab_size=4096, hidden=cfg["hidden"], num_layers=2,
                 num_heads=30, num_kv_heads=30, intermediate=4096,
                 rms_norm_eps=1e-6, qk_norm="proj", norm="post",
                 layer_pattern=[
                     {"mixer": delta, "rope": False},
                     {"mixer": "attention", "rope": False,
                      "attn_precision": "highest"}])
    pal0 = stat_get("gated_delta_lowered_pallas")
    ref0 = stat_get("gated_delta_lowered_reference")
    gen = GenerationEngine(model, num_slots=2, max_seq_len=512,
                           prefill_buckets=[256], page_tokens=16,
                           prefill_chunk=0, prefix_reuse=False,
                           speculate=False, keep_logits=True, eos_id=-1)
    try:
        gen.warmup()
        pallas = stat_get("gated_delta_lowered_pallas") - pal0
        reference = stat_get("gated_delta_lowered_reference") - ref0
        check(pallas >= 2 and reference == 0,
              f"the delta ops lowered to {pallas} kernels and {reference} "
              f"XLA formulations, not to kernels alone")
        rng = np.random.default_rng(41)
        worst = 0.0
        for n_prompt in (cfg["prompt"], 3):   # the second reuses slot 0
            prompt = rng.integers(1, 4096, n_prompt).tolist()
            res = gen.generate(prompt, cfg["steps"] + 1, timeout=600)
            check(res["slot"] == 0, f"request landed in slot {res['slot']}")
            seq = prompt + res["tokens"]
            want = _forward_logits(gen, model, seq, 256)[
                n_prompt - 1:n_prompt + cfg["steps"]]
            got = np.stack(res["logits"])
            rel = float(np.abs(got - want).max() / np.abs(want).max())
            worst = max(worst, rel)
            check(np.isfinite(got).all() and rel <= TOL,
                  f"delta-state round trip (prompt {n_prompt}) off the "
                  f"uncached forward by {rel:.4g}")
        counters = gen.stats()["counters"]
        check(counters["slot_state_writes"] == 2
              and counters["delta_state_steps"] >= 2 * cfg["steps"],
              f"state counters {counters['slot_state_writes']} writes, "
              f"{counters['delta_state_steps']} steps")
    finally:
        gen.close()
    say(f"delta: state through a chunked prefill, {cfg['steps']} decode "
        f"steps and a reused slot within {worst:.4g} of the uncached "
        f"forward; gated_delta_lowered_pallas +{pallas}, "
        f"gated_delta_lowered_reference +{reference}")


SHARE = dict(heads=64, head_dim=128, slots=64, seq=640, valid=600,
             hidden=4096, width=1280, router=320, held=(40, 20), top_k=8,
             rows=(64, 1000, 4096))


def share_and_channel_phase(cfg=SHARE):
    """What one chip's share of an expert-parallel group with KDA layers
    adds (PR 43), at that family's published sizes: the two delta-rule
    kernels with a log decay a key channel (64 heads of 128 x 128: the
    whole scan over a padded prompt with blocks of 16 inside a chunk, the
    step over 64 slots of which some are dead) against their XLA
    formulations; and the held experts' part of a layer (20 of a 320-wide
    router's experts of width 1280 on hidden 4096, 8 a token, sigmoid
    scores with a bias) at a decode step's rows, a chunk's and the widest
    rung's, against a plain loop over the held experts, with its counts;
    both products of each are the Pallas grouped matmul (PR 52:
    ``grouped_matmul_lowered_pallas`` +2 a program, ``_ragged_dot`` +0) on
    runs of ``held_run`` sorted pairs."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.parallel.moe import (held_run, moe_routed_tokens,
                                         route_top_k)

    H, D = cfg["heads"], cfg["head_dim"]
    _delta_kernels_against_xla("share", 43, H, D, D, cfg["seq"],
                               cfg["valid"], cfg["slots"], channel=True)
    key = jax.random.key(43)

    def draw(i, *shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape)

    hid, width, E, k_top = cfg["hidden"], cfg["width"], cfg["router"], \
        cfg["top_k"]
    first, held = cfg["held"]
    router = draw(11, hid, E) * hid ** -0.5
    bias = 0.02 * draw(12, E)
    gate_up = draw(13, held, hid, 2 * width) * hid ** -0.5
    down = draw(14, held, width, hid) * width ** -0.5
    hi = jax.lax.Precision.HIGHEST

    # (the weights are arguments: closed over, 1.3 GB of them would be
    # constants of each compiled program)
    @jax.jit
    def plain(x, router, bias, gate_up, down):
        _, experts, weights = route_top_k(x, router, k_top, "sigmoid", bias)
        dense = jnp.zeros((x.shape[0], E)).at[
            jnp.arange(x.shape[0])[:, None], experts].set(weights)

        def one(e, acc):
            h = jnp.dot(x, gate_up[e], precision=hi)
            y = jnp.dot(jax.nn.silu(h[:, :width]) * h[:, width:], down[e],
                        precision=hi)
            return acc + dense[:, first + e, None] * y

        return jax.lax.fori_loop(0, held, one, jnp.zeros_like(x)), experts

    share = jax.jit(lambda x, router, bias, gate_up, down: moe_routed_tokens(
        x, x, router, gate_up, down, top_k=k_top, activation="silu",
        precision=hi, score="sigmoid", expert_bias=bias, held_first=first))
    lowered = ("grouped_matmul_lowered_pallas",
               "grouped_matmul_lowered_ragged_dot")
    for rows in cfg["rows"]:
        x = draw(20 + rows, rows, hid)
        want, experts = plain(x, router, bias, gate_up, down)
        before = [stat_get(n) for n in lowered]
        out, counts, _ = share(x, router, bias, gate_up, down)
        grew = [stat_get(n) - b for n, b in zip(lowered, before)]
        check(grew == [2, 0],
              f"the held experts' part of {rows} rows lowered "
              f"{dict(zip(lowered, grew))}, expected both products on the "
              f"Pallas kernel")
        here = int(((experts >= first) & (experts < first + held)).sum())
        rel = float(jnp.abs(out - want).max() / jnp.abs(want).max())
        check(bool(jnp.isfinite(out).all()) and rel <= TOL,
              f"the held experts' part of {rows} rows off the plain loop "
              f"by {rel:.4g}")
        check(int(counts.sum()) == rows * k_top
              and int(counts[first:first + held].sum()) == here,
              f"counts {int(counts.sum())} routed, "
              f"{int(counts[first:first + held].sum())} held, want "
              f"{rows * k_top} and {here}")
        say(f"share: {held} of {E} experts held, {rows} rows x {k_top}: "
            f"{here} held pairs in runs of "
            f"{held_run(rows * k_top, held, E, True)} within {rel:.4g} of "
            f"the plain loop; grouped_matmul_lowered_pallas +{grew[0]}, "
            f"_ragged_dot +{grew[1]}")


LATENT = dict(heads=64, latent=512, nope=128, rope=64, v=128, slots=32,
              rows=2000, page_tokens=16, seq=768, hidden=1024)


def latent_phase(cfg=LATENT):
    """What a decoder with latent (MLA) attention adds (PR 47), at that
    family's published head sizes (64 heads of nope 128 + rope 64 over a
    latent of 512, values of 128): the absorbed decode kernel
    ``mla_decode_attention`` over a pool of 640-lane rows at 32 slots of
    up to 2,000 rows (an idle slot, permuted pages, NaN in every page a
    slot does not own and behind every live length) and the expanded
    prefill kernel ``mla_prefill_attention`` at keys of 192 over values of
    128, each against plain float32 "highest" einsums; then one round trip
    through a two-slot ``GenerationEngine`` of one latent layer and one
    delta layer with two value heads a key head (sandwich norms, clamped
    SwiGLU, YaRN over interleaved pairs): a prefill, eight absorbed decode
    steps, the slot taken again, against the uncached forward's expanded
    path, with the lowering counters."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.ops.pallas import latent_attention as la
    from paddle_tpu.serving import GenerationEngine

    H, C, dn, dr, dv = (cfg[k] for k in ("heads", "latent", "nope", "rope",
                                         "v"))
    B, pt_ = cfg["slots"], cfg["page_tokens"]
    row = la.row_lanes(C + dr)
    np_slot = -(-cfg["rows"] // pt_)
    P = B * np_slot + 1
    key = jax.random.key(47)
    pos = jnp.asarray(np.r_[0, cfg["rows"] - 1, np.random.default_rng(47)
                            .integers(1, cfg["rows"], B - 2)], jnp.int32)
    table = np.random.default_rng(48).permutation(P - 1)[:B * np_slot] + 1
    table = jnp.asarray(table.reshape(B, np_slot), jnp.int32)
    rows = jax.random.normal(jax.random.fold_in(key, 1),
                             (B, np_slot * pt_, C + dr), jnp.float32)
    live = jnp.arange(np_slot * pt_)[None, :] <= pos[:, None]
    padded = jnp.pad(jnp.where(live[..., None], rows, jnp.nan),
                     ((0, 0), (0, 0), (0, row - C - dr)))
    pool = jnp.full((P, 1, pt_, row), jnp.nan, jnp.float32).at[
        table.reshape(-1), 0].set(padded.reshape(B * np_slot, pt_, row))
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, H, C + dr),
                          jnp.float32) * 0.1
    q_row = jnp.pad(q, ((0, 0), (0, 0), (0, row - C - dr)))
    scale = (dn + dr) ** -0.5
    got = la.mla_decode_attention(q_row, pool, table, pos, scale=scale,
                                  value_dim=C)
    hi = jax.lax.Precision.HIGHEST
    clean = jnp.where(live[..., None], rows, 0.0)
    s = jnp.einsum("bhc,bsc->bhs", q, clean, precision=hi) * scale
    p = jax.nn.softmax(jnp.where(live[:, None], s, -jnp.inf), -1)
    want = jnp.einsum("bhs,bsc->bhc", p, clean[..., :C], precision=hi)
    rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    check(bool(jnp.isfinite(got).all()) and rel <= 2.0 ** -16,
          f"mla_decode_attention off the einsums by {rel:.4g}")

    S = cfg["seq"]
    qf, kf = (jax.random.normal(jax.random.fold_in(key, i),
                                (1, H, S, dn + dr), jnp.float32)
              for i in (3, 4))
    vf = jax.random.normal(jax.random.fold_in(key, 5), (1, H, S, dv),
                           jnp.float32)
    got = la.mla_prefill_attention(qf, kf, vf, scale=scale)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf, precision=hi) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vf,
                      precision=hi)
    rel_pre = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    check(bool(jnp.isfinite(got).all()) and rel_pre <= 2.0 ** -16,
          f"mla_prefill_attention off the einsums by {rel_pre:.4g}")
    say(f"latent: the absorbed decode kernel over {int(pos.sum()) + B} "
        f"live rows of {B} slots within {rel:.4g} of the einsums, the "
        f"expanded prefill kernel at {S} rows within {rel_pre:.4g}")

    delta = {"kind": "gated_delta", "key_heads": 4, "value_heads": 8,
             "key_dim": 128, "value_dim": 128, "conv": 4,
             "gate": "sigmoid", "gate_scale": 2.0}
    mla = {"q_rank": 384, "kv_rank": C, "nope_dim": dn, "rope_dim": dr,
           "v_dim": dv, "interleave": True,
           "scale": scale * (0.1 * np.log(8) + 1) ** 2,
           "yarn": {"factor": 8, "original_max": 32768, "beta_fast": 32,
                    "beta_slow": 1}}
    model = dict(vocab_size=4096, hidden=cfg["hidden"], num_layers=2,
                 num_heads=H, num_kv_heads=H, intermediate=2048,
                 rms_norm_eps=1e-6, rope_base=1e5, norm="pre_post",
                 layer_pattern=[
                     {"mixer": delta, "swiglu_limit": 10.0},
                     {"mixer": "attention", "mla": mla, "attn_gate": True,
                      "swiglu_limit": 10.0}])
    names = ("attention_lowered_latent_decode",
             "attention_lowered_latent_decode_reference",
             "attention_lowered_latent_prefill",
             "gated_delta_lowered_pallas", "gated_delta_lowered_reference")
    before = {n: stat_get(n) for n in names}
    writes0 = pool_writes()
    gen = GenerationEngine(model, num_slots=2, max_seq_len=512,
                           prefill_buckets=[256], page_tokens=16,
                           prefill_chunk=0, prefix_reuse=False,
                           speculate=False, keep_logits=True, eos_id=-1)
    try:
        gen.warmup()
        grew = {n: stat_get(n) - before[n] for n in names}
        check(grew["attention_lowered_latent_decode"] == 1
              and grew["attention_lowered_latent_decode_reference"] == 0
              and grew["attention_lowered_latent_prefill"] == 1
              and grew["gated_delta_lowered_pallas"] >= 2
              and grew["gated_delta_lowered_reference"] == 0,
              f"the latent and delta ops lowered to {grew}")
        wrote = {k: v - writes0[k] for k, v in pool_writes().items()}
        check(wrote == {"pages": 1, "rows": 0},
              f"the latent pool's prefill write lowered to {wrote}")
        rng = np.random.default_rng(47)
        worst = 0.0
        for n_prompt in (200, 3):             # the second reuses slot 0
            prompt = rng.integers(1, 4096, n_prompt).tolist()
            res = gen.generate(prompt, 9, timeout=600)
            check(res["slot"] == 0, f"request landed in slot {res['slot']}")
            seq = prompt + res["tokens"]
            want = _forward_logits(gen, model, seq, 256)[
                n_prompt - 1:n_prompt + 8]
            got = np.stack(res["logits"])
            rel = float(np.abs(got - want).max() / np.abs(want).max())
            worst = max(worst, rel)
            check(np.isfinite(got).all() and rel <= TOL,
                  f"latent round trip (prompt {n_prompt}) off the "
                  f"uncached forward by {rel:.4g}")
    finally:
        gen.close()
    say(f"latent: pages through a prefill, eight absorbed decode steps "
        f"and a reused slot within {worst:.4g} of the uncached forward's "
        f"expanded path; lowered {grew}")


CHUNKS = dict(heads=128, kv_heads=8, head_dim=128, rows=512, base=4096,
              view=6144, window=4096,
              engine=dict(hidden=1024, heads=16, kv_heads=1, head_dim=128,
                          ffn=2048, vocab=4096, window=512, chunk=256,
                          max_seq=2048, page_tokens=16, prompt=1300,
                          steps=4))


def chunk_phase(cfg=CHUNKS):
    """The chunk attention kernel at the published head shape against the
    einsum formulation, then a chunked prefill over a full and a window
    page pool through a two-slot engine: which chunk attention the
    programs lowered to, and the logits against the same weights' single-
    shot prefill."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.ops.decode_ops import _attend_cache
    from paddle_tpu.ops.pallas.flash_attention import chunk_attention
    from paddle_tpu.serving import GenerationEngine

    key = jax.random.key(51)
    H, Hkv, D = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    q = jax.random.normal(jax.random.fold_in(key, 0),
                          (1, H, cfg["rows"], D))
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, Hkv, cfg["view"], D))
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (1, Hkv, cfg["view"], D))
    base = jnp.asarray([cfg["base"]], jnp.int32)
    for window in (None, cfg["window"]):
        got = chunk_attention(q, k, v, base, window=window)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda q, k, v, b, w=window: _attend_cache(
                q, k, v, b, None, w))(q, k, v, base)
        rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        check(bool(jnp.isfinite(got).all()) and rel <= 2.0 ** -12,
              f"chunk attention under window {window} off the einsum "
              f"formulation by {rel:.4g}")
        say(f"chunks: {cfg['rows']} rows of {H} query over {Hkv} KV heads "
            f"at base {cfg['base']}, window {window}: within {rel:.4g} of "
            f"the einsum formulation (float32 operands whole)")
    del q, k, v

    e = cfg["engine"]
    model = dict(vocab_size=e["vocab"], hidden=e["hidden"], num_layers=2,
                 num_heads=e["heads"], num_kv_heads=e["kv_heads"],
                 head_dim=e["head_dim"], intermediate=e["ffn"],
                 norm="parallel", norm_kind="layer", tie_head=True,
                 rope_base=50000.0, layer_pattern=[
                     {"window": e["window"], "rope": True,
                      "rope_interleave": True, "attn_precision": "highest"},
                     {"window": None, "rope": False,
                      "attn_precision": "highest"}])
    args = dict(num_slots=2, max_seq_len=e["max_seq"], eos_id=-1,
                page_tokens=e["page_tokens"], prefix_reuse=False,
                speculate=False, keep_logits=True, deadline_ms=600000)
    names = ("attention_lowered_chunk_pallas",
             "attention_lowered_chunk_reference")
    before = [stat_get(n) for n in names]
    prompt = np.random.default_rng(51).integers(
        1, e["vocab"], e["prompt"]).tolist()
    whole = GenerationEngine(model, prefill_chunk=0,
                             prefill_buckets=[2048], **args)
    try:
        want = np.stack(whole.generate(prompt, e["steps"],
                                       timeout=600)["logits"])
        chunked = GenerationEngine(
            model, scope=whole.scope, prefill_chunk=e["chunk"],
            prefill_buckets=[64, e["chunk"]], **args)
        try:
            res = chunked.generate(prompt, e["steps"], timeout=600)
            stats = chunked.stats()
        finally:
            chunked.close()
    finally:
        whole.close()
    got = np.stack(res["logits"])
    grew = [stat_get(n) - b for n, b in zip(names, before)]
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    chunks = -(-e["prompt"] // e["chunk"])
    check(stats["counters"]["prefill_chunks"] == chunks
          and stats["paged"]["window"]["pages_released_in_prefill"] > 0,
          f"chunked prefill ran {stats['counters']['prefill_chunks']} "
          f"chunks, let go of "
          f"{stats['paged']['window']['pages_released_in_prefill']} window "
          f"pages while the prompt came in")
    # two chunk rungs built, one op a layer each; nothing downgraded
    check(grew == [4, 0], f"chunk programs lowered {dict(zip(names, grew))}"
          f", expected every chunk attention on the Pallas kernel")
    check(bool(np.isfinite(got).all()) and rel <= 2.0 ** -10,
          f"chunked prefill off the single-shot prefill by {rel:.4g}")
    say(f"chunks: a {e['prompt']}-token prompt in {chunks} chunks of "
        f"{e['chunk']} over a full and a window-{e['window']} page pool, "
        f"{stats['paged']['window']['pages_released_in_prefill']} window "
        f"pages let go while it came in; attention_lowered_chunk_pallas "
        f"+{grew[0]}, _chunk_reference +{grew[1]}; logits within "
        f"{rel:.4g} of the single-shot prefill's")


LATENT_CHUNK = dict(heads=128, latent=512, nope=128, rope=64, v=128,
                    rows=512, base=4096, view=6144, check_heads=16,
                    engine=dict(hidden=1024, heads=16, q_rank=384, ffn=2048,
                                vocab=4096, chunk=256, max_seq=2048,
                                page_tokens=16, prompt=1300, steps=4,
                                experts=16, groups=4, keep=2, top_k=3,
                                width=256))


def latent_chunk_phase(cfg=LATENT_CHUNK):
    """What an all-latent decoder with group-limited routing adds (PR 56):
    the chunk kernel over latent rows (``mla_chunk_attention``) at
    DeepSeek-V2's published head sizes (128 heads of nope 128 + rope 64
    over a latent of 512, values of 128), 512 rows at base 4096 over a
    view whose rows behind the chunk are zero, against "highest" einsums of
    the expanded arithmetic on a few of the heads; then a 1,300-token
    prompt in chunks of 256 over latent pages through a two-slot engine
    (one latent layer over the dense SwiGLU, one over a group-limited
    router of which one whole group is held, YaRN with ``mscale_all_dim``),
    with the lowering counters and the single-shot prefill's logits."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.ops.pallas import latent_attention as la
    from paddle_tpu.serving import GenerationEngine

    H, C, dn, dr, dv = (cfg[k] for k in ("heads", "latent", "nope", "rope",
                                         "v"))
    T, base, S = cfg["rows"], cfg["base"], cfg["view"]
    row = la.row_lanes(C + dr)
    key = jax.random.key(56)
    qn = jax.random.normal(jax.random.fold_in(key, 0), (H, T, dn)) * 0.3
    qr = jax.random.normal(jax.random.fold_in(key, 1), (H, T, dr)) * 0.3
    live = (jnp.arange(S) < base + T)[:, None]
    rows = jnp.pad(jnp.where(live, jax.random.normal(
        jax.random.fold_in(key, 2), (S, C + dr)), 0.0),
        ((0, 0), (0, row - C - dr)))
    w = jax.random.normal(jax.random.fold_in(key, 3),
                          (C, H * (dn + dv))) * C ** -0.5
    scale = (dn + dr) ** -0.5
    at = jnp.asarray([base], jnp.int32)
    got = la.mla_chunk_attention(qn, qr, rows, w, at, scale=scale,
                                 nope_dim=dn, latent_dim=C)
    G = cfg["check_heads"]
    hi = jax.lax.Precision.HIGHEST
    kv = jnp.dot(rows[:, :C], w[:, :G * (dn + dv)],
                 precision=hi).reshape(S, G, dn + dv)
    s = (jnp.einsum("hqd,shd->hqs", qn[:G], kv[..., :dn], precision=hi)
         + jnp.einsum("hqr,sr->hqs", qr[:G], rows[:, C:C + dr],
                      precision=hi)) * scale
    keep = jnp.arange(S)[None, :] <= base + jnp.arange(T)[:, None]
    want = jnp.einsum("hqs,shd->hqd", jax.nn.softmax(
        jnp.where(keep[None], s, -jnp.inf), -1), kv[..., dn:], precision=hi)
    rel = float(jnp.abs(got[:G] - want).max() / jnp.abs(want).max())
    check(bool(jnp.isfinite(got).all()) and rel <= 2.0 ** -16,
          f"mla_chunk_attention off the einsums by {rel:.4g}")
    say(f"latent chunks: {T} rows of {H} heads at base {base} over "
        f"{base + T} latent rows within {rel:.4g} of the expanded einsums "
        f"({G} heads compared)")
    del qn, qr, rows, w, kv, s, want, got

    e = cfg["engine"]
    mla = {"q_rank": e["q_rank"], "kv_rank": C, "nope_dim": dn,
           "rope_dim": dr, "v_dim": dv, "interleave": True,
           "yarn": {"factor": 40, "original_max": 4096, "beta_fast": 32,
                    "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707}}
    per = e["experts"] // e["groups"]
    moe = {"experts": e["experts"], "held": (per, per), "top_k": e["top_k"],
           "width": e["width"], "activation": "silu", "route_from": "normed",
           "norm_topk": False, "route_scale": 16.0, "n_group": e["groups"],
           "topk_group": e["keep"], "shared_width": 2 * e["width"]}
    model = dict(vocab_size=e["vocab"], hidden=e["hidden"], num_layers=2,
                 num_heads=e["heads"], num_kv_heads=e["heads"],
                 intermediate=e["ffn"], rms_norm_eps=1e-6, rope_base=1e4,
                 layer_pattern=[{"mla": mla}, {"mla": mla, "ffn": moe}])
    args = dict(num_slots=2, max_seq_len=e["max_seq"], eos_id=-1,
                page_tokens=e["page_tokens"], prefix_reuse=False,
                speculate=False, keep_logits=True, deadline_ms=600000)
    names = ("attention_lowered_latent_chunk",
             "attention_lowered_latent_chunk_reference")
    before = [stat_get(n) for n in names]
    prompt = np.random.default_rng(56).integers(
        1, e["vocab"], e["prompt"]).tolist()
    whole = GenerationEngine(model, prefill_chunk=0,
                             prefill_buckets=[2048], **args)
    try:
        want = np.stack(whole.generate(prompt, e["steps"],
                                       timeout=600)["logits"])
        chunked = GenerationEngine(
            model, scope=whole.scope, prefill_chunk=e["chunk"],
            prefill_buckets=[64, e["chunk"]], **args)
        try:
            res = chunked.generate(prompt, e["steps"], timeout=600)
            counters = chunked.stats()["counters"]
        finally:
            chunked.close()
    finally:
        whole.close()
    got = np.stack(res["logits"])
    grew = [stat_get(n) - b for n, b in zip(names, before)]
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    chunks = -(-e["prompt"] // e["chunk"])
    check(counters["prefill_chunks"] == chunks
          and counters["moe_tokens_dropped"] == 0
          and 0 < counters["moe_pairs_held"] < counters["moe_pairs_routed"]
          and counters["moe_rows_group_held"] > 0,
          f"chunked latent prefill ran {counters['prefill_chunks']} "
          f"chunks; pairs held {counters['moe_pairs_held']} of "
          f"{counters['moe_pairs_routed']}, rows that kept the held group "
          f"{counters['moe_rows_group_held']}")
    # two chunk rungs built, one op a layer each; nothing downgraded
    check(grew == [4, 0], f"chunk programs lowered {dict(zip(names, grew))}"
          f", expected every latent chunk on the Pallas kernel")
    check(bool(np.isfinite(got).all()) and rel <= 2.0 ** -10,
          f"chunked latent prefill off the single-shot prefill by "
          f"{rel:.4g}")
    say(f"latent chunks: a {e['prompt']}-token prompt in {chunks} chunks "
        f"of {e['chunk']} over latent pages, group-limited routing with "
        f"one group of {per} held ({counters['moe_pairs_held']} of "
        f"{counters['moe_pairs_routed']} pairs, "
        f"{counters['moe_rows_group_held']} row-layers kept the group); "
        f"attention_lowered_latent_chunk +{grew[0]}, "
        f"_latent_chunk_reference +{grew[1]}; logits within {rel:.4g} of "
        f"the single-shot prefill's")


SSD = dict(heads=64, head_dim=64, state=128, conv=4, slots=32, seq=512,
           valid=450, hidden=2048, prompt=300, steps=3)


def _ssd_kernels_against_xla(tag, seed, cfg):
    """The two kernels of ``ops/pallas/ssd.py`` against the XLA
    formulations of ``ops/ssd_ops.py`` at ``cfg``'s sizes (``groups``
    above 1: B and C of ``[.., G, N]``): the whole scan over a padded
    prompt, and the step over ``slots`` slots of which some are dead,
    their state and the trash row bit for bit what they were."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import ssd_ops
    from paddle_tpu.ops.pallas import ssd as kern

    H, P, N = cfg["heads"], cfg["head_dim"], cfg["state"]
    T, n, G = cfg["seq"], cfg["slots"], cfg.get("groups", 1)
    bc = (N,) if G == 1 else (G, N)
    key = jax.random.key(seed)

    def draw(i, *shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape)

    def steps(i, *shape):
        return jnp.exp(jax.random.uniform(
            jax.random.fold_in(key, i), shape, jnp.float32,
            np.log(1e-3), np.log(0.1)))

    a = -jax.random.uniform(jax.random.fold_in(key, 20), (H,), jnp.float32,
                            1.0, 16.0)
    d = jnp.ones((H,), jnp.float32)
    valid = jnp.asarray([cfg["valid"]], jnp.int32)
    ops = (draw(0, 1, T, H, P), steps(1, 1, T, H), a, draw(2, 1, T, *bc),
           draw(3, 1, T, *bc), d)
    want_o, want_s = jax.jit(lambda *t: ssd_ops.chunked(*t, valid=valid))(
        *ops)
    got_o, got_s = kern.chunk(*ops, valid=valid)
    rel = max(float(jnp.abs(got_o - want_o).max() / jnp.abs(want_o).max()),
              float(jnp.abs(got_s - want_s).max() / jnp.abs(want_s).max()))
    check(bool(jnp.isfinite(got_o).all()) and rel <= TOL,
          f"{tag}_chunk kernel off the XLA scan by {rel:.4g}")
    state = draw(5, n + 1, N, H * P)
    live = jnp.asarray(np.arange(n) % 5 != 3, jnp.int32)
    row = (draw(6, n, H, P), steps(7, n, H), a, draw(8, n, *bc),
           draw(9, n, *bc), d)
    want_o, want_s = jax.jit(ssd_ops.step)(*row, state, live.astype(bool))
    got_o, got_s = kern.step(*row, state, live)
    on = np.asarray(live, bool)
    rel_step = max(
        float(np.abs(np.asarray(got_o - want_o))[on].max()
              / jnp.abs(want_o).max()),
        float(jnp.abs(got_s - want_s).max() / jnp.abs(want_s).max()))
    check(rel_step <= TOL,
          f"{tag}_step kernel off the XLA step by {rel_step:.4g}")
    check(bool(jnp.array_equal(got_s[:n][~on], state[:n][~on]))
          and bool(jnp.array_equal(got_s[n], state[n])),
          f"{tag}_step moved a dead slot's state or the trash row")
    say(f"{tag}: kernels at {H} heads of {P} over {N} state rows in {G} "
        f"group(s): the whole scan over {cfg['valid']} of {T} rows within "
        f"{rel:.4g} of the XLA form, the step over {int(on.sum())} live of "
        f"{n} slots within {rel_step:.4g} of it, dead slots untouched "
        f"(tolerance {TOL})")


def ssd_phase(cfg=SSD):
    """What a decoder with state-space duality (Mamba-2) layers adds (PR
    59), at granite-4.0-h-micro's published sizes (64 heads of 64 over 128
    state rows, one group): the two Pallas kernels of
    ``ops/pallas/ssd.py`` against the XLA formulations of
    ``ops/ssd_ops.py`` (the whole scan over a padded prompt; the step over
    32 slots of which some are dead: their state and the trash row bit for
    bit what they were); and one state round trip through a two-slot
    ``GenerationEngine`` of one state-space layer and one attention layer
    without rotary embedding at hidden 2048 under the family's four
    multipliers and the tied head: a prefill of more than two chunks,
    three decode steps, the slot taken again, each against the uncached
    forward, with the lowering counters."""
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.serving import GenerationEngine

    H, P, N = cfg["heads"], cfg["head_dim"], cfg["state"]
    _ssd_kernels_against_xla("ssd", 59, cfg)

    ssd = {"kind": "ssd", "heads": H, "head_dim": P, "state": N,
           "groups": 1, "conv": cfg["conv"], "conv_bias": True}
    model = dict(vocab_size=4096, hidden=cfg["hidden"], num_layers=2,
                 num_heads=32, num_kv_heads=8, intermediate=4096,
                 rms_norm_eps=1e-5, tie_head=True, embed_scale=12.0,
                 residual_scale=0.22, attn_scale=0.015625,
                 logit_scale=0.125,
                 layer_pattern=[
                     {"mixer": ssd, "rope": False},
                     {"mixer": "attention", "rope": False,
                      "attn_precision": "highest"}])
    pal0 = stat_get("ssd_lowered_pallas")
    ref0 = stat_get("ssd_lowered_reference")
    gen = GenerationEngine(model, num_slots=2, max_seq_len=512,
                           prefill_buckets=[384], page_tokens=16,
                           prefill_chunk=0, prefix_reuse=False,
                           speculate=False, keep_logits=True, eos_id=-1)
    try:
        gen.warmup()
        pallas = stat_get("ssd_lowered_pallas") - pal0
        reference = stat_get("ssd_lowered_reference") - ref0
        check(pallas >= 2 and reference == 0,
              f"the state-space ops lowered to {pallas} kernels and "
              f"{reference} XLA formulations, not to kernels alone")
        rng = np.random.default_rng(59)
        worst = 0.0
        for n_prompt in (cfg["prompt"], 3):   # the second reuses slot 0
            prompt = rng.integers(1, 4096, n_prompt).tolist()
            res = gen.generate(prompt, cfg["steps"] + 1, timeout=600)
            check(res["slot"] == 0, f"request landed in slot {res['slot']}")
            seq = prompt + res["tokens"]
            want = _forward_logits(gen, model, seq, 384)[
                n_prompt - 1:n_prompt + cfg["steps"]]
            got = np.stack(res["logits"])
            rel = float(np.abs(got - want).max() / np.abs(want).max())
            worst = max(worst, rel)
            check(np.isfinite(got).all() and rel <= TOL,
                  f"state-space round trip (prompt {n_prompt}) off the "
                  f"uncached forward by {rel:.4g}")
        counters = gen.stats()["counters"]
        check(counters["slot_state_writes"] == 2
              and counters["ssm_state_steps"] >= 2 * cfg["steps"],
              f"state counters {counters['slot_state_writes']} writes, "
              f"{counters['ssm_state_steps']} steps")
    finally:
        gen.close()
    say(f"ssd: state through a chunked prefill, {cfg['steps']} decode "
        f"steps and a reused slot within {worst:.4g} of the uncached "
        f"forward; ssd_lowered_pallas +{pallas}, ssd_lowered_reference "
        f"+{reference}")


GROUPED = dict(heads=128, head_dim=64, state=128, groups=8, slots=32,
               seq=512, valid=450, experts=64, latent=1024, width=2688,
               hidden=2048, rows=512, top_k=6, prompt=300, steps=3)


def single_sublayer_phase(cfg=GROUPED):
    """What a decoder of single-sublayer layers with grouped state-space
    layers and latent experts of two matrices adds (PR 63), at
    nemotron3-super-120b-a12b's published sizes: the two SSD kernels with
    EIGHT groups of B and C at 128 heads of 64 over 128 state rows against
    the XLA formulations (dead slots and the trash row bit for bit
    untouched); the routed layer of non-gated ReLU^2 experts of 2688 in a
    latent of 1024 (64 of them, 6 a token, the router reading rows twice as
    wide) against a loop over the experts, its activation and routing
    weight riding the grouped kernel (``grouped_matmul_epilogue_act``); and
    one round trip through a two-slot ``GenerationEngine`` of three layers
    of ONE sublayer each (state-space, latent experts with a share held
    and a full-width shared expert, attention): a prefill of more than two
    chunks, three decode steps, the slot taken again, each against the
    uncached forward, with the lowering counters."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.parallel import moe
    from paddle_tpu.serving import GenerationEngine

    H, P, N, G = cfg["heads"], cfg["head_dim"], cfg["state"], cfg["groups"]
    _ssd_kernels_against_xla("grouped ssd", 63, cfg)
    key = jax.random.key(63)

    def draw(i, *shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape)

    # experts of two matrices in a latent row, the sorted route
    E, R, I, k = cfg["experts"], cfg["latent"], cfg["width"], cfg["top_k"]
    rows, wide = cfg["rows"], cfg["hidden"]
    u, h = draw(30, rows, R), draw(31, rows, wide)
    router = draw(32, wide, E) * wide ** -0.5
    up = draw(33, E, R, I) * R ** -0.5
    down = draw(34, E, I, R) * I ** -0.5
    counted = {c: stat_get(c) for c in (
        "grouped_matmul_lowered_pallas", "grouped_matmul_lowered_ragged_dot",
        "grouped_matmul_epilogue_act", "grouped_matmul_epilogue_scale",
        "grouped_matmul_epilogue_gate")}
    got, counts, _ = jax.jit(lambda *t: moe.moe_routed_tokens(
        *t, top_k=k, activation="relu2", score="sigmoid", route_scale=5.0,
        precision=jax.lax.Precision.HIGHEST))(u, h, router, up, down)

    @jax.jit
    def loop(u, h, router, up, down):
        _, experts, weights = moe.route_top_k(h, router, k, "sigmoid",
                                             route_scale=5.0)
        dense = jnp.zeros((rows, E), jnp.float32).at[
            jnp.arange(rows)[:, None], experts].set(weights)

        def one(e, acc):
            y = jnp.square(jax.nn.relu(jnp.dot(
                u, up[e], precision="highest")))
            return acc + dense[:, e, None] * jnp.dot(
                y, down[e], precision="highest")

        return jax.lax.fori_loop(0, E, one, jnp.zeros_like(u))

    want = loop(u, h, router, up, down)
    rel_moe = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    grew = {c: stat_get(c) - v for c, v in counted.items()}
    check(rel_moe <= TOL and int(counts.sum()) == rows * k,
          f"latent experts of two matrices off the loop by {rel_moe:.4g}, "
          f"{int(counts.sum())} pairs placed")
    check(grew == {"grouped_matmul_lowered_pallas": 2,
                   "grouped_matmul_lowered_ragged_dot": 0,
                   "grouped_matmul_epilogue_act": 1,
                   "grouped_matmul_epilogue_scale": 1,
                   "grouped_matmul_epilogue_gate": 0},
          f"the two products of experts without a gate lowered to {grew}")
    say(f"latent experts: {E} non-gated ReLU^2 experts of {I} in a latent "
        f"of {R}, {k} a token over {rows} rows, within {rel_moe:.4g} of a "
        f"loop over the experts; the activation and the routing weight on "
        f"the kernel's accumulator ({grew})")

    ssd = {"kind": "ssd", "heads": H, "head_dim": P, "state": N,
           "groups": G, "conv": 4, "conv_bias": True}
    experts = {"experts": E, "held": (8, 8), "top_k": k, "width": I,
               "latent": R, "activation": "relu2", "gated": False,
               "route_from": "normed", "score": "sigmoid",
               "expert_bias": True, "route_scale": 5.0,
               "shared_width": 2 * I}
    model = dict(vocab_size=4096, hidden=4096, num_layers=3, num_heads=32,
                 num_kv_heads=2, head_dim=128, intermediate=I,
                 rms_norm_eps=1e-5, tie_head=False,
                 layer_pattern=[
                     {"mixer": ssd, "ffn": None},
                     {"mixer": None, "ffn": experts},
                     {"mixer": "attention", "ffn": None, "rope": False,
                      "attn_precision": "highest"}])
    pal0 = stat_get("ssd_lowered_pallas")
    ref0 = stat_get("ssd_lowered_reference")
    gen = GenerationEngine(model, num_slots=2, max_seq_len=512,
                           prefill_buckets=[384], page_tokens=16,
                           prefill_chunk=0, prefix_reuse=False,
                           speculate=False, keep_logits=True, eos_id=-1)
    try:
        gen.warmup()
        pallas = stat_get("ssd_lowered_pallas") - pal0
        reference = stat_get("ssd_lowered_reference") - ref0
        check(pallas >= 2 and reference == 0,
              f"the grouped state-space ops lowered to {pallas} kernels and "
              f"{reference} XLA formulations, not to kernels alone")
        rng = np.random.default_rng(63)
        worst = 0.0
        for n_prompt in (cfg["prompt"], 3):   # the second reuses slot 0
            prompt = rng.integers(1, 4096, n_prompt).tolist()
            res = gen.generate(prompt, cfg["steps"] + 1, timeout=600)
            check(res["slot"] == 0, f"request landed in slot {res['slot']}")
            seq = prompt + res["tokens"]
            want = _forward_logits(gen, model, seq, 384)[
                n_prompt - 1:n_prompt + cfg["steps"]]
            got = np.stack(res["logits"])
            rel = float(np.abs(got - want).max() / np.abs(want).max())
            worst = max(worst, rel)
            check(np.isfinite(got).all() and rel <= TOL,
                  f"single-sublayer round trip (prompt {n_prompt}) off the "
                  f"uncached forward by {rel:.4g}")
        counters = gen.stats()["counters"]
        check(counters["slot_state_writes"] == 2
              and counters["ssm_state_steps"] >= 2 * cfg["steps"]
              and counters["moe_tokens_dropped"] == 0
              and 0 < counters["moe_pairs_held"]
              < counters["moe_pairs_routed"],
              f"counters {counters['slot_state_writes']} writes, "
              f"{counters['ssm_state_steps']} state steps, "
              f"{counters['moe_pairs_held']} of "
              f"{counters['moe_pairs_routed']} pairs held")
    finally:
        gen.close()
    say(f"single-sublayer layers: state, a held share of latent experts "
        f"and NoPE attention through a chunked prefill, {cfg['steps']} "
        f"decode steps and a reused slot within {worst:.4g} of the uncached "
        f"forward; ssd_lowered_pallas +{pallas}, ssd_lowered_reference "
        f"+{reference}")


STORE = dict(hidden=1024, heads=8, kv_heads=4, ffn=2048, vocab=4096,
             layers=2, slots=2, max_seq=512, bucket=256, prompt=200,
             new_tokens=4)
STORE_STATS = ("program_store_hits", "program_store_misses",
               "program_store_refused", "compile_cache_hits",
               "compile_cache_misses", "attention_lowered_pallas",
               "attention_lowered_paged_decode", "kv_pool_write_pages",
               "attention_lowered_blockwise",
               "attention_lowered_paged_decode_reference")


SHORTCUT = dict(hidden=6144, heads=8, q_rank=1536, latent=512, nope=128,
                rope=64, v=128, ffn=12288, experts=768, zero=256, held=8,
                width=2048, top_k=12, factor=6.0, rows=(32, 512), vocab=2048,
                chunk=512, max_seq=1024, prompt=700, steps=3)


def shortcut_phase(cfg=SHORTCUT):
    """What a decoder of shortcut-connected layers under a router with
    identity experts adds (PR 66), at longcat-flash-chat's published widths
    for one chip of its 64-chip group: the expert branch (a 768-wide
    softmax router with a selection bias whose last 256 outputs are
    identity experts, 12 picks a token weighed 6 p unnormalised, 8 of the
    512 real experts of 2048 held over 6144) at a decode step's 32 rows and
    a chunk's 512 against a written-out sum, the held products on the
    Pallas grouped matmul inside the scoped VMEM; and one round trip
    through a two-slot ``GenerationEngine`` of ONE published layer (two
    latent sublayers of 8 heads with the inner norms scaled 2 and 3.4641,
    two dense SwiGLUs of 12288, the branch carried past the second
    sublayer): a 700-token prompt in two chunks over latent pages and three
    absorbed decode steps against the uncached forward, with what the
    router's picks were."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.parallel import moe
    from paddle_tpu.serving import GenerationEngine

    hid, E, Z, held = cfg["hidden"], cfg["experts"], cfg["zero"], cfg["held"]
    I, k, factor = cfg["width"], cfg["top_k"], cfg["factor"]
    key = jax.random.key(66)

    def draw(i, *shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape)

    router = draw(1, hid, E) * hid ** -0.5
    bias = 0.001 * draw(2, E)
    gate_up = draw(3, held, hid, 2 * I) * hid ** -0.5
    down = draw(4, held, I, hid) * I ** -0.5
    for rows in cfg["rows"]:
        u = draw(10 + rows, rows, hid)
        counted = {c: stat_get(c) for c in (
            "grouped_matmul_lowered_pallas",
            "grouped_matmul_lowered_ragged_dot")}
        got, counts, _ = jax.jit(lambda *t: moe.moe_routed_tokens(
            *t[:5], top_k=k, activation="silu", score="softmax",
            expert_bias=t[5], norm_topk=False, route_scale=factor,
            held_first=0, zero_experts=Z,
            precision=jax.lax.Precision.HIGHEST))(
                u, u, router, gate_up, down, bias)

        @jax.jit
        def written_out(u, router, gate_up, down, bias):
            p = jax.nn.softmax(jnp.dot(u, router, precision="highest"), -1)
            _, picks = jax.lax.top_k(p + bias, k)
            dense = jnp.zeros_like(p).at[
                jnp.arange(u.shape[0])[:, None], picks].set(
                    factor * jnp.take_along_axis(p, picks, -1))

            def one(e, acc):
                gu = jnp.dot(u, gate_up[e], precision="highest")
                y = jnp.dot(jax.nn.silu(gu[:, :I]) * gu[:, I:], down[e],
                            precision="highest")
                return acc + dense[:, e, None] * y

            real = jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))
            return real + dense[:, E - Z:].sum(-1, keepdims=True) * u, dense

        want, dense = written_out(u, router, gate_up, down, bias)
        rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        grew = {c: stat_get(c) - v for c, v in counted.items()}
        n_zero, n_held = int(counts[E - Z:].sum()), int(counts[:held].sum())
        check(rel <= TOL and int(counts.sum()) == rows * k
              and n_zero == int((dense[:, E - Z:] > 0).sum()),
              f"the expert branch over {rows} rows off the written-out sum "
              f"by {rel:.4g}, {int(counts.sum())} pairs placed")
        check(grew == {"grouped_matmul_lowered_pallas": 2,
                       "grouped_matmul_lowered_ragged_dot": 0},
              f"the held experts' two products lowered to {grew}")
        say(f"shortcut branch: {rows} rows x {k} picks over {E} outputs, "
            f"{n_zero} identity, {n_held} held of {held} real experts of "
            f"{I} over {hid}, within {rel:.4g} of the written-out sum; "
            f"both products on the Pallas kernel")
    del router, gate_up, down, u, got, want, dense

    mla = {"q_rank": cfg["q_rank"], "kv_rank": cfg["latent"],
           "nope_dim": cfg["nope"], "rope_dim": cfg["rope"],
           "v_dim": cfg["v"], "interleave": True,
           "q_norm_scale": (hid / cfg["q_rank"]) ** 0.5,
           "kv_norm_scale": (hid / cfg["latent"]) ** 0.5}
    experts = {"experts": E, "zero_experts": Z, "held": (0, held),
               "top_k": k, "width": I, "activation": "silu",
               "route_from": "normed", "score": "softmax",
               "expert_bias": True, "norm_topk": False,
               "route_scale": factor}
    sub = {"mla": mla, "ffn": "dense"}
    model = dict(vocab_size=cfg["vocab"], hidden=hid, num_layers=2,
                 num_heads=cfg["heads"], num_kv_heads=cfg["heads"],
                 intermediate=cfg["ffn"], rms_norm_eps=1e-5, rope_base=1e7,
                 layer_pattern=[dict(sub, branch=experts),
                                dict(sub, join=True)])
    names = ("attention_lowered_latent_chunk",
             "attention_lowered_latent_chunk_reference",
             "attention_lowered_latent_decode")
    before = [stat_get(n) for n in names]
    gen = GenerationEngine(
        model, num_slots=2, max_seq_len=cfg["max_seq"], eos_id=-1,
        page_tokens=16, prefill_chunk=cfg["chunk"],
        prefill_buckets=[cfg["chunk"] // 2, cfg["chunk"]], prefix_reuse=False,
        speculate=False, keep_logits=True, deadline_ms=600000)
    try:
        gen.scope.set_var(f"{gen.name}.blk0.moe.expert_bias", bias)
        prompt = np.random.default_rng(66).integers(
            1, cfg["vocab"], cfg["prompt"]).tolist()
        res = gen.generate(prompt, cfg["steps"], timeout=600)
        counters = gen.stats()["counters"]
        want = _forward_logits(gen, model, prompt + res["tokens"][:-1],
                               cfg["max_seq"])[cfg["prompt"] - 1:]
    finally:
        gen.close()
    got = np.stack(res["logits"])
    grew = [stat_get(n) - b for n, b in zip(names, before)]
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    routed, zero = counters["moe_pairs_routed"], counters["moe_pairs_zero"]
    check(counters["prefill_chunks"] == 2
          and counters["moe_tokens_dropped"] == 0
          and 0 < counters["moe_pairs_held"] < routed - zero < routed,
          f"the shortcut layer placed {routed} pairs: "
          f"{counters['moe_pairs_held']} held, {zero} identity")
    # two chunk rungs and the step, one latent op a SUBLAYER each
    check(grew == [4, 0, 2], f"latent programs lowered "
          f"{dict(zip(names, grew))}")
    check(bool(np.isfinite(got).all()) and rel <= 2.0 ** -10,
          f"the shortcut layer's chunks and steps off the uncached forward "
          f"by {rel:.4g}")
    say(f"shortcut layer: a {cfg['prompt']}-token prompt in 2 chunks over "
        f"two latent caches and {cfg['steps']} steps, the branch carried "
        f"past the second sublayer, within {rel:.4g} of the uncached "
        f"forward; of {routed} routed pairs {counters['moe_pairs_held']} "
        f"held, {zero} identity")


DENSE = dict(hidden=2048, heads=16, kv_heads=8, ffn=8192, vocab=4096,
             rung=2048, prompts=(600, 2048), steps=1, rows=(768 + 2048, 1280))
# (PR 65) a short rung at Mistral's layer shapes: the single products'
# weights have the 4096 rows from which a rung under 2048 rows takes them
DENSE_SHORT = dict(hidden=4096, heads=32, kv_heads=8, ffn=14336, vocab=4096,
                   rung=1024, prompts=(593, 1024), steps=1,
                   rows=(768 + 1024, 256))


def dense_rows_phase(cfg=DENSE):
    """What a prefill whose dense products stop at the prompt's end adds
    (PR 64): a 2048-row rung at ``valid`` 600 (three of eight segments run)
    and 2048 (all eight) against the same program with the plain products
    on the same weights, and the engine's account of the rows
    (``cfg["rows"]``: run, skipped).  ``DENSE_SHORT`` (PR 65): a 1024-row
    rung at 593 (three of four) and 1024."""
    import functools

    from paddle_tpu.models.llama import build_llama_prefill
    from paddle_tpu.serving import GenerationEngine

    model = dict(vocab_size=cfg["vocab"], hidden=cfg["hidden"],
                 num_layers=2, num_heads=cfg["heads"],
                 num_kv_heads=cfg["kv_heads"], intermediate=cfg["ffn"])
    kw = dict(num_slots=2, max_seq_len=cfg["rung"] + 64,
              prefill_buckets=[cfg["rung"]], page_tokens=16,
              prefill_chunk=0, prefix_reuse=False, speculate=False,
              keep_logits=True, eos_id=-1,
              # (the float32 table's rungs and segments: ``cfg["rows"]``)
              dtype="float32")
    gen = GenerationEngine(model, **kw)
    plain = GenerationEngine(model, scope=gen.scope.new_scope(), **kw)
    plain._build_fn_prefill = functools.partial(build_llama_prefill,
                                                stop_at_prompt=False)
    try:
        for eng in (gen, plain):
            eng.warmup()
        ops = [[op.type for op in eng._prefill_prog_for(
            cfg["rung"])[0].global_block().ops] for eng in (gen, plain)]
        check("mul_valid_rows" in ops[0] and "mul_valid_rows" not in ops[1],
              "the rung's program stops at the prompt, the other does not")
        rng = np.random.default_rng(64)
        worst, took = 0.0, {}
        for n_prompt in cfg["prompts"]:
            prompt = rng.integers(1, cfg["vocab"], n_prompt).tolist()
            both = []
            for tag, eng in (("stops", gen), ("plain", plain)):
                t0 = time.perf_counter()
                both.append(eng.generate(prompt, cfg["steps"] + 1,
                                         timeout=600))
                took[tag, n_prompt] = time.perf_counter() - t0
            got, want = (np.stack(r["logits"]) for r in both)
            rel = float(np.abs(got - want).max() / np.abs(want).max())
            worst = max(worst, rel)
            check(np.isfinite(got).all() and rel <= TOL
                  and both[0]["tokens"] == both[1]["tokens"],
                  f"a prompt of {n_prompt} on rung {cfg['rung']} off the "
                  f"plain products by {rel:.4g}")
        counters = gen.stats()["counters"]
        run, skipped = (counters["prefill_rows_run"],
                        counters["prefill_rows_skipped"])
        check((run, skipped) == cfg["rows"],
              f"rows counted: {run} run, {skipped} skipped")
    finally:
        gen.close()
        plain.close()
    say(f"dense rows: rung {cfg['rung']} at prompts {cfg['prompts']} within "
        f"{worst:.4g} of the plain products; prefill_rows_run {run}, "
        f"prefill_rows_skipped {skipped}; a request's wall time, stops | "
        "plain: " + ", ".join(
            f"{n}: {took['stops', n]:.3f} | {took['plain', n]:.3f} s"
            for n in cfg["prompts"]))


# (PR 68) Mistral-7B's published widths at a toy depth, one rung, and the
# limit its cells hold a bfloat16 program to
DTYPE = dict(config="mistral-7b-v0.1", layers=2, rung=1024, prompts=(48, 700),
             steps=8)


def dtype_phase(cfg=DTYPE):
    """What the engine's rule serves a dense decoder in (PR 68): the toy-
    depth Mistral at published widths as the rule builds it (bfloat16) and
    with float32 stated, on the same (rounded) weights: the counters of the
    programs built, the attention kernels both took (no reference
    formulation), the classes of arrays the benchmark would observe, and
    the bfloat16 program's reading through the cell's comparison (its
    logits at a prefill and eight cached steps against the plain float32
    reference on the same weights, as a share of the reference's range)
    beside the two stand-ins' (``benchmark/tests/standins.py``: ``stated``,
    ``throughout``) and the float32 program's, at the file's limits."""
    import jax.numpy as jnp

    from paddle_tpu.serving import GenerationEngine

    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "benchmark")
    sys.path[:0] = [p for p in (bench, os.path.join(bench, "tests"))
                    if p not in sys.path]
    import harness
    import standins

    whole = harness.load_json("configs", cfg["config"] + ".json")
    conf = dict(whole, num_hidden_layers=cfg["layers"])
    ref = harness.load_module("reference", cfg["config"])
    model = harness.load_module("builders", whole["builder"]).model_args(conf)
    kw = dict(num_slots=2, max_seq_len=cfg["rung"] + 64,
              prefill_buckets=[cfg["rung"]], page_tokens=16, prefill_chunk=0,
              prefix_reuse=False, speculate=False, keep_logits=True,
              eos_id=-1, seed=0)
    paths0 = attention_paths()
    low = GenerationEngine(model, **kw)
    f32 = GenerationEngine(model, dtype="float32", **kw)
    try:
        for eng in (low, f32):
            eng.warmup()
        built = {eng.dtype: {k: v for k, v in eng.stats()[
            "counters"].items() if k.startswith("programs_built_")}
            for eng in (low, f32)}
        check(low.dtype == "bfloat16" and built == {
            "bfloat16": {"programs_built_bfloat16": 2,
                         "programs_built_float32": 0},
            "float32": {"programs_built_bfloat16": 0,
                        "programs_built_float32": 2}},
            f"the rule builds bfloat16, float32 where stated: {built}")
        paths = paths_since(paths0)
        check(paths.get("pallas", 0) >= 2 * cfg["layers"]
              and paths.get("paged_decode", 0) >= 2 * cfg["layers"]
              and not any("reference" in k or "blockwise" in k
                          for k in paths),
              f"both dtypes took the kernels, no reference: {paths}")
        seen = harness.observe_engine(low, conf)
        entry, problems = harness.held_to(whole, seen)
        check(seen == {"weights": "bfloat16", "pages": "bfloat16",
                       "state": None, "kept_not_float32": []}
              and not problems
              and entry is whole["check_tolerance"]["bfloat16"],
              f"the benchmark would observe {seen}")
        # the same (rounded) weights under the float32 program
        for n in low._weight_names():
            f32.scope.set_var(n, jnp.asarray(low.scope.find_var(n),
                                             jnp.float32))
        params = ref.params_from_scope(f32.scope, conf)
        limit, limit32 = entry["share_of_range"], \
            whole["check_tolerance"]["share_of_range"]
        steps, read = cfg["steps"], {}
        rng = np.random.default_rng(68)
        for n in cfg["prompts"]:
            prompt = rng.integers(1, conf["vocab_size"], n).tolist()
            res = low.generate(prompt, steps + 1, timeout=600)
            ids = np.zeros((cfg["rung"] + 64,), "int32")
            seq = prompt + res["tokens"][:-1]
            ids[:len(seq)] = seq
            rows = np.arange(n - 1, n + steps)
            want = np.asarray(ref.forward(params, ids, conf, rows))
            span = float(want.max() - want.min())

            def rel(got):
                return float(np.abs(np.asarray(got, "float32")
                                    - want).max() / span)

            read["program", n] = rel(np.stack(res["logits"]))
            # (teacher-forced on the same tokens: the float32 program's
            # own answer where its tokens are the bfloat16 program's)
            res32 = f32.generate(prompt, steps + 1, timeout=600)
            if res32["tokens"] == res["tokens"]:
                read["float32 program", n] = rel(np.stack(res32["logits"]))
            for kind in ("stated", "throughout"):
                read[kind, n] = rel(standins.lowered(ref, conf, kind)(
                    params, ids, rows)[0])
            check(np.isfinite(read["program", n])
                  and read["program", n] <= limit,
                  f"prompt {n}: the bfloat16 program lies "
                  f"{read['program', n]:.4g} of the range off the float32 "
                  f"reference (limit {limit:.4g})")
            check(read.get(("float32 program", n), 0.0) <= limit32,
                  f"prompt {n}: the float32 program within its own limit "
                  f"{limit32:.4g}")
    finally:
        low.close()
        f32.close()
    say("serving dtype: " + "; ".join(
        f"{what} {n}: {v:.4g}" for (what, n), v in sorted(read.items()))
        + f" (limits: bfloat16 {limit:.4g}, float32 {limit32:.4g}); "
        f"programs built {built}; attention {paths}")


def store_child(cfg=STORE):
    """One of ``store_phase``'s two processes: the decoder's prefill rung
    and decode program warm, one prompt answered, and one line of JSON:
    the device, the stats, a digest of the logits' bits."""
    import jax

    from paddle_tpu.monitor import stat_get
    from paddle_tpu.serving import GenerationEngine

    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        return 2
    # (jax keeps only programs that took a second to compile; here every
    # one is kept, so that the second process finds all of them)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    model = dict(vocab_size=cfg["vocab"], hidden=cfg["hidden"],
                 num_layers=cfg["layers"], num_heads=cfg["heads"],
                 num_kv_heads=cfg["kv_heads"], intermediate=cfg["ffn"])
    gen = GenerationEngine(
        model, num_slots=cfg["slots"], max_seq_len=cfg["max_seq"],
        prefill_buckets=[cfg["bucket"]], page_tokens=16, prefill_chunk=0,
        prefix_reuse=False, speculate=False, keep_logits=True, seed=0,
        eos_id=-1)
    try:
        programs = gen.warmup()
        prompt = np.random.default_rng(60).integers(
            1, cfg["vocab"], cfg["prompt"]).tolist()
        res = gen.generate(prompt, cfg["new_tokens"], timeout=600)
    finally:
        gen.close()
    logits = np.stack(res["logits"])
    print("STORE " + json.dumps({
        "platform": d0.platform, "programs": programs,
        "tokens": res["tokens"], "finite": bool(np.isfinite(logits).all()),
        "logits_sha256": hashlib.sha256(logits.tobytes()).hexdigest(),
        "stats": {k: stat_get(k) for k in STORE_STATS}}), flush=True)
    return 0


def store_phase():
    """The program store across two processes on the chip (PR 60; alone:
    ``python -c "import chip_smoke; chip_smoke.store_phase()"``).  The
    caller must not have touched the chip: a chip belongs to one process.
    Returns 2 where the children find no TPU."""
    runs = []
    for turn in ("first", "second"):
        t0 = time.perf_counter()
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--store-child"],
            capture_output=True, text=True, timeout=900)
        if done.returncode == 2:
            return 2
        lines = [ln for ln in done.stdout.splitlines()
                 if ln.startswith("STORE ")]
        check(done.returncode == 0 and len(lines) == 1,
              f"the {turn} store process exited {done.returncode}: "
              f"{done.stderr[-2000:]}")
        run = json.loads(lines[0][len("STORE "):])
        runs.append(run)
        say(f"store: {turn} process [{time.perf_counter() - t0:.1f} s] "
            f"{run['programs']} programs, " + ", ".join(
                f"{k} {v}" for k, v in run["stats"].items()))
    first, second = (r["stats"] for r in runs)
    check(first["attention_lowered_paged_decode"] > 0
          and first["attention_lowered_pallas"] > 0
          and first["attention_lowered_paged_decode_reference"] == 0,
          "the programs hold no Pallas kernel")
    check(first["program_store_refused"] == 0
          and second["program_store_refused"] == 0,
          "the store refused a program")
    check(second["program_store_hits"] > 0
          and second["program_store_misses"] == 0,
          f"the second process loaded {second['program_store_hits']} "
          f"modules and made {second['program_store_misses']}")
    check(second["program_store_hits"] == first["program_store_hits"]
          + first["program_store_misses"],
          "the two processes asked the store for different programs")
    check(second["compile_cache_misses"] == 0,
          f"the second process compiled {second['compile_cache_misses']} "
          f"programs anew")
    lowered = [k for k in STORE_STATS if "lowered" in k or "kv_pool" in k]
    check([first[k] for k in lowered] == [second[k] for k in lowered],
          "a hit did not book what the trace booked")
    check(runs[0]["finite"] and runs[0]["tokens"] == runs[1]["tokens"]
          and runs[0]["logits_sha256"] == runs[1]["logits_sha256"],
          "the loaded modules' logits are not the traced ones' to the bit")
    say(f"store: the second process loaded {second['program_store_hits']} "
        f"modules, compiled nothing anew and returned the first's "
        f"{STORE['new_tokens']} rows of logits to the bit")
    return 0


def main():
    if sys.argv[1:] == ["--store-child"]:
        return store_child()
    t_start = time.perf_counter()
    # the program first: in a directory that holds only this file the
    # script must fail before it prints anything
    import paddle_tpu  # noqa: F401

    # (two processes of their own, so before this one claims the chip)
    store_rc = store_phase()
    t_store = time.perf_counter() - t_start

    import jax
    from paddle_tpu.compile_cache import ensure_compile_cache
    from paddle_tpu.monitor import stat_get

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu" or store_rc:
        print(f"chip_smoke: refusing to run: jax.devices()[0].platform is "
              f"{d0.platform!r}, not 'tpu'.  This script proves the main "
              f"path on the chip; on the CPU run the tests.",
              file=sys.stderr)
        return 2
    import jaxlib

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not importable"
    say(f"device: platform {d0.platform}, kind {d0.device_kind}, count "
        f"{len(devices)}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {libtpu_version}")

    say(f"program store done [{t_store:.1f} s]")
    say(f"compile cache: {ensure_compile_cache()}")

    t0 = time.perf_counter()
    train = train_phase()
    say(f"train phase done [{time.perf_counter() - t0:.1f} s]")
    gc.collect()  # the trainer's state leaves HBM before the server loads

    t0 = time.perf_counter()
    serve = serve_phase()
    say(f"serve phase done [{time.perf_counter() - t0:.1f} s]")
    say_startup_account()
    gc.collect()

    t0 = time.perf_counter()
    sparse_window_phase()
    say(f"sparse and windowed kernels done "
        f"[{time.perf_counter() - t0:.1f} s]")
    gc.collect()

    t0 = time.perf_counter()
    slot_state_phase()
    say(f"slot state and head-64 kernel done "
        f"[{time.perf_counter() - t0:.1f} s]")
    gc.collect()

    t0 = time.perf_counter()
    delta_state_phase()
    say(f"delta-rule kernels and state done "
        f"[{time.perf_counter() - t0:.1f} s]")
    gc.collect()

    t0 = time.perf_counter()
    share_and_channel_phase()
    say(f"held experts and per-channel delta kernels done "
        f"[{time.perf_counter() - t0:.1f} s]")
    gc.collect()

    t0 = time.perf_counter()
    latent_phase()
    say(f"latent attention kernels and pages done "
        f"[{time.perf_counter() - t0:.1f} s]")
    gc.collect()

    t0 = time.perf_counter()
    chunk_phase()
    say(f"chunk attention kernel and chunks over two page kinds done "
        f"[{time.perf_counter() - t0:.1f} s]")
    gc.collect()

    t0 = time.perf_counter()
    latent_chunk_phase()
    say(f"latent chunk kernel and chunks over latent pages done "
        f"[{time.perf_counter() - t0:.1f} s]")
    gc.collect()

    t0 = time.perf_counter()
    ssd_phase()
    say(f"state-space kernels and state done "
        f"[{time.perf_counter() - t0:.1f} s]")

    t0 = time.perf_counter()
    single_sublayer_phase()
    say(f"grouped state-space kernels, latent experts of two matrices and "
        f"single-sublayer layers done [{time.perf_counter() - t0:.1f} s]")

    t0 = time.perf_counter()
    shortcut_phase()
    say(f"shortcut-connected layer and identity experts done "
        f"[{time.perf_counter() - t0:.1f} s]")
    gc.collect()

    t0 = time.perf_counter()
    dense_rows_phase()
    dense_rows_phase(DENSE_SHORT)
    say(f"dense products that stop at the prompt's end done "
        f"[{time.perf_counter() - t0:.1f} s]")
    gc.collect()

    t0 = time.perf_counter()
    dtype_phase()
    say(f"the dense decoder in bfloat16 beside float32 done "
        f"[{time.perf_counter() - t0:.1f} s]")

    say(f"set-up (compile-dominated: kernel check + first train step + "
        f"serving warm-up) {train['setup_s'] + serve['setup_s']:.1f} s, "
        f"compile_cache_hits {stat_get('compile_cache_hits')}, total "
        f"{time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
