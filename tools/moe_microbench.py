#!/usr/bin/env python
"""Which formulation of the grouped expert matmul, on the chip.

    chiprun -- python tools/moe_microbench.py [--only NAME] [--tiles TM,TN;...]
    chiprun -- python tools/moe_microbench.py --held 1 [--only NAME] [--runs R,...]
    chiprun -- python tools/moe_microbench.py --valid 1 [--only NAME]
    chiprun -- python tools/moe_microbench.py --pieces 1 [--only NAME] [--rows N,...]

Times the two grouped matmuls of one expert FFN (gate + up, then down;
float32 "highest") over rows sorted by expert, at the shapes the
benchmark's three routed configurations run (``SHAPES``: rows a group at
each prefill rung, and the decode step's or block pass's rows), with the
groups even and with the routing's skew (the largest group 2.5 times the
mean):

* ``parent`` -- what ``grouped_matmul`` ran before the Pallas kernel: one
  ``jax.lax.ragged_dot`` call, or runs of 192 sorted rows where
  ``192 < M < 512 x groups`` (PR 32);
* ``ragged_dot`` -- the one call, at "highest" and (at the shapes PR 28
  measured) at default precision;
* ``megablox`` -- the Pallas grouped matmul that ships with JAX
  (``jax.experimental.pallas.ops.tpu.megablox.gmm``), tiles (128, 128,
  128) and (512, 512, 512), at those shapes too; it feeds the MXU at
  default precision only;
* ``route`` -- ``grouped_matmul`` itself, whatever it chooses: the Pallas
  kernel ``ops/pallas/grouped_matmul.py`` at the blocks its ``tiles`` gives
  the shape, or the one ``ragged_dot`` call;
* ``kernel`` -- the kernel at every TM,TN pair of ``--tiles`` (the sweep
  those blocks were chosen from).

``--held 1`` times instead the whole held share of a layer
(``parallel/moe.py`` ``_held_share``: the sort, and a trip's gather, two
products, gate and scatter-add) at the three configurations that serve one
chip's share of an expert-parallel group (``HELD``), the held pairs drawn
at the cell's share of all pairs and its routing's skew over the router's
whole width: ``parent`` (PR 43's loop: runs of 192 through
``jax.lax.ragged_dot``, kept here as ``parent_held_share``), the loop with
both products on the route of ``grouped_matmul`` at each run length of
``--runs``, and ``rule``, the run ``held_run`` gives the shape.  Writes
``chiprun_out/moe_held_sweep.json``.

``--valid 1`` times one whole routed layer (``moe_routed_tokens``: router,
sort, gather, both products, gate, scatter back) at a rung whose tail lies
behind the prompt's end (``VALID``: rows, real rows), three ways: ``all
rows`` (``valid=None``: every pair is multiplied, as before PR 54), ``valid:
every row`` (the mechanism with nothing to leave out) and ``valid: the
prompt's rows`` (the tail's pairs sort past the last group).  Real rows are
standard normal and routed with the cell's skew; the tail holds ONE drawn
row repeated, as a rung's tail holds one token id, so all its pairs go to
the same ``top_k`` experts.  Writes ``chiprun_out/moe_valid_sweep.json``.

``--pieces 1`` times one routed layer's pieces apart (``PIECES``: the
three configurations at a step's or pass's rows and at each rung of the
mix, every row real): route and top-k; argsort and bincount; the row gather
into sorted order; product 1; the gate; product 2; the routing weight; the
un-sort; the k-sum, as the layer was built until PR 56 (``routed_layer``
with nothing fused is that formulation, kept here), and beside them what PR
57 put in their place: the row gather with its indices said to be in bounds,
product 1 with the gate as its epilogue, product 2 with the routing weight
as its epilogue, the inverse of the sort and the gather-sum (``gather_sum``:
the planes the layer takes, and the two forms it was chosen over).  Each
piece has its bytes' floor at 819 GB/s beside it (a product: the larger of
that and the six-pass MXU floor); a piece alone costs a dispatch, about
0.25 ms here, which the whole layer pays once.  Then the whole layer:
``routed_layer`` at each step from the parent's formulation to the new, and
``moe_routed_tokens`` as the tree has it.  Writes
``chiprun_out/moe_pieces.json``.

Writes ``chiprun_out/moe_formulation_sweep.json`` and prints one line per
formulation: milliseconds for the pair of matmuls, against the six-pass
MXU floor and the touched experts' bytes over 819 GB/s, and the error
against a float64 loop over 64 rows spread over the call.  Refuses to run
without a TPU backend.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# name: (groups, K, width I, rows a group at the prefill rungs, the rows
# of the decode step / block pass: the cell's, and one row block's where
# the cell's is not the fewest the kernel takes)
SHAPES = {
    "smallthinker-21b-a3b": (64, 2560, 768, (48, 96, 192, 384, 768),
                             (192, 64)),
    "lfm2-24b-a2b": (64, 2048, 1536, (8, 16, 32, 64, 128, 256), (256, 64)),
    "sdar-30b-a3b-chat": (128, 2048, 768, (8, 16, 32, 64), (1536,)),
}
SKEW = 2.5                      # moe_expert_load_max_over_mean.pool
# name: (router's experts, held, top k, K, width I, gate, the cell's
# moe_pairs_held_pct.* and moe_expert_load_max_over_mean.pool (ledger, PR
# 51), the decode step's slots, the prefill rungs' or the chunk's rows)
HELD = {
    "solar-open2-250b": (320, 20, 8, 4096, 1280, 6.24, 5.3, 64,
                         (256, 512, 1024, 2048, 4096)),
    "gigachat35-432b-a28b": (256, 8, 8, 7168, 2048, 2.97, 5.9, 32,
                             (256, 512, 1024, 2048)),
    "command-a-plus-05-2026": (128, 8, 8, 4096, 4096, 6.03, 5.5, 10,
                               (1024,)),
}
HELD_RUNS = (64, 128, 192, 512, 1024, 2048, 4096)
# name: (top k, the gate, (rows of the program, real rows) ...): a rung of
# the cell's ladder under a prompt of the mix's mean share of it, and the
# decode step or block pass with every slot live
VALID = {
    "smallthinker-21b-a3b": (6, "relu", ((4096, 2900), (8192, 5800),
                                         (512, 360), (32, 32))),
    "lfm2-24b-a2b": (4, "silu", ((1024, 700), (64, 64))),
    "sdar-30b-a3b-chat": (8, "silu", ((512, 350), (192, 192))),
}
# name: (top k, the gate, the step's or pass's rows, the mix's rungs)
PIECES = {
    "smallthinker-21b-a3b": (6, "relu", 32, (512, 1024, 2048, 4096, 8192)),
    "lfm2-24b-a2b": (4, "silu", 64, (128, 256, 512, 1024, 2048, 4096)),
    "sdar-30b-a3b-chat": (8, "silu", 192, (128, 256, 512, 1024)),
}
PEAK, HBM = 197e12, 819e9
RUN_ROWS, WIDE_TILE = 192, 512  # the parent's runs (PR 32)


def timed(fn, *args, reps=20):
    out = fn(*args)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e3, out


def group_sizes(rng, m, groups, skew):
    """``m`` rows over ``groups``: even, or drawn from loads whose largest
    is ``skew`` times their mean."""
    if not skew:
        sizes = np.full(groups, m // groups)
        sizes[:m - sizes.sum()] += 1
        return sizes.astype(np.int32)
    return rng.multinomial(m, skewed_loads(rng, groups, skew)).astype(
        np.int32)


def skewed_loads(rng, groups, skew):
    """Shares of ``groups`` whose largest is ``skew`` times their mean."""
    z = rng.standard_normal(groups)
    lo, hi = 0.0, 4.0
    for _ in range(50):
        s = (lo + hi) / 2
        p = np.exp(s * z)
        lo, hi = (s, hi) if p.max() / p.mean() < skew else (lo, s)
    return p / p.sum()


def parent_runs(rows, weights, sizes, precision, run=RUN_ROWS):
    """PR 32's ``_in_runs``, kept here as the line the kernel is timed
    against: runs of ``run`` sorted rows, a ``ragged_dot`` call a run."""
    import jax
    import jax.numpy as jnp

    m = rows.shape[0]
    n = -(-m // run)
    rows = jnp.pad(rows, ((0, n * run - m), (0, 0))).reshape(n, run, -1)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    lo = jnp.arange(n, dtype=ends.dtype)[:, None] * run
    cut = jnp.clip(jnp.minimum(ends, lo + run) - jnp.maximum(starts, lo),
                   0, None).astype(sizes.dtype)
    out = jax.lax.map(lambda a: jax.lax.ragged_dot(
        a[0], weights, a[1], precision=precision,
        preferred_element_type=a[0].dtype), (rows, cut))
    return out.reshape(n * run, -1)[:m]


def parent_held_share(x, local, weights, w_gate_up, w_down, precision):
    """``_held_share`` as PR 43 to PR 51 had it (SiLU, no clamp): runs of
    192 sorted pairs, each trip two ``jax.lax.ragged_dot`` calls."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import ROW_TILE, _gated, _one_call

    N, H = x.shape
    top_k = local.shape[1]
    held, inter = w_gate_up.shape[0], w_down.shape[1]
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts, n_held = ends - sizes, ends[-1]
    run = min(RUN_ROWS, -(-N * top_k // ROW_TILE) * ROW_TILE)
    order = jnp.pad(order, (0, -order.shape[0] % run))
    pair_w = weights.reshape(-1)

    def body(i, out):
        lo = i * run
        pairs = jax.lax.dynamic_slice_in_dim(order, lo, run)
        real = lo + jnp.arange(run) < n_held
        tok = pairs // top_k
        rows = jnp.take(x, tok, axis=0)
        size = jnp.clip(jnp.minimum(ends, lo + run) - jnp.maximum(starts, lo),
                        0, None).astype(jnp.int32)
        h = _one_call(rows, w_gate_up, size, precision)
        y = _one_call(_gated(h, inter, "silu"), w_down, size, precision)
        y = y * jnp.take(pair_w, pairs)[:, None].astype(y.dtype)
        return out.at[tok].add(jnp.where(real[:, None], y, 0))

    return jax.lax.fori_loop(0, -(-n_held // run), body,
                             jnp.zeros((N, H), x.dtype))


def held_pairs(rng, n, experts, held, top_k, pct, skew):
    """``local`` [n, top_k] as ``moe_routed_tokens`` hands it to
    ``_held_share``: each row draws ``top_k`` distinct experts of the
    router's ``experts`` from loads whose largest is ``skew`` times their
    mean, and the ``held`` consecutive experts whose load is nearest
    ``pct`` percent of all are the ones held (index ``held``: absent)."""
    p = skewed_loads(rng, experts, skew)
    chosen = np.argsort(-(np.log(p) + rng.gumbel(size=(n, experts))),
                        axis=-1)[:, :top_k]
    load = np.bincount(chosen.reshape(-1), minlength=experts) / chosen.size
    window = np.convolve(load, np.ones(held), "valid")
    first = int(np.abs(window - pct / 100).argmin())
    local = np.where((chosen >= first) & (chosen < first + held),
                     chosen - first, held)
    return local.astype(np.int32)


def held_main(args) -> int:
    """The ``--held 1`` table (the module docstring)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    highest = jax.lax.Precision.HIGHEST
    runs = tuple(int(r) for r in args.runs.split(",") if r) or HELD_RUNS
    results = []
    for name, (E, held, top_k, K, I, pct, skew, slots, rungs) in HELD.items():
        if args.only and name != args.only:
            continue
        key = jax.random.key(E + K)
        gu = jax.random.normal(jax.random.fold_in(key, 1),
                               (held, K, 2 * I)) * .02
        dn = jax.random.normal(jax.random.fold_in(key, 2),
                               (held, I, K)) * .02
        gu64 = dn64 = None
        for n, label in [(slots, "step")] + [(r, f"rung {r}") for r in rungs]:
            rng = np.random.default_rng(n + E)
            local_np = held_pairs(rng, n, E, held, top_k, pct, skew)
            local = jnp.asarray(local_np)
            x = jax.random.normal(jax.random.fold_in(key, n), (n, K))
            w = jax.random.uniform(jax.random.fold_in(key, n + 1),
                                   (n, top_k)) / top_k
            sizes = np.bincount(local_np.reshape(-1),
                                minlength=held + 1)[:held]
            n_held, touched = int(sizes.sum()), int((sizes > 0).sum())
            pairs = n * top_k
            rule = moe.held_run(pairs, held, E, True)
            whole = -(-pairs // 64) * 64
            cases = [("parent", None)] + [
                (f"run {r}", r) for r in runs if r <= whole] + [
                (f"rule: run {rule}", rule)]
            # float64 loop on the host over up to 8 rows that hold a pair
            at = np.unique(np.nonzero((local_np < held).any(-1))[0][
                :: max(1, n_held // 8)])[:8]
            if gu64 is None:
                gu64, dn64 = (np.asarray(a, np.float64) for a in (gu, dn))
            x64, w64 = np.asarray(x, np.float64), np.asarray(w, np.float64)
            want = np.zeros((len(at), K))
            for j, t in enumerate(at):
                for slot in np.nonzero(local_np[t] < held)[0]:
                    g = local_np[t, slot]
                    h = x64[t] @ gu64[g]
                    a = h[:I] / (1 + np.exp(-h[:I])) * h[I:]
                    want[j] += w64[t, slot] * (a @ dn64[g])
            flops = 2.0 * n_held * 3 * K * I
            floor_mxu = 6 * flops / PEAK * 1e3
            floor_hbm = touched * 3 * K * I * 4 / HBM * 1e3
            print(f"{name} {label} ({n} rows, {pairs} pairs, {n_held} held "
                  f"= {100 * n_held / pairs:.2f}%, {touched} of {held} "
                  f"touched, largest group {sizes.max()}): six-pass MXU "
                  f"floor {floor_mxu:.2f} ms, weights' bytes "
                  f"{floor_hbm:.2f} ms", flush=True)
            for case, run in cases:
                rec = {"shape": name, "rows": n, "label": label,
                       "pairs_held": n_held, "formulation": case}
                if run is None:
                    f = jax.jit(lambda x, l, w, gu, dn: parent_held_share(
                        x, l, w, gu, dn, highest))
                else:
                    f = jax.jit(lambda x, l, w, gu, dn, run=run:
                                moe._held_share(x, l, w, gu, dn, "silu",
                                                highest, experts=E, run=run))
                try:
                    ms, out = timed(f, x, local, w, gu, dn)
                    peak = f.lower(x, local, w, gu, dn).compile() \
                        .memory_analysis().temp_size_in_bytes
                except Exception as e:  # noqa: BLE001 — as in main
                    print(f"    {case:20s} refused: {str(e)[:200]}",
                          flush=True)
                    results.append(dict(rec, refused=str(e)[:400]))
                    continue
                err = float(np.abs(np.asarray(out[at]) - want).max()
                            / np.abs(want).max()) if len(at) else 0.0
                print(f"    {case:20s} {ms:9.3f} ms  x"
                      f"{ms / max(floor_mxu, floor_hbm, 1e-9):.2f} the larger "
                      f"floor, temporaries {peak / 2**20:.0f} MiB, off the "
                      f"float64 loop by {err:.3g}", flush=True)
                results.append(dict(
                    rec, ms=ms, run=run, experts_touched=touched,
                    largest_group=int(sizes.max()), mxu_floor_ms=floor_mxu,
                    bytes_floor_ms=floor_hbm, temp_bytes=int(peak),
                    rel_err=err))
        del gu, dn
    os.makedirs("chiprun_out", exist_ok=True)
    out = "chiprun_out/moe_held_sweep.json"
    if args.only:
        out = out.replace(".json", f"_{args.only}.json")
    with open(out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "results": results}, f, indent=1)
    return 0


def valid_main(args) -> int:
    """The ``--valid`` table (the module docstring)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    highest = jax.lax.Precision.HIGHEST
    results = []
    for name, (top_k, gate, rungs) in VALID.items():
        if args.only and name != args.only:
            continue
        groups, K, I = SHAPES[name][:3]
        key = jax.random.key(groups + K)
        gu = jax.random.normal(jax.random.fold_in(key, 1),
                               (groups, K, 2 * I)) * .02
        dn = jax.random.normal(jax.random.fold_in(key, 2),
                               (groups, I, K)) * .02
        for n, real in rungs:
            rng = np.random.default_rng(n + groups)
            # the router reads rows of its own: their last column is 1 and
            # the router's last row the log of the skewed loads
            router = rng.standard_normal((K, groups)).astype(np.float32) * .02
            router[-1] = np.log(skewed_loads(rng, groups, SKEW))
            x, rx = (rng.standard_normal((n, K)).astype(np.float32)
                     for _ in range(2))
            x[real:], rx[real:] = x[real:real + 1], rx[real:real + 1]
            rx[:, -1] = 1.0
            live = np.arange(n) < real
            cases = [("all rows (valid=None)", None),
                     ("valid: every row", np.ones(n, bool)),
                     (f"valid: the prompt's {real} rows", live)]
            f = jax.jit(lambda x, rx, r, gu, dn, v: moe.moe_routed_tokens(
                x, rx, r, gu, dn, top_k=top_k, activation=gate, valid=v,
                precision=highest))
            outs, took = [], []
            for case, valid in cases:
                operands = (jnp.asarray(x), jnp.asarray(rx),
                            jnp.asarray(router), gu, dn,
                            None if valid is None else jnp.asarray(valid))
                ms, out = timed(lambda *a: f(*a)[0], *operands)
                counts = np.asarray(f(*operands)[1])
                outs.append(np.asarray(out))
                took.append(ms)
                results.append({
                    "shape": name, "rows": n, "real_rows": real,
                    "formulation": case, "ms": ms,
                    "pairs_multiplied": int(counts.sum()),
                    "experts_touched": int((counts > 0).sum()),
                    "largest_group": int(counts.max())})
            every, _, cut = outs
            off = float(np.abs(cut[:real] - every[:real]).max()
                        / np.abs(every[:real]).max())
            pad = n - real
            print(f"{name} rung {n}, {real} real rows, {pad} behind them "
                  f"(one row repeated; top {top_k} of {groups}): all rows "
                  f"{took[0]:.3f} ms, valid every row {took[1]:.3f}, valid "
                  f"the prompt's rows {took[2]:.3f}"
                  + (f": {(took[0] - took[2]) / pad * 1e3:.2f} us a pad row"
                     if pad else "")
                  + f"; real rows off the all-rows layer by {off:.3g}, pad "
                  f"rows' out all 0: {not cut[real:].any()}", flush=True)
            results[-1].update(real_rows_rel_diff=off,
                               pad_rows_zero=not cut[real:].any())
        del gu, dn
    os.makedirs("chiprun_out", exist_ok=True)
    out = "chiprun_out/moe_valid_sweep.json"
    if args.only:
        out = out.replace(".json", f"_{args.only}.json")
    with open(out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "pad_rows": "one drawn row repeated", "skew": SKEW,
                   "results": results}, f, indent=1)
    return 0


def inverse_of(order, top_k=None):
    """Where each pair went: ``inverse[order[r]] == r``; with ``top_k`` in
    plane order, ``inverse[j N + n]`` the row of pair (n, j)."""
    import jax.numpy as jnp

    at = order
    if top_k:
        at = (order % top_k) * (order.shape[0] // top_k) + order // top_k
    return jnp.zeros(order.shape, jnp.int32).at[at].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))


def gather_sum(y, inverse, top_k, how="planes"):
    """``out[n] = sum_j y[row of pair (n, j)]``, j in order.  ``planes``
    (what ``moe._combine`` does): ``inverse`` in plane order, one gather
    writes [k, N, H] and the k-sum runs over the LEADING axis, whole rows
    added to whole rows.  ``one``: ``inverse`` in pair order, one gather of
    [N k, H] and the k-sum over the middle axis of [N, k, H] (what PR 57
    first built: the parent's bits, and its slow reduce).  ``each``: k
    gathers of [N, H] added up.  The indices are in bounds, and said to be
    (``mode="clip"``): ``jnp.take``'s default fills what an index out of
    bounds would read, a select over the whole result."""
    import jax.numpy as jnp

    n = inverse.shape[0] // top_k
    if how == "planes":
        return jnp.take(y, inverse, axis=0, mode="clip").reshape(
            top_k, n, -1).sum(axis=0)
    if how == "one":
        return jnp.take(y, inverse, axis=0, mode="clip").reshape(
            n, top_k, -1).sum(axis=1)
    at = inverse.reshape(n, top_k)
    out = jnp.take(y, at[:, 0], axis=0, mode="clip")
    for j in range(1, top_k):
        out = out + jnp.take(y, at[:, j], axis=0, mode="clip")
    return out


def routed_layer(x, rx, router, gu, dn, top_k, gate, fuse_gate=False,
                 fuse_scale=False, combine="scatter"):
    """``moe_routed_tokens`` (every row real, softmax router) built from
    its pieces.  With nothing fused and ``combine`` "scatter" it is the
    formulation of PR 28 to PR 56, kept here as the line PR 57 is timed
    against: the rows gathered with ``jnp.take``'s default fill, ``h`` [N k,
    2I] written and gated by XLA, ``y`` scaled by a pass of its own,
    scattered into zeros and summed."""
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import grouped_matmul as kernel
    from paddle_tpu.parallel import moe

    highest = jax.lax.Precision.HIGHEST
    n, inter = x.shape[0], dn.shape[1]
    _, experts, weights = moe.route_top_k(rx, router, top_k)
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=router.shape[1]).astype(jnp.int32)
    rows = jnp.take(x, order // top_k, axis=0,
                    mode="fill" if combine == "scatter" else "clip")
    scale = jnp.take(weights.reshape(-1), order)
    t1 = kernel.tiles(rows.shape[0], rows.shape[1], gu.shape[2])
    if fuse_gate and t1 and kernel.gate_fits(gu.shape[2], t1[1]):
        act = kernel.grouped_matmul_epilogue(
            rows, gu, sizes, tm=t1[0], tn=t1[1], gate=functools.partial(
                moe._gated, inter=inter, activation=gate))
    else:
        act = moe._gated(moe.grouped_matmul(rows, gu, sizes, highest),
                         inter, gate)
    t2 = kernel.tiles(act.shape[0], act.shape[1], dn.shape[2])
    if fuse_scale and t2:
        y = kernel.grouped_matmul_epilogue(act, dn, sizes, scale, tm=t2[0],
                                           tn=t2[1])
    else:
        y = moe.grouped_matmul(act, dn, sizes, highest) * scale[:, None]
    if combine == "scatter":
        return jnp.zeros_like(y).at[order].set(y, mode="drop").reshape(
            n, top_k, -1).sum(axis=1)
    return gather_sum(
        y, inverse_of(order, top_k if combine == "planes" else None), top_k,
        combine)


def pieces_main(args) -> int:
    """The ``--pieces`` table (the module docstring)."""
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import grouped_matmul as kernel
    from paddle_tpu.parallel import moe

    highest = jax.lax.Precision.HIGHEST
    only_rows = {int(r) for r in args.rows.split(",") if r}
    os.makedirs("chiprun_out", exist_ok=True)
    out_path = "chiprun_out/moe_pieces.json"
    if args.only or only_rows:
        out_path = out_path.replace(
            ".json", f"_{args.only or 'all'}" + (
                f"_{'-'.join(map(str, sorted(only_rows)))}"
                if only_rows else "") + ".json")
    results = []
    for name, (top_k, gate, step, rungs) in PIECES.items():
        if args.only and name != args.only:
            continue
        groups, K, I = SHAPES[name][:3]
        key = jax.random.key(groups + K)
        gu = jax.random.normal(jax.random.fold_in(key, 1),
                               (groups, K, 2 * I)) * .02
        dn = jax.random.normal(jax.random.fold_in(key, 2),
                               (groups, I, K)) * .02
        for n in (step,) + rungs:
            if only_rows and n not in only_rows:
                continue
            rng = np.random.default_rng(n + groups)
            # the router reads rows of its own: their last column is 1 and
            # the router's last row the log of the skewed loads
            router = rng.standard_normal((K, groups)).astype(np.float32) * .02
            router[-1] = np.log(skewed_loads(rng, groups, SKEW))
            x, rx = (rng.standard_normal((n, K)).astype(np.float32)
                     for _ in range(2))
            rx[:, -1] = 1.0
            x, rx, router = (jnp.asarray(a) for a in (x, rx, router))
            m = n * top_k
            # every piece's operands, made once by the parent's pieces
            _, experts, weights = jax.jit(functools.partial(
                moe.route_top_k, top_k=top_k))(rx, router)
            flat = experts.reshape(-1)
            order = jnp.argsort(flat, stable=True)
            sizes = jnp.bincount(flat, length=groups).astype(jnp.int32)
            rows = jnp.take(x, order // top_k, axis=0)
            mm = jax.jit(lambda a, b, s: moe.grouped_matmul(a, b, s, highest))
            h = mm(rows, gu, sizes)
            act = jax.jit(lambda h: moe._gated(h, I, gate))(h)
            y = mm(act, dn, sizes)
            scale = jnp.take(weights.reshape(-1), order)
            inv = inverse_of(order)
            del h
            t1, t2 = kernel.tiles(m, K, 2 * I), kernel.tiles(m, I, K)
            touched = int((np.asarray(sizes) > 0).sum())

            def passes(floats):
                return floats * 4 / HBM * 1e3

            def product(width_in, width_out, wrote):
                return max(6 * 2.0 * m * width_in * width_out / PEAK * 1e3,
                           (touched * width_in * width_out + m * width_in
                            + m * wrote) * 4 / HBM * 1e3)

            mh = m * K
            cases = [
                ("route and top-k", lambda: jax.jit(functools.partial(
                    moe.route_top_k, top_k=top_k)), (rx, router),
                 passes(n * K)),
                ("argsort and bincount", lambda: jax.jit(lambda f: (
                    jnp.argsort(f, stable=True),
                    jnp.bincount(f, length=groups))), (flat,), 0.0),
                ("row gather", lambda: jax.jit(lambda x, o: jnp.take(
                    x, o // top_k, axis=0)), (x, order), passes(2 * mh)),
                ("new: row gather, indices said in bounds", lambda: jax.jit(
                    lambda x, o: jnp.take(x, o // top_k, axis=0,
                                          mode="clip")), (x, order),
                 passes(2 * mh)),
                ("product 1", lambda: mm, (rows, gu, sizes),
                 product(K, 2 * I, 2 * I)),
                ("gate", lambda: jax.jit(lambda h: moe._gated(h, I, gate)),
                 (mm(rows, gu, sizes),), passes(3 * m * I)),
                ("product 2", lambda: mm, (act, dn, sizes),
                 product(I, K, K)),
                ("scale", lambda: jax.jit(lambda y, s: y * s[:, None]),
                 (y, scale), passes(2 * mh)),
                ("un-sort (zeros, scatter)", lambda: jax.jit(
                    lambda y, o: jnp.zeros_like(y).at[o].set(
                        y, mode="drop")), (y, order), passes(3 * mh)),
                ("k-sum", lambda: jax.jit(lambda y: y.reshape(
                    n, top_k, -1).sum(axis=1)), (y,),
                 passes(mh + n * K)),
                ("new: inverse of the sort (a scatter of int32)",
                 lambda: jax.jit(functools.partial(inverse_of, top_k=top_k)),
                 (order,), 0.0),
                ("new: gather-sum, planes [k, N, H]", lambda: jax.jit(
                    functools.partial(gather_sum, top_k=top_k)),
                 (y, inverse_of(order, top_k)), passes(mh + n * K)),
                ("new: gather-sum, one gather of [N k, H], sum over [N, k, H]",
                 lambda: jax.jit(functools.partial(
                     gather_sum, top_k=top_k, how="one")),
                 (y, inv), passes(mh + n * K)),
                ("new: gather-sum, k gathers of [N, H]", lambda: jax.jit(
                    functools.partial(gather_sum, top_k=top_k, how="each")),
                 (y, inv), passes(mh + n * K)),
            ]
            if t1 and kernel.gate_fits(2 * I, t1[1]):
                cases.append((
                    "new: product 1, gate its epilogue", lambda: jax.jit(
                        lambda a, b, s: kernel.grouped_matmul_epilogue(
                            a, b, s, tm=t1[0], tn=t1[1],
                            gate=functools.partial(
                                moe._gated, inter=I, activation=gate))),
                    (rows, gu, sizes), product(K, 2 * I, I)))
            if t2:
                cases.append((
                    "new: product 2, scale its epilogue", lambda: jax.jit(
                        lambda a, b, s, c: kernel.grouped_matmul_epilogue(
                            a, b, s, c, tm=t2[0], tn=t2[1])),
                    (act, dn, sizes, scale), product(I, K, K)))
            layer = functools.partial(routed_layer, top_k=top_k, gate=gate)
            wholes = [
                ("whole: parent formulation", layer),
                ("whole: gather-sum (one gather, [N, k, H])",
                 functools.partial(layer, combine="one")),
                ("whole: gather-sum (k gathers)", functools.partial(
                    layer, combine="each")),
                ("whole: gather-sum (planes)", functools.partial(
                    layer, combine="planes")),
                ("whole: gather-sum (planes), scale fused",
                 functools.partial(layer, combine="planes",
                                   fuse_scale=True)),
                ("whole: gather-sum (planes), scale and gate fused",
                 functools.partial(layer, combine="planes", fuse_scale=True,
                                   fuse_gate=True)),
                ("whole: moe_routed_tokens as the tree has it",
                 lambda x, rx, r, gu, dn: moe.moe_routed_tokens(
                     x, rx, r, gu, dn, top_k=top_k, activation=gate,
                     precision=highest)[0]),
            ]
            cases += [(case, lambda f=f: jax.jit(f), (x, rx, router, gu, dn),
                       0.0) for case, f in wholes]
            print(f"{name} {n} rows ({m} pairs, top {top_k} of {groups}, "
                  f"{touched} touched, largest group "
                  f"{int(np.asarray(sizes).max())}; kernel blocks {t1} "
                  f"{t2})", flush=True)
            first = None
            for case, build, operands, floor in cases:
                rec = {"shape": name, "rows": n, "pairs": m, "piece": case}
                try:
                    f = build()
                    ms, out = timed(
                        lambda *a: jax.tree_util.tree_leaves(f(*a))[0],
                        *operands, reps=10)
                except Exception as e:  # noqa: BLE001 — as in main
                    print(f"    {case:48s} refused: {str(e)[:200]}",
                          flush=True)
                    results.append(dict(rec, refused=str(e)[:400]))
                    continue
                if case.startswith("whole"):
                    out = np.asarray(out)
                    first = out if first is None else first
                    rec["rel_diff_to_parent"] = float(
                        np.abs(out - first).max() / np.abs(first).max())
                    rec["same_bits_as_parent"] = bool(
                        np.array_equal(out, first))
                print(f"    {case:48s} {ms:8.3f} ms"
                      + (f"  floor {floor:6.3f}" if floor else "")
                      + (f"  off the parent's by "
                         f"{rec['rel_diff_to_parent']:.3g}"
                         if "rel_diff_to_parent" in rec else ""),
                      flush=True)
                results.append(dict(rec, ms=ms, floor_ms=floor))
            # after every shape: a run cut short keeps what it measured
            with open(out_path, "w") as f:
                json.dump({"device": jax.devices()[0].device_kind,
                           "skew": SKEW, "results": results}, f, indent=1)
        del gu, dn
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="one name of SHAPES")
    ap.add_argument("--tiles", default="",
                    help="kernel lines at these TM,TN pairs too (a sweep)")
    ap.add_argument("--even", type=int, default=1,
                    help="0: the skewed groups alone")
    ap.add_argument("--held", type=int, default=0,
                    help="1: the held share's loop at HELD's shapes")
    ap.add_argument("--runs", default="",
                    help="with --held: the run lengths (HELD_RUNS)")
    ap.add_argument("--valid", type=int, default=0,
                    help="1: a whole routed layer at VALID's rungs, all rows "
                         "against the prompt's")
    ap.add_argument("--pieces", type=int, default=0,
                    help="1: a routed layer's pieces apart at PIECES' rows, "
                         "the parent's beside PR 57's")
    ap.add_argument("--rows", default="",
                    help="with --pieces: these rows of PIECES alone")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("moe_microbench: no TPU backend, nothing measured",
              file=sys.stderr)
        return 2
    if args.held:
        return held_main(args)
    if args.valid:
        return valid_main(args)
    if args.pieces:
        return pieces_main(args)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from paddle_tpu.ops.pallas import grouped_matmul as kernel
    from paddle_tpu.parallel import moe

    highest = jax.lax.Precision.HIGHEST
    sweep = [tuple(int(x) for x in t.split(","))
             for t in args.tiles.split(";") if t]
    results = []
    for name, (groups, K, I, rungs, step_rows) in SHAPES.items():
        if args.only and name != args.only:
            continue
        key = jax.random.key(groups + K)
        gu = jax.random.normal(jax.random.fold_in(key, 1),
                               (groups, K, 2 * I)) * .02
        dn = jax.random.normal(jax.random.fold_in(key, 2),
                               (groups, I, K)) * .02
        f64 = {}                # a group's weights in float64, as needed
        cells = [(m, "step", SKEW) for m in step_rows]
        for r in rungs:
            cells.append((r * groups, f"{r} a group", SKEW))
            if args.even and name == "smallthinker-21b-a3b":
                cells.append((r * groups, f"{r} a group", 0))
        for m, label, skew in cells:
            rng = np.random.default_rng(m + groups)
            sizes_np = group_sizes(rng, m, groups, skew)
            sizes = jnp.asarray(sizes_np)
            rows = jax.random.normal(jax.random.fold_in(key, m), (m, K))
            touched = int((sizes_np > 0).sum())

            def ffn(mm):
                def f(rows, gu, dn, sizes):
                    h = mm(rows, gu, sizes)
                    return mm(jnp.maximum(h[:, :I], 0) * h[:, I:], dn, sizes)
                return jax.jit(f)

            def ragged(prec):
                return lambda a, b, s: jax.lax.ragged_dot(
                    a, b, s, precision=prec, preferred_element_type=a.dtype)

            def parent(a, b, s):
                if RUN_ROWS < a.shape[0] < WIDE_TILE * b.shape[0]:
                    return parent_runs(a, b, s, highest)
                return moe._one_call(a, b, s, highest)

            def tiled(tm, tn):
                """... the widest whole-lane-tile divisor of N up to tn"""
                return lambda a, b, s: kernel.grouped_matmul(
                    a, b, s, tm=tm, tn=max(
                        t for t in range(128, min(tn, b.shape[2]) + 1, 128)
                        if b.shape[2] % t == 0))

            chosen = [kernel.tiles(m, K, 2 * I), kernel.tiles(m, I, K)]
            cases = [("parent", parent),
                     ("ragged_dot highest", ragged(highest)),
                     ("route: " + ("kernel {} {}".format(*chosen)
                                   if all(chosen) else "ragged_dot"),
                      lambda a, b, s: moe.grouped_matmul(a, b, s, highest))]
            if name == "smallthinker-21b-a3b" and m in (
                    192, 768 * groups) and skew:
                cases += [("ragged_dot default", ragged(None)),
                          ("megablox 128", lambda a, b, s: gmm(
                              a, b, s, a.dtype, (128, 128, 128))),
                          ("megablox 512", lambda a, b, s: gmm(
                              a, b, s, a.dtype, (512, 512, 512)))]
            cases += [(f"kernel ({tm}, {tn})", tiled(tm, tn))
                      for tm, tn in sweep]
            # float64 loop on the host, over 64 rows spread over the call
            at = np.unique(np.linspace(0, m - 1, 64).astype(int))
            ends = np.cumsum(sizes_np)
            which = np.searchsorted(ends, at, side="right")
            for g in set(which.tolist()) - set(f64):
                f64[g] = (np.asarray(gu[g], np.float64),
                          np.asarray(dn[g], np.float64))
            r64 = np.asarray(rows[at], np.float64)
            want = np.stack([
                (lambda h: (np.maximum(h[:I], 0) * h[I:]) @ f64[g][1])(
                    r @ f64[g][0]) for r, g in zip(r64, which)])
            flops = 2.0 * m * 3 * K * I
            floor_mxu = 6 * flops / PEAK * 1e3
            floor_hbm = touched * 3 * K * I * 4 / HBM * 1e3
            print(f"{name} {label} ({m} rows, "
                  f"{'skew %.1f' % skew if skew else 'even'}; largest group "
                  f"{sizes_np.max()}, {touched} touched): six-pass MXU floor "
                  f"{floor_mxu:.2f} ms, weights' bytes {floor_hbm:.2f} ms",
                  flush=True)
            for case, mm in cases:
                rec = {"shape": name, "rows": m, "label": label,
                       "skew": skew, "formulation": case}
                try:
                    ms, out = timed(ffn(mm), rows, gu, dn, sizes)
                except Exception as e:  # noqa: BLE001 — a formulation the
                    # compiler refuses is a finding, not a crash
                    print(f"    {case:34s} refused: {str(e)[:200]}",
                          flush=True)
                    results.append(dict(rec, refused=str(e)[:400]))
                    continue
                err = float(np.abs(np.asarray(out[at]) - want).max()
                            / np.abs(want).max())
                print(f"    {case:34s} {ms:9.3f} ms  {flops / ms / 1e9:6.1f} "
                      f"TFLOP/s, x{ms / max(floor_mxu, floor_hbm):.2f} the "
                      f"larger floor, off the float64 loop by {err:.3g}",
                      flush=True)
                results.append(dict(
                    rec, ms=ms, experts_touched=touched,
                    largest_group=int(sizes_np.max()), mxu_floor_ms=floor_mxu,
                    bytes_floor_ms=floor_hbm, tflops=flops / ms / 1e9,
                    rel_err=err))
        del gu, dn
    os.makedirs("chiprun_out", exist_ok=True)
    out = "chiprun_out/moe_formulation_sweep.json"
    if args.only or sweep:
        out = out.replace(".json", f"_{args.only or 'all'}"
                          f"{'_tiles' if sweep else ''}.json")
    with open(out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "results": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
