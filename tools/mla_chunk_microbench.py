#!/usr/bin/env python3
"""Times the two forms a prefill chunk over latent (MLA) pages can take, on
the chip, at DeepSeek-V2's published head sizes (128 heads of nope 128 +
rope 64 over a latent of 512, values of 128; float32 operands whole):

(a) **expanded block by block** (``ops/pallas/latent_attention.py``
    ``mla_chunk_attention``, what the program runs): each key block of
    cached rows goes through the head's columns of ``W_kvb`` in VMEM and
    meets the head's chunk of queries: 640 FLOP a pair a head, and 65,536
    x 2 a cached row a head re-expanded once a chunk;
(b) **absorbed** (this file's kernel, as the decode step does it, at C x
    128 query rows): ``q_lat = q_nope W_UK^T`` once a head, scores over
    the 576 lanes of a cached row, the latent itself as the value, ``o =
    o_lat W_UV`` at the end: 2,176 FLOP a pair a head, nothing expanded.

``python tools/mla_chunk_microbench.py`` (chip only, about three minutes):
a chunk of 1024 rows over 4,096 and 12,288 cached rows (its own included),
a chunk of 256 at the same ends, form (a) at key blocks of 256 / 512 /
1024; each the median of ``--reps`` runs after a warm-up, with the two
forms' largest difference.  Writes ``chiprun_out/mla_chunk_microbench.json``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _absorbed_kernel(base_ref, qn_ref, qr_ref, rows_ref, w_ref, o_ref,
                     qlat_ref, m_ref, l_ref, acc_ref, *, scale, block_k, ck,
                     dn):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.ops.pallas.latent_attention import NEG_INF, PRECISION

    j = pl.program_id(1)
    base = base_ref[0]
    contract = (((1,), (1,)), ((), ()))
    dots = dict(preferred_element_type=jnp.float32, precision=PRECISION)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        qlat_ref[...] = jax.lax.dot_general(
            qn_ref[0], w_ref[...][:, :dn], contract, **dots)   # [C, ck]

    @pl.when(j * block_k < base + qn_ref.shape[1])
    def _():
        lat = rows_ref[...]
        s = (jax.lax.dot_general(qlat_ref[...], lat[:, :ck], contract,
                                 **dots)
             + jax.lax.dot_general(qr_ref[0], lat[:, ck:], contract,
                                   **dots)) * scale
        q_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(p, lat[:, :ck], **dots)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = jnp.dot(acc_ref[...] / jnp.maximum(l_ref[...], 1e-30),
                           w_ref[...][:, dn:], **dots)


def absorbed_chunk_attention(q_nope, q_rope, rows, w_kvb, base, *, scale,
                             latent_dim, block_k=512):
    """``mla_chunk_attention``'s arguments and result, in the absorbed
    arithmetic."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.pallas.latent_attention import CHUNK_VMEM_BYTES

    H, C, dn = q_nope.shape
    S, row = rows.shape
    ck, per_head = int(latent_dim), w_kvb.shape[1] // H
    rest = row - ck
    q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, rest - q_rope.shape[-1])))
    kernel = functools.partial(_absorbed_kernel, scale=float(scale),
                               block_k=block_k, ck=ck, dn=dn)

    def rows_at(h, j, base):
        return jnp.minimum(j, (base[0] + C - 1) // block_k), 0

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((H, C, per_head - dn), q_nope.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H, S // block_k),
            in_specs=[pl.BlockSpec((1, C, dn), lambda h, j, *_: (h, 0, 0)),
                      pl.BlockSpec((1, C, rest), lambda h, j, *_: (h, 0, 0)),
                      pl.BlockSpec((block_k, row), rows_at),
                      pl.BlockSpec((ck, per_head), lambda h, j, *_: (0, h))],
            out_specs=pl.BlockSpec((1, C, per_head - dn),
                                   lambda h, j, *_: (h, 0, 0)),
            scratch_shapes=[pltpu.VMEM((C, ck), jnp.float32),
                            pltpu.VMEM((C, 1), jnp.float32),
                            pltpu.VMEM((C, 1), jnp.float32),
                            pltpu.VMEM((C, ck), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=CHUNK_VMEM_BYTES),
        name="mla_chunk_attention_absorbed",
    )(base.astype(jnp.int32).reshape(1), q_nope, q_rope, rows, w_kvb)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--view", type=int, default=12800)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import latent_attention as la

    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        print("mla_chunk_microbench: needs the chip", file=sys.stderr)
        return 2
    H, C, dn, dr, dv, S = 128, 512, 128, 64, 128, args.view
    row = la.row_lanes(C + dr)
    scale = 0.11472
    key = jax.random.key(56)
    w = jax.random.normal(jax.random.fold_in(key, 3),
                          (C, H * (dn + dv))) * C ** -0.5
    all_rows = jnp.pad(jax.random.normal(jax.random.fold_in(key, 2),
                                         (S, C + dr)),
                       ((0, 0), (0, row - C - dr)))

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        took = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            took.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(took)

    results = []
    for T in (1024, 256):
        qn = jax.random.normal(jax.random.fold_in(key, T), (H, T, dn)) * 0.3
        qr = jax.random.normal(jax.random.fold_in(key, T + 1),
                               (H, T, dr)) * 0.3
        for cached in (4096, 12288):
            base = jnp.asarray([cached - T], jnp.int32)
            rows = jnp.where((jnp.arange(S) < cached)[:, None], all_rows, 0.0)
            pairs = T * (cached - T) + T * (T + 1) // 2
            line = {"rows": T, "cached": cached, "pairs_a_head": pairs}
            outs = {}
            for bk in (256, 512, 1024):
                fn = functools.partial(la.mla_chunk_attention, scale=scale,
                                       nope_dim=dn, latent_dim=C, block_k=bk)
                line[f"expanded_bk{bk}_ms"] = timed(fn, qn, qr, rows, w, base)
                outs[bk] = fn(qn, qr, rows, w, base)
            fn = functools.partial(absorbed_chunk_attention, scale=scale,
                                   latent_dim=C)
            line["absorbed_bk512_ms"] = timed(fn, qn, qr, rows, w, base)
            other = fn(qn, qr, rows, w, base)
            line["forms_differ_by"] = float(
                jnp.abs(outs[512] - other).max() / jnp.abs(other).max())
            # the expanded form's work of the admitted pairs, each cached
            # row expanded once: the share's numerator in the benchmark
            line["expanded_tflops_at_bk512"] = (
                H * pairs * 640 + H * cached * 2 * C * (dn + dv)) \
                / line["expanded_bk512_ms"] / 1e9
            print(json.dumps(line), flush=True)
            results.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mla_chunk_microbench.json", "w") as f:
        json.dump({"device": d0.device_kind, "reps": args.reps,
                   "results": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
