"""Explicit-collective SPMD execution via shard_map.

Reference analog: the multi-process collective mode — each GPU runs the
transpiled program containing c_allreduce_sum ops over NCCL rings
(transpiler/collective.py:178, operators/collective/c_allreduce_op.h:109).
Here the N "processes" are the mesh devices of ONE jitted SPMD program:
the block is lowered inside jax.shard_map, so mesh axis names are bound
and each c_* op lowers to the matching lax collective over ICI.

Complements sharded.py (GSPMD/implicit): use spmd when the program carries
explicit communication ops (fleet-rewritten programs, collective op tests),
gspmd when communication should be inferred from shardings.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from ..compile_cache import ensure_compile_cache
from ..framework.core import Program
from ..framework.executor import analyze_block
from ..ops.registry import LowerContext, lower_op
from .mesh import DP_AXIS


def _lower_block_spmd(block, env, base_key, mesh, axis_names, ring_table,
                      is_test=False):
    ctx = LowerContext(block, env, base_key=base_key, is_test=is_test,
                       mesh=mesh,
                       amp=getattr(block.program, "_amp_lowering", None))
    ctx.axis_names = tuple(axis_names)
    ctx.ring_table = dict(ring_table or {})
    for op in block.ops:
        if op.type in ("feed", "fetch"):
            continue
        lower_op(ctx, op)
    return ctx


def build_spmd_step(program: Program, feed_names: Sequence[str],
                    fetch_names: Sequence[str], mesh,
                    batch_axis: str = DP_AXIS,
                    ring_table: Optional[Dict[int, str]] = None,
                    donate_state: bool = True):
    """Lower block 0 inside shard_map over `mesh`.

    Feeds are split on dim 0 over `batch_axis`; state (params, opt moments)
    is replicated per participant — exactly the reference's multi-process
    data layout. Returns (fn, mut_in, const_in, extra_out) with
    ``fn(feed_vals, mut_vals, const_vals, step) ->
        (fetches, new_mut, extra)``.

    Fetch semantics mirror ParallelExecutor: each fetched var is the
    concatenation of the participants' values along dim 0 (scalars become
    shape [nranks]) — reference details/fetch_op_handle.cc.
    """
    import jax
    from jax.sharding import PartitionSpec as P


    block = program.global_block()
    state_in, state_out = analyze_block(block, feed_names)
    out_set = set(state_out)
    mut_in = [n for n in state_in if n in out_set]
    const_in = [n for n in state_in if n not in out_set]
    extra_out = [n for n in state_out if n not in set(mut_in)]
    seed = program.random_seed or 0
    ring_table = dict(ring_table or {})
    ring_table.setdefault(0, batch_axis)
    axis_names = tuple(mesh.axis_names)

    feed_spec = tuple(P(batch_axis) for _ in feed_names)
    mut_spec = tuple(P() for _ in mut_in)
    const_spec = tuple(P() for _ in const_in)

    def shard_body(feed_vals, mut_vals, const_vals, step):
        base_key = jax.random.fold_in(jax.random.key(np.uint32(seed)), step)
        # per-participant randomness (dropout masks differ per shard, as in
        # the reference's per-process seeds)
        base_key = jax.random.fold_in(
            base_key, jax.lax.axis_index(batch_axis))
        env: Dict[str, object] = {}
        env.update(zip(feed_names, feed_vals))
        env.update(zip(mut_in, mut_vals))
        env.update(zip(const_in, const_vals))
        _lower_block_spmd(block, env, base_key, mesh, axis_names, ring_table)
        import jax.numpy as jnp
        fetches = tuple(
            jnp.reshape(env[n], (1,)) if jnp.ndim(env[n]) == 0 else env[n]
            for n in fetch_names)
        return (fetches,
                tuple(env[n] for n in mut_in),
                tuple(env[n] for n in extra_out))

    from .mesh import shard_map_compat
    mapped = shard_map_compat(
        shard_body, mesh,
        in_specs=(feed_spec, mut_spec, const_spec, P()),
        out_specs=(tuple(P(batch_axis) for _ in fetch_names), mut_spec,
                   tuple(P() for _ in extra_out)))

    ensure_compile_cache()
    fn = jax.jit(mapped, donate_argnums=(1,) if donate_state else ())
    return fn, mut_in, const_in, extra_out
