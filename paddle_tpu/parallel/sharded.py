"""GSPMD lowering of a static Program to a sharded, jitted step function.

This is the TPU-native replacement for the reference's entire multi-device
execution stack — ParallelExecutor's SSA graph with AllReduceOpHandles
(framework/parallel_executor.cc:504, details/all_reduce_op_handle.cc:60) and
the Fleet collective transpiler that inserts c_allreduce_sum ops
(python/paddle/fluid/transpiler/collective.py:178). Instead of rewriting
the program, we:

  1. lower the block once to a pure step function (same path the Executor
     uses — framework/executor.py),
  2. attach `jax.sharding.NamedSharding`s to the feed (batch over `dp`) and
     to every parameter / optimizer-state array (sharding *rules*),
  3. `jax.jit` over the mesh — XLA's SPMD partitioner inserts all-reduce /
     all-gather / reduce-scatter over ICI exactly where the reference
     inserts NCCL ops.

A gradient allreduce never appears in our IR: with the batch sharded over
`dp`, the loss reduction crosses a sharded axis and XLA emits the psum.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..compile_cache import ensure_compile_cache
from ..framework.core import Block, Program, Variable
from ..framework.executor import analyze_block, lower_block
from .mesh import DP_AXIS, MP_AXIS


class ShardingRules:
    """Maps variable (name, shape) -> PartitionSpec.

    Reference analog: the per-strategy program rewrites of §2.6; here a
    strategy is *just a rule table*. Compose with `then`.
    """

    def __init__(self, fn: Callable[[str, Tuple[int, ...]], Optional[tuple]]):
        self._fn = fn

    def spec(self, name: str, shape) -> tuple:
        from jax.sharding import PartitionSpec as P
        s = self._fn(name, tuple(shape or ()))
        return s if s is not None else P()

    def then(self, other: "ShardingRules") -> "ShardingRules":
        def fn(name, shape):
            s = self._fn(name, shape)
            return s if s is not None else other._fn(name, shape)
        return ShardingRules(fn)


def data_parallel_rules() -> ShardingRules:
    """Replicate everything (params live replicated; batch sharding is done
    on the feed, not via these rules)."""
    return ShardingRules(lambda name, shape: None)


def megatron_rules(mesh, axis: str = MP_AXIS) -> ShardingRules:
    """Tensor-parallel rule table in the GSPMD style: annotate weight
    shardings and let XLA pick the collectives (vs. Megatron's hand-placed
    row/column splits + allreduces — new capability, absent in the
    reference vintage, SURVEY.md §2.6 last row).

    >=2-D weights (matmul + embedding tables) shard their last dim over
    `axis` when divisible; XLA propagates and inserts all-gathers /
    reduce-scatters as needed.
    """
    from jax.sharding import PartitionSpec as P

    size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)

    def fn(name, shape):
        if size <= 1 or not shape:
            return None
        if len(shape) >= 2 and shape[-1] % size == 0:
            return P(*([None] * (len(shape) - 1) + [axis]))
        return None

    return ShardingRules(fn)


def build_sharded_step(program: Program, feed_names: Sequence[str],
                       fetch_names: Sequence[str], mesh,
                       rules: Optional[ShardingRules] = None,
                       batch_axes: Sequence[str] = (DP_AXIS,),
                       donate_state: bool = True,
                       feed_pspecs: Optional[Dict[str, tuple]] = None):
    """Lower block 0 of `program` into one jitted SPMD step function.

    Returns (fn, mut_in, const_in, extra_out) where
    ``fn(feed_vals, mut_vals, const_vals, step)
        -> (fetches, new_mut_vals, extra_vals)``.
    ``new_mut_vals`` aligns with ``mut_in`` so training loops can thread it
    straight back in; ``extra_vals`` aligns with ``extra_out`` (persistable
    vars written but never read, e.g. fetch-only state). Feed arrays are
    sharded on dim 0 over `batch_axes`; state arrays are placed by `rules`.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ensure_compile_cache()
    rules = rules or data_parallel_rules()
    block = program.global_block()
    state_in, state_out = analyze_block(block, feed_names)
    out_set = set(state_out)
    mut_in = [n for n in state_in if n in out_set]
    const_in = [n for n in state_in if n not in out_set]
    extra_out = [n for n in state_out if n not in set(mut_in)]
    seed = program.random_seed or 0

    present = [a for a in batch_axes if a in mesh.axis_names]
    batch_spec = P(tuple(present)) if present else P()

    def _state_sharding(name):
        v = block._find_var_recursive(name)
        shape = v.shape if v is not None else ()
        return NamedSharding(mesh, rules.spec(name, shape))

    feed_pspecs = feed_pspecs or {}
    feed_sh = tuple(
        NamedSharding(mesh, feed_pspecs.get(n, batch_spec))
        for n in feed_names)
    mut_sh = tuple(_state_sharding(n) for n in mut_in)
    const_sh = tuple(_state_sharding(n) for n in const_in)
    extra_sh = tuple(_state_sharding(n) for n in extra_out)
    fetch_sh = tuple(NamedSharding(mesh, P()) for _ in fetch_names)
    step_sh = NamedSharding(mesh, P())

    def step_fn(feed_vals, mut_vals, const_vals, step):
        base_key = jax.random.fold_in(jax.random.key(np.uint32(seed)), step)
        env: Dict[str, object] = {}
        env.update(zip(feed_names, feed_vals))
        env.update(zip(mut_in, mut_vals))
        env.update(zip(const_in, const_vals))
        lower_block(block, env, base_key, mesh=mesh)
        return (tuple(env[n] for n in fetch_names),
                tuple(env[n] for n in mut_in),
                tuple(env[n] for n in extra_out))

    # out_shardings pins the mut state to its declared placement so the
    # returned arrays can be threaded straight back in (donation-safe).
    fn = jax.jit(
        step_fn,
        in_shardings=(feed_sh, mut_sh, const_sh, step_sh),
        out_shardings=(fetch_sh, mut_sh, extra_sh),
        donate_argnums=(1,) if donate_state else (),
    )
    return fn, mut_in, const_in, extra_out


def build_sharded_multistep(program: Program, feed_names: Sequence[str],
                            fetch_names: Sequence[str], mesh, num_steps: int,
                            rules: Optional[ShardingRules] = None,
                            batch_axes: Sequence[str] = (DP_AXIS,),
                            donate_state: bool = True):
    """Like build_sharded_step, but runs `num_steps` optimizer steps in ONE
    device dispatch via lax.scan over a stacked feed.

    ``fn(stacked_feeds, mut_vals, const_vals, step0)
        -> (last_fetches, new_mut_vals, last_extra_vals)``
    where each stacked feed has a leading [num_steps] axis. The per-step
    RNG folding matches build_sharded_step exactly (step0+1, step0+2, ...).

    Rationale: a host dispatch per step costs fixed latency; a
    device-side while loop amortizes it to once per window. This
    is the TPU-native executor shape: the reference's trainer loop
    dispatches per-op per-step, ours compiles the whole window
    (SURVEY.md §2.1 Executor).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ensure_compile_cache()
    rules = rules or data_parallel_rules()
    block = program.global_block()
    state_in, state_out = analyze_block(block, feed_names)
    out_set = set(state_out)
    mut_in = [n for n in state_in if n in out_set]
    const_in = [n for n in state_in if n not in out_set]
    extra_out = [n for n in state_out if n not in set(mut_in)]
    seed = program.random_seed or 0

    present = [a for a in batch_axes if a in mesh.axis_names]
    # feeds carry a leading scan axis; batch is dim 1
    batch_spec = P(None, tuple(present)) if present else P()

    def _state_sharding(name):
        v = block._find_var_recursive(name)
        shape = v.shape if v is not None else ()
        return NamedSharding(mesh, rules.spec(name, shape))

    feed_sh = tuple(NamedSharding(mesh, batch_spec) for _ in feed_names)
    mut_sh = tuple(_state_sharding(n) for n in mut_in)
    const_sh = tuple(_state_sharding(n) for n in const_in)
    extra_sh = tuple(_state_sharding(n) for n in extra_out)
    fetch_sh = tuple(NamedSharding(mesh, P()) for _ in fetch_names)
    step_sh = NamedSharding(mesh, P())

    def multi_fn(stacked_feeds, mut_vals, const_vals, step0):
        def body(carry, feeds):
            mut_vals, step = carry
            step = step + 1
            base_key = jax.random.fold_in(
                jax.random.key(np.uint32(seed)), step)
            env: Dict[str, object] = {}
            env.update(zip(feed_names, feeds))
            env.update(zip(mut_in, mut_vals))
            env.update(zip(const_in, const_vals))
            lower_block(block, env, base_key, mesh=mesh)
            return ((tuple(env[n] for n in mut_in), step),
                    (tuple(env[n] for n in fetch_names),
                     tuple(env[n] for n in extra_out)))

        (mut_vals, _), (fetches, extras) = jax.lax.scan(
            body, (mut_vals, step0), tuple(stacked_feeds))
        last = jax.tree_util.tree_map(lambda x: x[-1], (fetches, extras))
        return last[0], mut_vals, last[1]

    fn = jax.jit(
        multi_fn,
        in_shardings=(feed_sh, mut_sh, const_sh, step_sh),
        out_shardings=(fetch_sh, mut_sh, extra_sh),
        donate_argnums=(1,) if donate_state else (),
        static_argnames=(),
    )
    return fn, mut_in, const_in, extra_out


def shard_batch(mesh, arrays: Sequence, batch_axes: Sequence[str] = (DP_AXIS,)):
    """Device_put feed arrays with the batch dim sharded over the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    present = [a for a in batch_axes if a in mesh.axis_names]
    sh = NamedSharding(mesh, P(tuple(present)) if present else P())
    return [jax.device_put(a, sh) for a in arrays]
