"""CompiledProgram / ParallelExecutor equivalents.

Reference: python/paddle/fluid/compiler.py:87 (CompiledProgram,
_compile_data_parallel:319) wrapping the C++ ParallelExecutor SSA-graph
engine (framework/parallel_executor.cc:504).

TPU-native: "compiling with data parallelism" = choosing one of two SPMD
lowerings over a device mesh (parallel/):
  * programs WITHOUT explicit c_* collective ops -> GSPMD (sharded.py):
    batch sharded over dp, XLA infers the gradient all-reduce;
  * programs WITH c_* ops (fleet-rewritten) -> shard_map (spmd.py):
    the ops lower to lax collectives.
The reference's thread-pools, SSA dependency graphs, and op-handle
scheduling have no equivalent — XLA schedules the whole step.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from .core import Program
from .executor import Scope, global_scope


class ReduceStrategy:
    AllReduce = 0
    Reduce = 1


class BuildStrategy:
    """Accepted for API parity (reference details/build_strategy.h). Most
    knobs configure the SSA-graph passes, which don't exist here; the
    meaningful ones map to lowering choices."""

    ReduceStrategy = ReduceStrategy

    def __init__(self):
        self.reduce_strategy = ReduceStrategy.AllReduce
        self.gradient_scale_strategy = None
        self.fuse_all_reduce_ops = True      # XLA fuses collectives itself
        self.fuse_elewise_add_act_ops = True  # XLA fusion
        self.fuse_bn_act_ops = True
        self.enable_inplace = True           # buffer donation
        self.memory_optimize = True
        self.sync_batch_norm = False
        self.enable_sequential_execution = False
        self.num_trainers = 1
        self.trainer_id = 0


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 100
        self.num_iteration_per_run = 1
        self.use_thread_barrier = False


class CompiledProgram:
    """exe.run(CompiledProgram(prog).with_data_parallel(...)) parity."""

    def __init__(self, program_or_graph, build_strategy: Optional[
            BuildStrategy] = None):
        self._program: Program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._is_data_parallel = False
        self._loss_name = None
        self._exec_strategy = None
        self._places = None
        # (sig, executable, mut_in, const_in, mesh, mode, batch_axes)
        self._compiled = None

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        self._is_data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._places = places
        return self

    @property
    def executable(self):
        """The data-parallel step as XLA compiled it for the last feed
        signature (a ``jax.stages.Compiled``: ``as_text()``,
        ``input_shardings``, ``memory_analysis()``); None before the
        first run."""
        return self._compiled[1] if self._compiled else None

    # Executor.run delegates here (framework/executor.py)
    def _compile_and_run(self, exe, feed, fetch_list, scope, return_numpy):
        import jax

        from ..framework.executor import _fetch_names, _prepare_feed
        if not self._is_data_parallel:
            return exe.run(self._program, feed, fetch_list, scope,
                           return_numpy, use_program_cache=True)

        scope = scope or global_scope()
        feed = dict(feed or {})
        block = self._program.global_block()
        feed_arrays = _prepare_feed(block, feed)
        fetch_names = _fetch_names(fetch_list)
        sig = tuple((n, tuple(np.shape(a)), str(np.asarray(a).dtype))
                    for n, a in sorted(feed_arrays.items()))
        key = (sig, tuple(fetch_names))

        def _val(n):
            v = scope.find_var(n)
            if v is None:
                raise RuntimeError(f"variable {n!r} missing from scope; "
                                   f"run the startup program first")
            return v

        feed_vals = tuple(feed_arrays.values())
        exe._step += 1
        step = np.int32(exe._step)
        if self._compiled is None or self._compiled[0] != key:
            fn, mut_in, const_in, *layout = self._build(
                list(feed_arrays), fetch_names)
            # one XLA compile, kept: the executable is the step from
            # here on and can be inspected (``executable``)
            executable = fn.lower(
                feed_vals, tuple(_val(n) for n in mut_in),
                tuple(_val(n) for n in const_in), step).compile()
            self._compiled = (key, executable, mut_in, const_in, *layout)
        _, executable, mut_in, const_in = self._compiled[:4]

        # the executable takes its arguments where it was compiled to
        # find them: the batch split over the mesh, and state moved to
        # its mesh placement once (the step hands it back placed)
        feed_sh, mut_sh, const_sh, _ = executable.input_shardings[0]

        def _placed(names, shardings):
            vals = []
            for n, sh in zip(names, shardings):
                v = _val(n)
                if getattr(v, "sharding", None) != sh:
                    v = jax.device_put(v, sh)
                    scope.set_var(n, v)
                vals.append(v)
            return tuple(vals)

        fetches, new_mut, _extra = executable(
            jax.device_put(feed_vals, feed_sh), _placed(mut_in, mut_sh),
            _placed(const_in, const_sh), step)
        for n, v in zip(mut_in, new_mut):
            scope.set_var(n, v)
        exe._last_dispatch = new_mut
        # same epilogue contract as Executor.run: blocking numpy, or lazy
        # FetchHandles (run_async wraps these into its AsyncRunResult)
        return exe._finish_fetches(list(fetches), return_numpy)

    def _build(self, feed_names, fetch_names):
        import jax
        from ..parallel.mesh import dp_mesh
        from ..parallel.sharded import build_sharded_step
        from ..parallel.spmd import build_spmd_step

        n = len(self._places) if self._places else len(jax.devices())
        mesh = dp_mesh(n)
        batch_axes = ("dp",)

        if self._build_strategy.sync_batch_norm:
            # the reference's sync-BN build pass rewrites batch_norm ->
            # sync_batch_norm (details/build_strategy.cc); same here —
            # the op's pmean binds the dp axis in the spmd lowering
            for blk in self._program.blocks:
                for op in blk.ops:
                    if op.type == "batch_norm":
                        op.type = "sync_batch_norm"

        def _has_collective(blk):
            return any(
                op.type.startswith(("c_", "send_v2", "recv_v2", "barrier"))
                or op.type == "sync_batch_norm"
                or any(op.attr(k) is not None and _has_collective(
                       self._program.block(op.attr(k)))
                       for k in ("sub_block", "true_block", "false_block"))
                for op in blk.ops)

        if _has_collective(self._program.global_block()):
            fn, mut_in, const_in, _extra = build_spmd_step(
                self._program, feed_names, fetch_names, mesh)
            return fn, mut_in, const_in, mesh, "spmd", batch_axes
        rules = None
        zs = getattr(self._program, "_zero_sharding", None)
        if zs:
            from ..distributed.fleet.meta_optimizers.sharding_optimizer \
                import zero_mesh, zero_sharding_rules
            mesh, batch_axes = zero_mesh(n, zs.get("degree", n))
            rules = zero_sharding_rules(mesh)
        fn, mut_in, const_in, _extra = build_sharded_step(
            self._program, feed_names, fetch_names, mesh, rules=rules,
            batch_axes=batch_axes)
        return fn, mut_in, const_in, mesh, "gspmd", batch_axes


class ParallelExecutor:
    """Thin reference-parity wrapper (fluid.ParallelExecutor) over
    CompiledProgram."""

    def __init__(self, use_cuda=False, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None,
                 build_strategy=None, num_trainers=1, trainer_id=0,
                 scope=None):
        from .core import default_main_program
        self._program = main_program or default_main_program()
        self._compiled = CompiledProgram(
            self._program, build_strategy).with_data_parallel(
            loss_name=loss_name, exec_strategy=exec_strategy)
        self._scope = scope

    def run(self, fetch_list, feed=None, feed_dict=None,
            return_numpy=True):
        from .executor import Executor
        exe = Executor()
        return self._compiled._compile_and_run(
            exe, feed or feed_dict, fetch_list, self._scope, return_numpy)
