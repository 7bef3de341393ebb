"""Sharded serving: mesh-partitioned inference under the batching front end.

Bridges the two halves the repo already proved separately — the
dynamic-batching serving engine (``serving/engine.py``, single-chip
predictor pool) and the GSPMD training path (``parallel/sharded.py``
dp×mp×ep meshes, MULTICHIP legs) — into the reference's missing
Fleet-inference analogue (PAPER.md L4b ParallelExecutor + L5 inference
engine): a model bigger than one chip serves weight-sharded over
``mp``/``ep``, and independent ``dp`` replica groups multiply
throughput, all under the unchanged batcher / admission / tracing /
drain front end.

* :class:`ShardedPredictor` — the :class:`~paddle_tpu.inference.
  Predictor` contract (``run`` / ``warmup`` / ``clone`` /
  ``cache_info`` with XLA manifests) lowered through the SAME GSPMD
  path training uses: ``jax.jit`` with ``in_shardings`` built from a
  :class:`~paddle_tpu.parallel.sharded.ShardingRules` table (weights
  over ``mp``/``ep``) and the feed batch dim over ``dp`` when the mesh
  carries one and the bucket divides.  Weights are placed onto the
  mesh ONCE at construction; ``clone()`` shares the placed weights and
  the compiled sharded executables (the mesh-aware Clone() contract).
* :class:`ReplicaGroupEngine` — a :class:`~paddle_tpu.serving.engine.
  ServingEngine` whose worker pool is one :class:`ShardedPredictor`
  per **dp replica group** (disjoint ``mp × ep`` sub-meshes of the
  device set).  Groups dispatch concurrently off the shared bounded
  queue; bucketed batching, deadline shedding, request tracing and
  SIGTERM drain are inherited unchanged.  Per-shard health — last
  batch status, consecutive failures, degraded flag, per-device
  ``_dev<i>`` attribution — rides ``/healthz`` and ``/statusz``.

Bit-exactness: the rule table (:func:`serving_shard_rules`) shards
weights only on NON-contracting dims (the GSPMD megatron style), so
XLA gathers activations rather than forming cross-device partial sums
— every reduction runs whole on one device in the single-device
order.  Replica-group serving therefore returns outputs
``np.array_equal`` to the unsharded predictor's (asserted across
dp-only / mp-only / dp×mp topologies at every bucket boundary in
``tests/test_sharded_serving.py``).  Two caveats.  (1) The contract
assumes the megatron divisibility rule: ``mp`` (or ``ep``) divides
EVERY >=2-D weight's last dim.  An indivisible weight replicates —
still correct — but contracting a still-sharded activation against a
replicated weight lets GSPMD partial-sum across devices, drifting
low-order bits.  (2) IN-mesh batch splitting (a ``dp`` axis inside
one ShardedPredictor's own mesh, not the engine's replica groups):
slicing the batch can change the backend's matmul tiling at very
small per-shard row counts and with it the low-order bits — which is
exactly why the engine's dp mechanism is independent whole-batch
groups, not batch splitting.

Degradation contract: a replica group whose batches keep failing
(``FLAGS_serving_group_degraded_after`` consecutive failures) reports
``degraded`` in ``/healthz``/``/statusz`` (engine status
``degraded``); it keeps pulling work — one poisoned group must not
sink its requests silently NOR stop the other groups (the
``serve_batch:fail`` fault matrix covers exactly this).  A group whose
mesh devices are missing from the live device set reports
``missing_shards``.  Poison-request *bisection* is inherited from the
base scheduler unchanged: a poisoned row in a group's batch is
isolated by split-and-retry on THAT group's mesh, its riders served
bit-exact (``tests/test_fault_containment.py``), and the stuck-worker
watchdog covers a wedged group dispatch thread the same way.

Stats (README catalog): gauges ``serving_replica_groups``,
``serving_groups_degraded``; per-device counters
``serving_sharded_batches_dev<i>`` /
``serving_sharded_batch_failures_dev<i>`` (dynamic ``_dev<i>``
convention, PR-6 groundwork).
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import telemetry
from ..flags import flag_value
from ..inference import Predictor
from ..parallel.mesh import (DP_AXIS, EP_AXIS, MP_AXIS, axis_size,
                             make_mesh, parse_mesh_spec)
from ..parallel.sharded import ShardingRules, megatron_rules
from .engine import ServingEngine

__all__ = ["ShardedPredictor", "ReplicaGroupEngine",
           "serving_shard_rules", "describe_mesh",
           "place_block_state"]

logger = logging.getLogger("paddle_tpu.serving.sharded")


def serving_shard_rules(mesh) -> ShardingRules:
    """The serving weight-placement table: shard every >=2-D weight's
    last (non-contracting) dim over ``mp`` when divisible, else over
    ``ep`` — models bigger than a chip split across the group's
    devices; 1-D params (biases, norms) replicate.  Never sharding a
    contraction dim is what keeps sharded serving bit-exact (XLA
    gathers activations instead of partial-summing)."""
    rules = megatron_rules(mesh, MP_AXIS)
    if axis_size(mesh, EP_AXIS) > 1:
        rules = rules.then(megatron_rules(mesh, EP_AXIS))
    return rules


def describe_mesh(mesh) -> str:
    """``"dp=2,mp=2"`` — the human-readable axis map for /statusz."""
    return ",".join(f"{a}={s}" for a, s in
                    zip(mesh.axis_names, mesh.devices.shape))


def place_block_state(block, feed_names, scope, mesh, rules,
                      skip=(), into=None) -> List[str]:
    """Shard every non-feed state array a block reads onto ``mesh``
    per the rule table (``device_put`` once — a compile must never
    re-transfer weights).  Placed arrays land in ``into`` when given
    (a private scope, so replica groups on disjoint sub-meshes never
    clobber each other), else back into ``scope``; ``skip`` names stay
    untouched (e.g. KV caches, which get their own placement).
    Returns the block's state-input names.  The one placement loop
    behind both :class:`ShardedPredictor` and the mesh-partitioned
    :class:`~paddle_tpu.serving.generation.GenerationEngine`."""
    import jax
    from jax.sharding import NamedSharding

    from ..framework.executor import analyze_block

    state_in, _ = analyze_block(block, feed_names)
    target = into if into is not None else scope
    skip = set(skip)
    for n in state_in:
        if n in skip:
            continue
        v = scope.find_var(n)
        if v is None:
            raise RuntimeError(
                f"mesh placement: no value for {n!r}; was the "
                "model saved with parameters (or the scope "
                "initialized with the same name prefix)?")
        var = block._find_var_recursive(n)
        shape = var.shape if var is not None else np.shape(v)
        sh = NamedSharding(mesh, rules.spec(n, shape))
        target.set_var(n, jax.device_put(v, sh))
    return list(state_in)


class ShardedPredictor(Predictor):
    """Mesh-partitioned AOT inference: the ``Predictor`` contract over
    a ``jax.sharding.Mesh``.

    ``mesh`` (required) carries any of the canonical axes: weights
    shard per ``rules`` (default :func:`serving_shard_rules` —
    ``mp``/``ep`` last-dim splits), the feed batch dim shards over
    ``batch_axes`` present in the mesh when the batch size divides
    (smaller buckets replicate — a batch of 1 on a dp=4 mesh is
    correct, just not dp-parallel).  Outputs replicate (the host reads
    them whole either way).

    Construction places every state array onto the mesh ONCE
    (``device_put`` per the rule table) into a private scope;
    ``clone()`` shares the placed weights AND the compiled sharded
    executables (``_share_with``), so a pool of clones holds one copy
    of each weight shard and compiles each bucket once.
    """

    def __init__(self, model_dir_or_program, feed_names=None,
                 fetch_vars=None, scope=None, mesh=None,
                 rules: Optional[ShardingRules] = None,
                 batch_axes: Sequence[str] = (DP_AXIS,),
                 model_filename=None, params_filename=None,
                 _share_with: Optional["ShardedPredictor"] = None):
        if mesh is None:
            raise ValueError("ShardedPredictor needs a mesh (use "
                             "parallel.make_mesh / parse_mesh_spec)")
        super().__init__(model_dir_or_program, feed_names, fetch_vars,
                         scope=scope, model_filename=model_filename,
                         params_filename=params_filename)
        self.mesh = mesh
        self.rules = rules or serving_shard_rules(mesh)
        self.batch_axes = tuple(batch_axes)
        self._batch_span = axis_size(mesh, *self.batch_axes)
        # weight-sharded 1-row batches lower matmuls to GEMV, whose
        # accumulation order the backend picks per LOCAL weight shape —
        # the halved shard can select a different kernel than the whole
        # weight and drift the low-order bits.  run()/warmup() keep the
        # generic GEMM path by duplicating the row to batch 2 and
        # slicing the result (the same trick cached_attention uses for
        # its Q=1 scores), which restores bit-exactness vs the
        # unsharded reference at the size-1 bucket.
        self._gemm_pad = axis_size(mesh, MP_AXIS, EP_AXIS) > 1
        if _share_with is not None:
            # mesh-aware Clone(): same placed weight shards, same
            # compiled executables, same lock (the cache is shared, so
            # its guard must be too)
            self._lock = _share_with._lock
            self._cache = _share_with._cache
            self._state_in = _share_with._state_in
            self.scope = _share_with.scope
        else:
            self._place_state()

    # -- placement ----------------------------------------------------------
    def _place_state(self):
        """Shard every state array onto the mesh — once, at
        construction, into a private scope
        (:func:`place_block_state`)."""
        from ..framework.executor import Scope

        placed = Scope()
        self._state_in = place_block_state(
            self._block, self.feed_names, self.scope, self.mesh,
            self.rules, into=placed)
        self.scope = placed

    def _clone_kwargs(self) -> dict:
        return {"mesh": self.mesh, "rules": self.rules,
                "batch_axes": self.batch_axes, "_share_with": self}

    # -- compilation --------------------------------------------------------
    def _fn_and_state(self):
        """Base contract, lowered under the mesh (ops that consult the
        mesh at trace time see it) and reading the PLACED state."""
        import jax

        from ..framework.executor import lower_block

        state_in = self._state_in
        block = self._block
        fetch_names = self.fetch_names
        feed_names = self.feed_names
        seed = self.program.random_seed or 0
        mesh = self.mesh

        def fn(feed_vals, state_vals):
            base_key = jax.random.key(np.uint32(seed))
            env = {}
            env.update(zip(feed_names, feed_vals))
            env.update(zip(state_in, state_vals))
            lower_block(block, env, base_key, is_test=True, mesh=mesh)
            return tuple(env[n] for n in fetch_names)

        state_vals = tuple(self.scope.find_var(n) for n in state_in)
        return fn, state_vals

    def _swap_place(self, name: str, value):
        """Hot-swap placement under the live sharded executables: the
        incoming array re-places per the SAME rule table construction
        used (:func:`place_block_state`), so the swapped weight drops
        into the compiled programs' input shardings unchanged.  Shape
        is already validated equal to the live array's, so the rule
        lookup resolves to the identical spec."""
        import jax
        from jax.sharding import NamedSharding

        var = self._block._find_var_recursive(name)
        shape = var.shape if var is not None else np.shape(value)
        sh = NamedSharding(self.mesh, self.rules.spec(name, shape))
        return jax.device_put(value, sh)

    def _feed_sharding(self, a):
        """Batch dim over the mesh's batch axes when it divides; else
        replicate (correct for every bucket, dp-parallel for the ones
        that span the groups)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        present = tuple(ax for ax in self.batch_axes
                        if ax in self.mesh.axis_names)
        span = self._batch_span
        rows = int(np.shape(a)[0]) if np.ndim(a) >= 1 else 0
        if present and span > 1 and rows >= span and rows % span == 0:
            return NamedSharding(self.mesh, P(present))
        return NamedSharding(self.mesh, P())

    def _compiled_for(self, sig, feed_arrays):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..compile_cache import ensure_compile_cache
        from ..costmodel import executable_manifest

        with self._lock:
            entry = self._cache.get(sig)
            if entry is None:
                ensure_compile_cache()
                fn, state_vals = self._fn_and_state()
                feed_sh = tuple(self._feed_sharding(a)
                                for a in feed_arrays)
                state_sh = tuple(v.sharding for v in state_vals)
                jitted = jax.jit(
                    fn,
                    in_shardings=(feed_sh, state_sh),
                    # outputs replicate: the front end splits them back
                    # into per-request rows on the host either way, and
                    # a replicated fetch reads without a cross-host
                    # gather on np.asarray
                    out_shardings=NamedSharding(self.mesh, P()))
                compiled = jitted.lower(tuple(feed_arrays),
                                        state_vals).compile()
                entry = (compiled, state_vals,
                         executable_manifest(compiled, signature=sig))
                self._cache[sig] = entry
            return entry[0], entry[1]

    # -- serving ------------------------------------------------------------
    def run(self, feed, return_numpy: bool = True):
        """Base contract; 1-row feeds of a weight-sharded predictor run
        at batch 2 via row duplication and slice back (see
        ``_gemm_pad`` above) so every bucket — including size 1 — is
        bit-exact vs the unsharded reference."""
        if not isinstance(feed, dict):
            feed = dict(zip(self.feed_names, feed))
        if self._gemm_pad and all(
                np.ndim(feed[n]) >= 1 and np.shape(feed[n])[0] == 1
                for n in self.feed_names):
            padded = {n: np.concatenate([np.asarray(feed[n])] * 2,
                                        axis=0)
                      for n in self.feed_names}
            outs = [o[:1] for o in super().run(padded,
                                               return_numpy=False)]
            return [np.asarray(o) for o in outs] if return_numpy \
                else outs
        return super().run(feed, return_numpy)

    def warmup(self, feed_shapes) -> int:
        """Base contract, with 1-row signatures promoted to the 2-row
        form :meth:`run` actually executes under GEMM padding — warming
        bucket 1 must prime the executable bucket-1 requests hit, not
        an orphan batch-1 compile."""
        if self._gemm_pad:
            if isinstance(feed_shapes, dict):
                feed_shapes = [feed_shapes]
            feed_shapes = [
                {n: ((2,) + tuple(s)[1:]) if tuple(s)[:1] == (1,)
                 else tuple(s) for n, s in shapes.items()}
                for shapes in feed_shapes]
        return super().warmup(feed_shapes)

    # -- introspection ------------------------------------------------------
    def placement(self, live_ids=None) -> dict:
        """The predictor's shard placement for per-group health: mesh
        axes, device ids, and ``missing_shards`` — mesh devices absent
        from the live device set (``live_ids`` injectable for tests; a
        group with missing shards cannot execute at all and reports
        ``missing_shards`` status in ``/healthz``/``/statusz``)."""
        import jax

        ids = [int(d.id) for d in self.mesh.devices.flat]
        if live_ids is None:
            live_ids = {int(d.id) for d in jax.devices()}
        live = set(int(d) for d in live_ids)
        return {"mesh": describe_mesh(self.mesh), "devices": ids,
                "missing_shards": [d for d in ids if d not in live]}

    def cache_info(self) -> dict:
        """Base inventory + the mesh this predictor is partitioned
        over (axes + device ids) — the /statusz executables block names
        WHICH shard set an executable runs on."""
        info = super().cache_info()
        info["mesh"] = describe_mesh(self.mesh)
        info["devices"] = [int(d.id) for d in self.mesh.devices.flat]
        return info

    def device_ids(self) -> List[int]:
        return [int(d.id) for d in self.mesh.devices.flat]


class ReplicaGroupEngine(ServingEngine):
    """Replica-group serving: dp independent ``mp × ep`` sub-meshes
    under one batching front end.

    The device set splits into ``groups`` disjoint sub-meshes of
    ``mp * ep`` devices; each group gets its own
    :class:`ShardedPredictor` (weights placed on ITS devices) and its
    own dispatch thread pulling from the shared bounded queue —
    admission control, bucketing, deadline shedding, tracing and
    SIGTERM drain are all inherited from :class:`ServingEngine`
    unchanged.  Throughput scales with ``groups``; per-model capacity
    scales with ``mp`` for dense weights (``ep`` shards what ``mp``
    doesn't divide — e.g. expert tables; a weight never splits over
    both axes jointly, see :func:`serving_shard_rules`).

    Topology comes from explicit ``groups`` / ``mp`` / ``ep`` kwargs,
    a ``mesh_spec`` string (``"dp=4,mp=2"``), or ``FLAGS_serving_mesh``
    — in that precedence; ``groups=None`` fills the remaining devices
    (``len(devices) // (mp * ep)``).
    """

    def __init__(self, predictor, groups: Optional[int] = None,
                 mp: Optional[int] = None, ep: Optional[int] = None,
                 mesh_spec: Optional[str] = None, devices=None,
                 rules: Optional[ShardingRules] = None, **engine_kw):
        import jax

        if not isinstance(predictor, Predictor):
            predictor = Predictor(predictor)
        if isinstance(predictor, ShardedPredictor):
            raise ValueError("pass the plain (unplaced) Predictor; the "
                             "engine builds one ShardedPredictor per "
                             "replica group itself")
        # the flag is only consulted (and only then parsed — a
        # malformed flag must not break a fully-kwarg'd constructor)
        # when the kwargs leave part of the topology open
        if mesh_spec is None and (groups is None or mp is None
                                  or ep is None):
            mesh_spec = str(flag_value("FLAGS_serving_mesh") or "")
        spec = parse_mesh_spec(mesh_spec or "")
        unsupported = sorted(set(spec) - {DP_AXIS, MP_AXIS, EP_AXIS})
        if unsupported:
            # a training topology string ('dp=2,pp=4') must not
            # silently serve on a fraction of the intended devices
            raise ValueError(
                f"serving mesh spec {mesh_spec!r} carries axes "
                f"{unsupported} the replica-group engine does not "
                f"serve over; supported: dp (replica groups), mp, ep")
        groups = int(groups if groups is not None
                     else spec.get(DP_AXIS, 0) or 0)
        mp = int(mp if mp is not None else spec.get(MP_AXIS, 1))
        ep = int(ep if ep is not None else spec.get(EP_AXIS, 1))
        devices = list(devices if devices is not None else jax.devices())
        group_size = mp * ep
        if group_size < 1:
            raise ValueError(f"mp={mp} x ep={ep} must be >= 1")
        if not groups:
            groups = len(devices) // group_size
        if groups < 1 or groups * group_size > len(devices):
            raise ValueError(
                f"replica topology dp={groups} x mp={mp} x ep={ep} "
                f"needs {groups * group_size} devices, have "
                f"{len(devices)}")
        self.replica_groups = groups
        self.group_axes = {MP_AXIS: mp, EP_AXIS: ep}
        axes = {a: s for a, s in self.group_axes.items() if s > 1} \
            or {MP_AXIS: 1}
        pool = []
        for g in range(groups):
            sub = devices[g * group_size:(g + 1) * group_size]
            mesh = make_mesh(axes, devices=sub)
            pool.append(ShardedPredictor(
                predictor.program, predictor.feed_names,
                predictor.fetch_names, scope=predictor.scope,
                mesh=mesh, rules=rules,
                # no dp axis inside a group: each group serves whole
                # batches independently — that IS the replica split
                batch_axes=()))
        super().__init__(predictor, pool=pool, **engine_kw)
        telemetry.gauge_set("serving_replica_groups", groups)

    def introspect(self) -> dict:
        out = super().introspect()
        out["replica_groups"] = {
            "groups": self.replica_groups,
            "group_axes": dict(self.group_axes),
            "devices_per_group": int(
                self.group_axes.get(MP_AXIS, 1)
                * self.group_axes.get(EP_AXIS, 1)),
        }
        return out
