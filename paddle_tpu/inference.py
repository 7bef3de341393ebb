"""Inference engine: AOT-compiled Predictor + StableHLO export.

Reference: the analysis predictor stack
(paddle/fluid/inference/api/analysis_predictor.h:82 — AOT program
preparation, zero-copy feeds, Clone()) and the C API surface
(paddle_inference_api.h: CreatePaddlePredictor / config).  The ~37K LoC
of pass-pipeline graph surgery collapses here: XLA is the optimizing
compiler, so "analysis" = lower the inference program once per feed
signature and cache the compiled executable.

  * `Predictor(dirname)` loads a save_inference_model export into its
    own scope, compiles ahead-of-time per feed shape, and serves
    `run(feed) -> outputs`.
  * Weights live as device arrays shared across `clone()`d predictors
    (the reference's shared-weight Clone, zero-copy).
  * `export_stablehlo(path, feed_shapes)` emits the portable StableHLO
    module text; `export_portable(path, feed_shapes)` writes a
    jax.export artifact that a fresh process can load WITHOUT the
    program/params (`load_portable`) — the TPU analog of the reference's
    frozen inference program + zero-copy tensors.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from .framework.core import Program, dtype_to_np
from .framework.executor import Scope, analyze_block, lower_block

__all__ = ["Config", "AnalysisConfig", "Predictor", "SwapMismatch",
           "weights_structure_fingerprint", "create_predictor",
           "load_portable"]


class SwapMismatch(ValueError):
    """A hot-swap checkpoint is structurally incompatible with the live
    weights (missing parameter, shape or dtype drift).  Rejected at
    admission — nothing is applied, the old weights keep serving.  The
    HTTP ``/swap`` endpoint maps this to 409, exactly like a
    :class:`~paddle_tpu.serving.disagg.SegmentMismatch`."""


def weights_structure_fingerprint(doc: Dict[str, tuple]) -> str:
    """sha256 fingerprint of a ``name -> (shape, dtype)`` weight-table
    structure — the swap-admission sibling of
    :func:`~paddle_tpu.serving.disagg.config_fingerprint`: equal
    fingerprints mean a checkpoint's arrays drop into the live
    compiled executables without recompilation or reshape."""
    import hashlib
    import json

    payload = {n: [list(int(d) for d in shape), str(dtype)]
               for n, (shape, dtype) in doc.items()}
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()[:24]


def _weight_doc(named_arrays) -> Dict[str, tuple]:
    """``name -> (shape, dtype)`` without forcing device arrays to
    host (np.shape / .dtype are metadata reads on jax arrays)."""
    doc = {}
    for n, v in named_arrays:
        dt = getattr(v, "dtype", None)
        if dt is None:
            dt = np.asarray(v).dtype
        doc[n] = (tuple(np.shape(v)), str(np.dtype(dt)))
    return doc


class Config:
    """Mirror of the reference AnalysisConfig surface (model paths +
    switches; accelerator switches are advisory — XLA owns codegen)."""

    def __init__(self, model_dir: Optional[str] = None,
                 model_filename: Optional[str] = None,
                 params_filename: Optional[str] = None):
        self.model_dir = model_dir
        self.model_filename = model_filename
        self.params_filename = params_filename

    # reference-API no-ops kept for parity
    def enable_use_gpu(self, *a, **k):
        pass

    def disable_gpu(self):
        pass

    def switch_ir_optim(self, flag=True):
        pass

    def enable_memory_optim(self):
        pass


AnalysisConfig = Config


class Predictor:
    """AOT inference over a loaded program (analysis_predictor.h:82)."""

    def __init__(self, model_dir_or_program, feed_names=None,
                 fetch_vars=None, scope: Optional[Scope] = None,
                 model_filename=None, params_filename=None):
        from . import io

        if isinstance(model_dir_or_program, Program):
            program = model_dir_or_program
            if feed_names is None or fetch_vars is None:
                raise ValueError("program-based Predictor needs feed_names "
                                 "and fetch_vars")
            self.scope = scope or Scope()
        else:
            # load program + params directly into OUR scope: serving must
            # never touch (or clobber) a live training process's global
            # scope (the reference predictor owns a private Scope too,
            # analysis_predictor.cc scope_)
            self.scope = scope or Scope()
            dirname = model_dir_or_program
            program, meta = io._load_model_payload(dirname, model_filename)
            params_path = os.path.join(dirname,
                                       params_filename or "__params__")
            if os.path.exists(params_path):
                for name, val in io._read(params_path).items():
                    self.scope.set_var(name, val)
            feed_names = meta["feeds"]
            fetch_vars = meta["fetches"]
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = [getattr(v, "name", v) for v in fetch_vars]
        self._block = program.global_block()
        self._cache: Dict[tuple, object] = {}
        self._state_in = None
        # last successful swap's replaced arrays (name -> device array):
        # the single-level undo revert_weights() restores — retained so
        # a canary revert is an instant in-memory flip, no checkpoint
        # round-trip.  Costs one old model of HBM until the next swap.
        self._prev_weights: Optional[Dict[str, object]] = None
        # run() is thread-safe: the per-shape compile cache (and the lazy
        # _state_in analysis) are guarded by this lock, so N threads can
        # share ONE predictor — first compile of a signature serializes,
        # steady-state is one lock acquire around a dict hit.  clone()d
        # predictors each get their own lock (and own cache); the shared
        # scope arrays are read-only at serve time.
        self._lock = threading.RLock()

    # -- reference-API accessors -------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self.feed_names)

    def get_output_names(self) -> List[str]:
        return list(self.fetch_names)

    # -- compilation --------------------------------------------------------
    def _fn_and_state(self):
        """The pure (feeds, state) -> fetches function + state binding."""
        import jax

        with self._lock:
            if self._state_in is None:
                state_in, _ = analyze_block(self._block, self.feed_names)
                self._state_in = state_in

        state_in = self._state_in
        block = self._block
        fetch_names = self.fetch_names
        feed_names = self.feed_names
        seed = self.program.random_seed or 0

        def fn(feed_vals, state_vals):
            base_key = jax.random.key(np.uint32(seed))
            env = {}
            env.update(zip(feed_names, feed_vals))
            env.update(zip(state_in, state_vals))
            lower_block(block, env, base_key, is_test=True)
            return tuple(env[n] for n in fetch_names)

        state_vals = []
        for n in state_in:
            v = self.scope.find_var(n)
            if v is None:
                raise RuntimeError(f"predictor: no value for {n!r}; was "
                                   "the model saved with parameters?")
            state_vals.append(v)
        return fn, tuple(state_vals)

    def _compiled_for(self, sig, feed_arrays):
        import jax

        from .compile_cache import ensure_compile_cache
        from .costmodel import executable_manifest

        with self._lock:
            entry = self._cache.get(sig)
            if entry is None:
                ensure_compile_cache()
                fn, state_vals = self._fn_and_state()
                jitted = jax.jit(fn)
                # AOT: compile now, at this signature.  Compiling under
                # the lock means two racing threads can't both miss and
                # build duplicate executables for the same signature.
                compiled = jitted.lower(tuple(feed_arrays), state_vals
                                        ).compile()
                # executable manifest (flops / bytes / peak HBM) rides
                # the cache entry into cache_info() -> /statusz
                entry = (compiled, state_vals,
                         executable_manifest(compiled, signature=sig))
                self._cache[sig] = entry
            return entry[0], entry[1]

    def _prepare(self, feed):
        arrays = []
        for n in self.feed_names:
            a = np.asarray(feed[n])
            v = self._block.var(n)
            want = dtype_to_np(v.dtype)
            if a.dtype != want:
                a = a.astype(want)
            arrays.append(a)
        sig = tuple((a.shape, str(a.dtype)) for a in arrays)
        return arrays, sig

    # -- serving ------------------------------------------------------------
    def run(self, feed, return_numpy: bool = True):
        """feed: dict name->array, or list aligned with get_input_names."""
        if not isinstance(feed, dict):
            feed = dict(zip(self.feed_names, feed))
        arrays, sig = self._prepare(feed)
        compiled, state_vals = self._compiled_for(sig, arrays)
        outs = compiled(tuple(arrays), state_vals)
        if return_numpy:
            return [np.asarray(o) for o in outs]
        return list(outs)

    def warmup(self, feed_shapes) -> int:
        """Pre-compile AND prime the given feed signatures (off the
        request path): ``feed_shapes`` is one ``{feed_name: shape}``
        dict or a list of them.  Dtypes come from the program's feed
        vars.  Each newly compiled executable is also run once on zero
        feeds (result discarded): the first execution pays one-time
        costs beyond compilation (runtime autotuning, thread-pool /
        allocator spin-up) that must not land on a real request.
        Returns the number of signatures compiled now (already-cached
        ones are free).  The serving engine uses this to warm every
        batch bucket at startup; direct users call it to move the
        first-request latency spike out of the serving path.  Priming
        goes through :meth:`_compiled_for` and the compiled call, so a
        mesh-partitioned subclass warms every bucket ON ITS MESH (the
        zero feeds flow through the executable's input shardings), not
        just device 0."""
        if isinstance(feed_shapes, dict):
            feed_shapes = [feed_shapes]
        compiled = 0
        for shapes in feed_shapes:
            arrays = []
            for n in self.feed_names:
                want = dtype_to_np(self._block.var(n).dtype)
                arrays.append(np.zeros(tuple(shapes[n]), dtype=want))
            sig = tuple((a.shape, str(a.dtype)) for a in arrays)
            with self._lock:
                hit = sig in self._cache
            if not hit:
                executable, state_vals = self._compiled_for(sig, arrays)
                executable(tuple(arrays), state_vals)
                compiled += 1
        return compiled

    def cache_info(self) -> dict:
        """Compiled-executable inventory for live introspection (the
        serving ``/statusz`` endpoint), each signature with its
        executable manifest (flops / bytes accessed / peak HBM from
        XLA cost+memory analysis; None where the backend exposes
        none).  Non-blocking by design: the cache lock is held for the
        full duration of an XLA compile, and a status probe must never
        stall behind one — on contention this reports ``busy: True``
        instead of waiting."""
        from .costmodel import manifest_summary

        if not self._lock.acquire(timeout=0.05):
            return {"compiled": None, "busy": True}
        try:
            entries = [(s, e[2] if len(e) > 2 else None)
                       for s, e in self._cache.items()]
        finally:
            self._lock.release()
        return {"compiled": len(entries),
                "signatures": sorted(str(s) for s, _ in entries),
                "manifests": {str(s): manifest_summary(m)
                              for s, m in sorted(entries,
                                                 key=lambda x: str(x[0]))}}

    # -- in-place weight hot-swap -------------------------------------------
    def _ensure_state_in(self) -> List[str]:
        with self._lock:
            if self._state_in is None:
                state_in, _ = analyze_block(self._block, self.feed_names)
                self._state_in = state_in
            return self._state_in

    def weights_doc(self) -> Dict[str, tuple]:
        """``name -> (shape, dtype)`` of the live executor-state
        weights — the structure a swap checkpoint must match."""
        state_in = self._ensure_state_in()
        pairs = []
        for n in state_in:
            v = self.scope.find_var(n)
            if v is None:
                raise RuntimeError(f"predictor: no value for {n!r}; was "
                                   "the model saved with parameters?")
            pairs.append((n, v))
        return _weight_doc(pairs)

    def weights_fingerprint(self) -> str:
        """Structural sha256 of the live weight table (see
        :func:`weights_structure_fingerprint`)."""
        return weights_structure_fingerprint(self.weights_doc())

    def _swap_place(self, name: str, value):
        """Device placement for one incoming weight.  The sharded
        subclass overrides this to re-place per its ShardingRules so
        the swapped arrays drop into the same mesh-partitioned
        executables."""
        import jax

        return jax.device_put(value)

    def _rebind_cache_locked(self):
        """Point every cached executable's state tuple at the CURRENT
        scope arrays (call with the lock held, after the scope flip)."""
        if self._state_in is None or not self._cache:
            return
        vals = tuple(self.scope.find_var(n) for n in self._state_in)
        for sig, entry in list(self._cache.items()):
            self._cache[sig] = (entry[0], vals,
                                entry[2] if len(entry) > 2 else None)

    def swap_weights(self, checkpoint, *, params_filename=None) -> dict:
        """Hot-swap the weights under the live compiled executables —
        zero recompiles, validated before anything is applied.

        ``checkpoint``: a ``save_inference_model``-style directory
        (its ``__params__`` pickle) or a ``name -> array`` dict.
        Every executor-state weight must be present with the exact
        live shape and dtype; any drift raises :class:`SwapMismatch`
        with both structural fingerprints and nothing applied.  The
        commit (device placement + scope flip + executable-state
        rebind) runs under the predictor lock; a failure mid-commit
        (the ``weight_swap`` fault site fires per array) rolls back
        to the old arrays — a torn mix is never observable.  The
        replaced arrays are retained for :meth:`revert_weights`."""
        from . import fault, io

        if isinstance(checkpoint, str):
            path = os.path.join(checkpoint,
                                params_filename or "__params__")
            if not os.path.exists(path):
                raise SwapMismatch(
                    f"swap checkpoint {checkpoint!r} has no "
                    f"{params_filename or '__params__'}")
            new = io._read(path)
        else:
            new = dict(checkpoint)
        live_doc = self.weights_doc()
        problems = []
        for n, (shape, dtype) in live_doc.items():
            if n not in new:
                problems.append(f"{n}: missing from checkpoint")
                continue
            got_shape = tuple(np.shape(new[n]))
            got_dt = getattr(new[n], "dtype", None)
            got_dtype = str(np.dtype(got_dt)) if got_dt is not None \
                else str(np.asarray(new[n]).dtype)
            if got_shape != shape:
                problems.append(f"{n}: shape {got_shape} != live {shape}")
            elif got_dtype != dtype:
                problems.append(f"{n}: dtype {got_dtype} != live {dtype}")
        if problems:
            new_doc = _weight_doc([(n, v) for n, v in new.items()
                                   if n in live_doc])
            raise SwapMismatch(
                f"checkpoint structure "
                f"{weights_structure_fingerprint(new_doc)} != live "
                f"{weights_structure_fingerprint(live_doc)}: "
                + "; ".join(problems[:4])
                + (f" (+{len(problems) - 4} more)"
                   if len(problems) > 4 else ""))
        state_in = self._ensure_state_in()
        old_vals: Dict[str, object] = {}
        with self._lock:
            try:
                for n in state_in:
                    kind = fault.fire("weight_swap")
                    fault.maybe_delay(kind)
                    if kind == "fail":
                        raise fault.InjectedFault(
                            f"injected weight_swap failure at {n!r}")
                    old_vals[n] = self.scope.find_var(n)
                    self.scope.set_var(n, self._swap_place(n, new[n]))
                self._rebind_cache_locked()
            except BaseException:
                # roll back: restore every already-flipped array and
                # rebind the executables to the restored scope — the
                # old weights keep serving, never a torn mix
                for n, v in old_vals.items():
                    self.scope.set_var(n, v)
                self._rebind_cache_locked()
                raise
            self._prev_weights = old_vals
        return {"replaced": len(state_in),
                "fingerprint": weights_structure_fingerprint(live_doc)}

    def revert_weights(self) -> dict:
        """Restore the arrays the last successful :meth:`swap_weights`
        replaced (single-level, in-memory — the canary auto-revert
        path).  Raises :class:`SwapMismatch` when no prior swap left
        anything to revert to."""
        prev = self._prev_weights
        if not prev:
            raise SwapMismatch("nothing to revert: no prior successful "
                               "swap retained its replaced weights")
        return self.swap_weights(prev)

    def rebind_weights(self):
        """Rebind this predictor's cached executables to the current
        scope arrays — the follow-up call for clones SHARING a scope
        another predictor just swapped (their executables still hold
        the old state tuples)."""
        with self._lock:
            self._rebind_cache_locked()

    def _clone_kwargs(self) -> dict:
        """Extra constructor kwargs a clone must inherit.  Subclasses
        with placement state (the mesh-partitioned ShardedPredictor)
        override this so ``clone()`` reproduces their device placement
        instead of silently degrading to single-device."""
        return {}

    def clone(self) -> "Predictor":
        """Shared-weight clone (zero-copy: same scope arrays), private
        compile cache — the reference Clone() contract.  Mesh-aware:
        constructs ``type(self)`` with :meth:`_clone_kwargs`, so a
        sharded predictor's clone shares its sharded executables and
        mesh-placed device weights rather than re-assuming device 0."""
        p = type(self)(self.program, self.feed_names, self.fetch_names,
                       scope=self.scope, **self._clone_kwargs())
        return p

    # -- export -------------------------------------------------------------
    def _abstract_args(self, feed_shapes: Dict[str, Sequence[int]]):
        import jax

        feeds = []
        for n in self.feed_names:
            v = self._block.var(n)
            feeds.append(jax.ShapeDtypeStruct(
                tuple(feed_shapes[n]), dtype_to_np(v.dtype)))
        return tuple(feeds)

    def export_stablehlo(self, path: str,
                         feed_shapes: Dict[str, Sequence[int]]) -> str:
        """Emit the StableHLO module text at the given feed shapes
        (portable IR for external toolchains; reference analog: the
        frozen __model__ program)."""
        import jax

        fn, state_vals = self._fn_and_state()
        lowered = jax.jit(fn).lower(self._abstract_args(feed_shapes),
                                    state_vals)
        text = lowered.as_text(dialect="stablehlo")
        with open(path, "w") as f:
            f.write(text)
        return text

    def export_portable(self, path: str,
                        feed_shapes: Dict[str, Sequence[int]]):
        """jax.export artifact: weights baked in as constants, loadable
        in a fresh process with ``load_portable`` (no program, no params
        directory needed)."""
        import jax
        from jax import export as jexport

        fn, state_vals = self._fn_and_state()

        def closed(*feed_vals):
            return fn(feed_vals, state_vals)

        exported = jexport.export(jax.jit(closed))(
            *self._abstract_args(feed_shapes))
        blob = exported.serialize()
        meta = {"feeds": self.feed_names, "fetches": self.fetch_names}
        import json
        with open(path, "wb") as f:
            head = json.dumps(meta).encode()
            f.write(len(head).to_bytes(4, "big") + head + blob)


class _PortablePredictor:
    """Serves a jax.export artifact (see Predictor.export_portable)."""

    def __init__(self, path: str):
        import json
        from jax import export as jexport

        with open(path, "rb") as f:
            n = int.from_bytes(f.read(4), "big")
            meta = json.loads(f.read(n).decode())
            self._exported = jexport.deserialize(bytearray(f.read()))
        self.feed_names = meta["feeds"]
        self.fetch_names = meta["fetches"]

    def get_input_names(self):
        return list(self.feed_names)

    def get_output_names(self):
        return list(self.fetch_names)

    def run(self, feed, return_numpy: bool = True):
        if not isinstance(feed, dict):
            feed = dict(zip(self.feed_names, feed))
        args = [np.asarray(feed[n]) for n in self.feed_names]
        outs = self._exported.call(*args)
        if return_numpy:
            return [np.asarray(o) for o in outs]
        return list(outs)


def load_portable(path: str) -> _PortablePredictor:
    return _PortablePredictor(path)


def create_predictor(config: Config) -> Predictor:
    """reference CreatePaddlePredictor(config)."""
    return Predictor(config.model_dir,
                     model_filename=config.model_filename,
                     params_filename=config.params_filename)


create_paddle_predictor = create_predictor
