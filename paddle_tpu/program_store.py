"""The lowered module of a Program's step, kept beside the compile cache.

A warm process used to trace every Program to a jaxpr and lower every
Pallas kernel to Mosaic again, 14-23 s of every benchmark cell's set-up,
to produce a module whose executable the persistent cache already held.
The store keeps the module: ``jax.export`` serialises the lowered step
where a process builds a Program first, and a later process that builds the
same Program loads it in place of tracing.

**Where.**  ``<compile cache dir>/programs/``, wherever
:func:`compile_cache.ensure_compile_cache` put the cache (jax's own cache
lists and evicts only ``*-cache`` files of its directory, never a
subdirectory).  Where that rule places no cache (the CPU, unless
``JAX_COMPILATION_CACHE_DIR`` is set) there is no store, no file is made
and ``costmodel.aot_compile`` is ``jitted.lower(*args).compile()``.  No
flag.  (The devices decide what "the CPU" is, as they do in the key: a test
that answers ``jax.default_backend()`` with "tpu" over the CPU's devices
gets the cache's directory and no store.)

**One path, hit or miss.**  A miss exports the jitted step (the one trace
and lowering a process paid before), writes the bytes under a temporary
name and renames them; a hit reads them.  Both then hand
``costmodel.aot_compile`` the same thing, a ``jit`` of the deserialised
module's call (:func:`wrapped`) under the step's own name and donation, so
the filling run and every warm run give XLA the same module and the
persistent cache the same key: a warm run is a store hit plus a cache hit.
The executable is the one the step's own ``jit`` compiles to
(``tools/program_hash.py --compiled``), and its results are as uncommitted
as the step's own (:func:`_stored_call_p` says why that takes a primitive
of this package's).

**The key** is complete without a trace, since a stale module is a wrong
program: sha256 over ``serde.program_to_json(program)`` (blocks, ops,
attrs, vars, ``random_seed``, ``_amp_lowering``: all ``lower_block``
reads of a Program), the feed, fetch and guard names, every argument's
abstract value and the arguments' tree, a digest of every ``*.py`` under
``paddle_tpu/`` (and of the file of any lowering registered from outside
it), the versions of jax, jaxlib and the device's runtime,
the device kind and count, the export's calling convention and the jax
options a trace reads.  **Flags** in a second step: the flags the filling
trace READ (``watch.py``) are stored with the module, names and
values, and compared on load; a run that sets a flag no lowering reads
still hits.  A load that fails (a truncated file, a version refused, a
flag that differs) is a miss that overwrites.

**A step over a mesh** (``parallel/sharded.py``: what a step builder
returns answers ``.lower(*args)`` from here) hands :func:`stored_step` its
mesh and what its ``jit`` was given.  Its key also holds the mesh's axis
names and shape, its devices' kind, every argument's and result's
``PartitionSpec`` and the compiler options; its module is kept for the
mesh's devices (``Exported.nr_devices``), and the stored call's ``jit``
gets the shardings, donation and compiler options the step's own did.

**What a trace does besides making the module** is stored beside it and
done again on a hit: the stats a lowering books (``attention_lowered_*``,
``kv_pool_write_pages``, ...; ``watch.py``) are added, the
warnings it logs (the once-a-reason downgrade lines) are logged, each once
a process.

**Refused**, each counted (``program_store_refused``) and logged once with
its reason, falling to ``jitted.lower(*args).compile()``: an argument
that lives on more than one device where no mesh was handed in (an
``Executor``'s step over state placed on a mesh), a kept module for
another number of devices than the mesh has, a module with
effects or host callbacks (``jax.export`` serialises neither), a Program
that has no key (attributes that are not JSON, a lowering from outside the
package without a source file), and ``FLAGS_check_nan_inf`` runs, which
make no module.

Stats ``program_store_hits`` / ``_misses`` / ``_refused``; one
``compile/program_store`` span a program in the start-up account (``hit``,
``bytes``, ``load_ms``, ``devices``, and the ``program`` / ``kind`` /
``bucket`` of the spans it is under), whose self time leaves out the trace
and lowering a miss holds.
"""
from __future__ import annotations

import functools
import hashlib
import json
import logging
import os
import threading
import time
from typing import Optional

from . import flags as _flags
from . import telemetry as _telemetry
from . import watch as _watch
from .compile_cache import ENV_VAR, ensure_compile_cache
from .monitor import monitor as _monitor

__all__ = ["directory", "program_digest", "stored_step", "export_step",
           "wrapped", "refuse"]

logger = logging.getLogger(__name__)

SUBDIR = "programs"
_MAGIC = b"paddle_tpu program 1\n"
_HITS = _monitor.get("program_store_hits")
_MISSES = _monitor.get("program_store_misses")
_REFUSED = _monitor.get("program_store_refused")
# the jax options a trace reads (``jax.config``)
_TRACE_OPTIONS = ("jax_enable_x64", "jax_default_matmul_precision",
                  "jax_default_prng_impl", "jax_threefry_partitionable",
                  "jax_numpy_dtype_promotion", "jax_numpy_rank_promotion",
                  "jax_export_calling_convention_version")
_INHERIT = ("program", "kind", "bucket")

_lock = threading.Lock()
_export_lock = threading.Lock()
_said = set()          # refusal reasons and replayed lines logged so far


def directory() -> Optional[str]:
    """Where the store lives, or None: no cache placed, no store.  The
    cache's rule asks ``jax.default_backend()``; the store also asks the
    devices, since its key does: a backend answered for over the CPU's
    devices (the tests' way to the kernel route) places no store."""
    import jax

    cache = ensure_compile_cache()
    if not cache or (not os.environ.get(ENV_VAR)
                     and jax.devices()[0].platform == "cpu"):
        return None
    return os.path.join(cache, SUBDIR)


def _once(key) -> bool:
    with _lock:
        if key in _said:
            return False
        _said.add(key)
        return True


def refuse(reason: str):
    """Count a step the store does not take and log the reason once;
    nothing where no store is placed."""
    if directory() is None:
        return
    _REFUSED.increase()
    if _once(("refused", reason)):
        logger.warning("program store: refused (the step is traced and "
                       "lowered as before): %s", reason)


@functools.lru_cache(maxsize=None)
def _source_digest() -> str:
    """sha256 over every ``*.py`` under ``paddle_tpu/``, once a process: a
    tree never loads a module another tree's sources made."""
    root = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, root).encode() + b"\0")
                with open(path, "rb") as f:
                    h.update(f.read() + b"\0")
    return h.hexdigest()


def _versions() -> dict:
    import jax
    import jaxlib

    device = jax.devices()[0]
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "runtime": device.client.platform_version,
            "device_kind": device.device_kind,
            "devices": jax.device_count(),
            "options": {k: str(getattr(jax.config, k))
                        for k in _TRACE_OPTIONS}}


def _outside_lowerings(program) -> list:
    """``[op type, sha256 of the file that defines its lowering]`` for
    every op of ``program`` whose lowering lives outside this package (a
    user's ``register_op``): the sources' digest does not cover it.
    ValueError where such a lowering has no source file."""
    import inspect

    from .ops.registry import get_op_def

    package = __name__.partition(".")[0] + "."
    out = []
    for op_type in sorted({op.type for block in program.blocks
                           for op in block.ops}):
        if op_type in ("feed", "fetch"):
            continue
        lower = get_op_def(op_type).lower
        if (getattr(lower, "__module__", None) or "").startswith(package):
            continue
        try:
            with open(inspect.getsourcefile(lower), "rb") as f:
                out.append([op_type, hashlib.sha256(f.read()).hexdigest()])
        except (TypeError, OSError) as e:
            raise ValueError(f"the lowering of {op_type!r} is defined "
                             f"outside {package[:-1]} and has no source "
                             f"file ({e})")
    return out


def program_digest(program, feed_names, fetch_names,
                   guard_loss) -> Optional[str]:
    """The Program's half of the key, made where the step is built; None
    where there is no store or the Program cannot be keyed (refused)."""
    if directory() is None:
        return None
    from .framework.serde import program_to_json

    try:
        text = program_to_json(program)
        outside = _outside_lowerings(program)
    except (KeyError, TypeError, ValueError) as e:
        refuse(f"the Program has no key ({e})")
        return None
    h = hashlib.sha256(text.encode())
    h.update(json.dumps([list(feed_names), list(fetch_names), guard_loss,
                         outside]).encode())
    return h.hexdigest()


def _key(digest: str, args, donate_argnums, placement=None) -> str:
    import jax

    leaves, tree = jax.tree_util.tree_flatten(args)
    parts = {"program": digest, "tree": str(tree),
             "avals": [repr(jax.typeof(leaf)) for leaf in leaves],
             "donate": list(donate_argnums),
             "source": _source_digest(), "versions": _versions()}
    if placement is not None:   # (a step under a mesh: ``_placement``)
        parts["placement"] = placement
    return hashlib.sha256(json.dumps(
        parts, sort_keys=True, default=str).encode()).hexdigest()


def _placement(mesh, jit_kwargs) -> dict:
    """What a step built over ``mesh`` adds to the key: the mesh's axes and
    shape and its devices' kind (they need not be ``jax.devices()``: a
    described topology), and everything the step's ``jit`` was given
    besides the function, a sharding as its ``PartitionSpec``."""
    import jax

    device = mesh.devices.flat[0]
    return {"axes": mesh.axis_names, "shape": mesh.devices.shape,
            "device": [device.platform, device.device_kind],
            "jit": jax.tree_util.tree_map(
                lambda x: str(x.spec) if hasattr(x, "spec") else x,
                jit_kwargs)}


def _on_many_devices(args) -> bool:
    import jax

    return any(len(getattr(getattr(leaf, "sharding", None), "device_set",
                           ())) > 1
               for leaf in jax.tree_util.tree_leaves(args))


class _Warnings(logging.Handler):
    """The warnings this thread's trace logs under ``paddle_tpu``."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.thread = threading.get_ident()
        self.lines = []

    def emit(self, record):
        if record.thread == self.thread:
            self.lines.append([record.name, record.levelno,
                               record.getMessage()])


def _keepable(exported, devices: int):
    """``exported``, or ValueError where it is no module the store keeps
    for a step over ``devices`` devices."""
    if exported.nr_devices != devices:
        raise ValueError(f"a module for {exported.nr_devices} devices, "
                         f"where the step runs on {devices}")
    if exported.ordered_effects or exported.unordered_effects:
        raise ValueError("a module with effects")
    return exported


def _load(path: str, devices: int):
    """``(exported, meta)`` of the entry at ``path``, or None: no file, a
    file cut short or changed, a flag the filling trace read that reads
    otherwise now, bytes this jax refuses.  ValueError where the module
    loads and is for another number of devices than ``devices``."""
    from jax import export

    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return None
    try:
        magic, head, blob = data.split(b"\n", 2)
        meta = json.loads(head)
        if magic + b"\n" != _MAGIC or len(blob) != meta["bytes"] \
                or hashlib.sha256(blob).hexdigest() != meta["sha256"]:
            return None
        for name, value in meta["flags"].items():
            if _flags.flag_value(name) != value:
                return None
        exported = export.deserialize(bytearray(blob))
    except Exception as e:  # noqa: BLE001 — whatever is wrong, it is a miss
        logger.debug("program store: %s does not load: %r", path, e)
        return None
    return _keepable(exported, devices), meta


def export_step(jitted, args, platforms=None):
    """``jax.export`` of ``jitted`` at ``args``: its one trace and lowering.
    A stored module is only ever loaded by the versions that made it (they
    are in the key), so the export does not lower for older readers:
    ``jax_export_ignore_forward_compatibility`` on, and a Pallas kernel is
    the Mosaic module ``jitted.lower`` makes of it, not an older
    serialisation of it."""
    import jax
    from jax import export

    option = "jax_export_ignore_forward_compatibility"
    with _export_lock:      # (the option is the process's, not a thread's)
        old = getattr(jax.config, option)
        jax.config.update(option, True)
        try:
            return export.export(jitted, platforms=platforms)(*args)
        finally:
            jax.config.update(option, old)


def _fill(jitted, args, path: str, devices: int, platforms=None):
    """Export ``jitted`` at ``args``, keep the module with what the trace
    read and booked at ``path``, and return ``(exported, meta)`` as a later
    load would."""
    from jax import export

    warnings = _Warnings()
    package = logging.getLogger(__name__.partition(".")[0])
    package.addHandler(warnings)
    try:
        with _watch.watching() as seen:
            exported = export_step(jitted, args, platforms)
    finally:
        package.removeHandler(warnings)
    blob = bytes(_keepable(exported, devices).serialize())
    for line in warnings.lines:
        _once(("said", line[0], line[2]))
    meta = {"bytes": len(blob), "sha256": hashlib.sha256(blob).hexdigest(),
            "flags": seen.flags, "stats": seen.stats,
            "logs": warnings.lines}
    tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(_MAGIC + json.dumps(meta).encode() + b"\n" + blob)
        os.replace(tmp, path)
    except OSError as e:
        if _once(("write", type(e).__name__)):
            logger.warning("program store: cannot write %s: %s", path, e)
        try:
            os.remove(tmp)
        except OSError:
            pass  # ok: tmp may never have been made
    # (the module the next process will load, not the one in memory)
    return export.deserialize(bytearray(blob)), meta


def _replay(meta: dict):
    """What the trace that made the module did besides making it."""
    for name, n in meta["stats"].items():
        _monitor.get(name).increase(n)
    for name, level, message in meta["logs"]:
        if _once(("said", name, message)):
            logging.getLogger(name).log(level, message)


def stored_step(jitted, args, digest: str, donate_argnums=(), mesh=None,
                **jit_kwargs):
    """What ``costmodel.aot_compile`` and a sharded step's ``.lower``
    (``parallel/sharded.py``) lower and compile in place of ``jitted``: a
    ``jit`` of the stored module's call, loaded (a hit) or made and kept
    now (a miss); ``jitted`` itself where the store refuses the step.
    ``digest`` is ``program_digest``'s: a store is placed.  A step built
    over a mesh hands in the ``mesh`` and the ``jit_kwargs`` its own ``jit``
    was given besides the donation (shardings, compiler options): they are
    in the key, the module is kept for the mesh's devices, and the stored
    call's ``jit`` gets them as the step's did."""
    if mesh is None and _on_many_devices(args):
        refuse("an argument lives on more than one device (a step under "
               "a mesh the store was not handed)")
        return jitted
    devices = 1 if mesh is None else mesh.size
    with _telemetry.startup_span("compile/program_store", inherit=_INHERIT,
                                 devices=devices) as span:
        try:
            placement = platforms = None
            if mesh is not None:
                placement = _placement(mesh, jit_kwargs)
                platforms = (mesh.devices.flat[0].platform,)
            path = os.path.join(directory(), _key(
                digest, args, donate_argnums, placement) + ".bin")
            t0 = time.perf_counter()
            got = _load(path, devices)
            load_ms = (time.perf_counter() - t0) * 1e3
            hit = got is not None
            if not hit:
                got = _fill(jitted, args, path, devices, platforms)
            exported, meta = got
            step = wrapped(exported, donate_argnums, **jit_kwargs)
        except Exception as e:  # noqa: BLE001 — any refusal of
            # jax.export's (a host callback, a custom call off its list):
            # today's path still compiles the step
            refuse(f"{type(e).__name__}: {e}".splitlines()[0][:200])
            span.attrs.update(hit=0, refused=1)
            return jitted
        if hit:
            _replay(meta)
        (_HITS if hit else _MISSES).increase()
        span.attrs.update(hit=int(hit), bytes=meta["bytes"],
                          load_ms=round(load_ms, 3))
    return step


@functools.lru_cache(maxsize=None)
def _stored_call_p():
    """The primitive a stored module is called through: jax's own lowering
    of ``Exported.call`` under a name of this package's.  Not
    ``exported.call`` itself: jax commits to one device the results of any
    ``jit`` that holds its ``call_exported`` (``pxla`` asks the jaxpr for
    the primitive by name), where the step's own ``jit`` leaves them as
    uncommitted as its arguments, and a start-up program's state committed
    to device 0 is refused by a step compiled over a mesh of four.  The
    lowering has no public name; a jax without it raises here, and the
    store refuses the step."""
    from jax._src.export._export import _call_exported_lowering
    from jax.extend.core import Primitive
    from jax.interpreters import mlir

    p = Primitive("stored_step")
    p.multiple_results = True
    p.def_abstract_eval(lambda *_avals, exported: exported.out_avals)
    mlir.register_lowering(p, _call_exported_lowering)
    return p


def wrapped(exported, donate_argnums=(), **jit_kwargs):
    """A ``jit`` of the call of ``exported``'s module under the exported
    function's own name (the module is ``jit_step_fn`` as before, and the
    account's rows and ``program_label`` read the name),
    ``donate_argnums`` and whatever else the step's own ``jit`` was given
    (``jit_kwargs``: a sharded step's shardings and compiler options).  An
    argument the module does not take (``jit`` drops the unused: a step
    that draws nothing never reads its step number) is not handed to the
    call either, so this ``jit`` drops it too and the executable has the
    parameters it had."""
    import jax
    import jax.numpy as jnp

    call_p = _stored_call_p()
    kept = set(exported.module_kept_var_idx)

    def call(*args):
        leaves, tree = jax.tree_util.tree_flatten((args, {}))
        if tree != exported.in_tree:
            raise ValueError(f"the stored module takes {exported.in_tree}, "
                             f"not {tree}")
        leaves = [x if i in kept else jnp.zeros(x.shape, x.dtype)
                  for i, x in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(
            exported.out_tree, call_p.bind(*leaves, exported=exported))

    call.__name__ = call.__qualname__ = exported.fun_name
    return jax.jit(call, donate_argnums=tuple(donate_argnums), **jit_kwargs)
