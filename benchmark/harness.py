"""What every cell's run shares: finding a cell's files, the device check,
the compile cache and compile counting, set-up phases, the profiler
window, the per-layer readers and the result line.

How a cell's files are found (``PERF.md`` section 3 says the same):
``BENCHMARK.json`` ``workloads[name]`` gives ``config``, ``traffic`` and
``chips``; the configuration is ``benchmark/configs/<config>.json`` with
its plain reference ``benchmark/reference/<config>.py``, and its
``builder`` names ``benchmark/builders/<builder>.py`` (published keys ->
the program); the traffic mix is ``benchmark/traffic/<traffic>.json``,
whose ``driver`` names the module ``benchmark/<driver>.py`` that runs it;
a per-layer metric ``m`` is ``benchmark/metrics/<m>.json``, whose
``reader`` names ``benchmark/readers/<reader>.py`` and whose ``args`` may
name a function ``<module>.<function>`` of a module under ``benchmark/``
(``resolve``).  A cell reports the end-to-end and per-layer metrics of
``BENCHMARK.json`` that have no ``workloads`` key or list the cell under
it.

One entry a family (PR 38, PR 55): a metric's file holds the reader and
the arguments every cell shares; what differs by cell comes from the
cell's own files, under the key ``per_layer_args`` and the entry's name:
``benchmark/configs/<config>.json`` where it follows from the model (the
``ops_bytes*`` function that counts its work, the span attributes that
function takes, the key that counts its experts), then
``benchmark/traffic/<traffic>.json`` where it follows from the engine the
mix asks for (100 / slots) (``Cell.reader_of``).  So a later cell joins a
family by appending its name to the entry's ``workloads`` and bringing
its arguments in its own new files; it edits no file that is there.
"""
from __future__ import annotations

import functools
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import traffic  # noqa: E402  (numpy only)

def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module; names may hold dots and
    dashes, so it is loaded by path."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(dotted: str):
    """``"module.function"`` of a module under ``benchmark/`` (which is on
    the path), so that a data file can name the arithmetic it needs."""
    module, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(module), name)


ARGS_KEY = "per_layer_args"

# -- what the program ran in, observed ---------------------------------------
# The benchmark passes the program no dtype and is told none: it reads the
# arrays of every engine the builder makes (``Cell.builder``), by class.
ADMITTED = ("float32", "bfloat16")   # dtypes a configuration may admit
CLASSES = ("weights", "pages", "state")
ROUTER_LIMIT = "router_off_limit_share_of_router_range"
ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "float8_e4m3fn": 1}


def kept_patterns(cfg: dict) -> list:
    """Array-name patterns (``fnmatch``, on the name behind the engine's
    prefix) that the configuration's ``as_run.bfloat16.keeps_float32``
    lists: what a bfloat16 program keeps in float32."""
    keeps = cfg.get("as_run", {}).get("bfloat16", {}).get("keeps_float32", [])
    return [p for entry in keeps for p in entry.get("arrays", [])]


def observe_engine(gen, cfg: dict) -> dict:
    """The dtypes of the device arrays of engine ``gen``, by class, from
    its scope: a walk over names and dtypes, no fetch, no compile.

    * ``weights``: floating arrays of two or more dimensions under the
      engine's prefix that are no cache and match no kept pattern;
    * ``pages``: ``gen.cache_names`` (K/V, window and latent pools);
    * ``state``: ``gen.state_names`` (convolution rows, delta matrices,
      state-space state).

    A class reads the one dtype all its arrays have, ``"mixed: a, b"``
    where they differ, None where the engine has none.
    ``kept_not_float32`` names (behind the prefix) the arrays that match a
    kept pattern, and the vectors (norm weights, biases, a head's
    constants), that are not float32."""
    import fnmatch

    import numpy as np

    pages = set(gen.cache_names)
    state = set(getattr(gen, "state_names", ()))
    patterns = kept_patterns(cfg)
    prefix = gen.name + "."
    seen = {c: set() for c in CLASSES}
    astray = []
    for n in gen.scope.local_var_names():
        v = gen.scope.find_var(n)
        if getattr(v, "dtype", None) is None:
            continue
        dtype = str(np.dtype(v.dtype))
        if "float" not in dtype:
            continue
        if n in pages or n in state:
            seen["pages" if n in pages else "state"].add(dtype)
        elif n.startswith(prefix):
            short = n[len(prefix):]
            if v.ndim >= 2 and not any(fnmatch.fnmatchcase(short, p)
                                       for p in patterns):
                seen["weights"].add(dtype)
            elif dtype != "float32":
                astray.append(f"{short} ({dtype})")

    def one(found):
        found = sorted(found)
        return None if not found else found[0] if len(found) == 1 \
            else "mixed: " + ", ".join(found)

    return dict({c: one(seen[c]) for c in CLASSES},
                kept_not_float32=sorted(astray))


def item_sizes(ctx: dict):
    """Bytes an item by class (``ops_bytes.ItemSizes``) for the roofline
    readers: what the run observed on its engines (``ctx["as_run_observed"]``
    where a context brings its own, else the run's cell).  A run always
    has an observation by the time a reader runs; a context with neither
    is a test's hand-made one and reads as a float32 program does.  No
    file's ``as_run.dtype`` is read: nothing states an item size."""
    from ops_bytes import ItemSizes, sizes_of

    seen = ctx.get("as_run_observed")
    if seen is None:
        cell = getattr(ctx.get("run"), "cell", None)
        seen = getattr(cell, "observed", None)
    if seen is None:
        return sizes_of(ITEMSIZE["float32"])
    return ItemSizes(**{c: ITEMSIZE[seen[c] or "float32"] for c in CLASSES})


def said_limit(limit) -> str:
    """`` (limit x)`` for a check's line, nothing where there is none."""
    return "" if limit is None else f" (limit {limit:.4g})"


def tolerance_entry(cfg: dict, dtype: str):
    """The entry of the configuration's ``check_tolerance`` that a program
    whose weights are ``dtype`` is held to, or None where the file admits
    no such program.  The keys at the top of ``check_tolerance`` are the
    ``float32`` entry (tests outside ``benchmark/`` read them there); the
    ``bfloat16`` entry lies under that key."""
    tol = cfg["check_tolerance"]
    if dtype == "float32":
        return {k: v for k, v in tol.items() if k not in ADMITTED}
    entry = tol.get(dtype) if dtype in ADMITTED else None
    return entry if isinstance(entry, dict) and "share_of_range" in entry \
        else None


def held_to(cfg: dict, observed: dict):
    """``(entry, problems)``: the tolerance entry that what was observed
    is held to, and why the program is not one the configuration admits
    (each problem names the array or the class).  A float32 program is
    all float32.  A bfloat16 program has every array that
    ``keeps_float32`` lists, and every vector, in float32, and its pages
    and state in what ``as_run.bfloat16`` states."""
    dtype = observed["weights"]
    admitted = cfg["as_run"].get("dtypes_admitted", ["float32"])
    entry = tolerance_entry(cfg, dtype) if dtype in admitted else None
    if entry is None:
        return None, [f"weights are {dtype}: the configuration admits "
                      f"{', '.join(admitted)} and its check_tolerance "
                      f"has no entry for that"]
    stated = cfg["as_run"].get(dtype, {}) if dtype != "float32" else {}
    problems = [f"array {name}: kept float32 beside {dtype} weights by "
                f"keeps_float32 (every vector is)"
                for name in observed["kept_not_float32"]]
    for c in ("pages", "state"):
        want = stated.get(c, "float32")
        if observed[c] not in (None, want):
            problems.append(f"{c} are {observed[c]}, stated {want} "
                            f"beside {dtype} weights")
    return entry, problems


class Cell:
    """One entry of ``workloads`` with its files resolved."""

    def __init__(self, name: str, rehearse: bool = False):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        entry = [w for w in self.bench["workloads"] if w["name"] == name]
        if not entry:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = entry[0]
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.cfg = load_json("configs", self.config_name + ".json")
        self.mix = traffic.load_mix(self.entry["traffic"])
        self.observed = None          # by class, once an engine is built
        self.observed_problems = []
        if rehearse:
            # toy sizes for the CPU, from the files themselves (a toy
            # tolerance is the float32 entry's; the others stay the file's,
            # and so does what ``as_run`` states of the admitted dtypes)
            toy = dict(self.cfg.get("rehearse", {}))
            tol = toy.pop("check_tolerance", {})
            if "as_run" in toy:
                toy["as_run"] = dict(
                    {k: v for k, v in self.cfg["as_run"].items()
                     if k in ("dtypes_admitted",) + ADMITTED},
                    **toy["as_run"])
            self.cfg.update(toy)
            self.cfg["check_tolerance"] = dict(self.cfg["check_tolerance"],
                                               **tol)
            toy = self.mix.get("rehearse", {})
            self.mix.update({k: v for k, v in toy.items() if k != "engine"})
            self.mix.setdefault("engine", {}).update(toy.get("engine", {}))

    def metrics(self, group: str) -> list:
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader_of(self, name: str):
        """``(reader, arguments)`` of per-layer entry ``name`` in this
        cell: the reader's name under ``benchmark/readers`` and what its
        ``read`` gets beside ``ctx``: the arguments of the entry's data
        file, then the configuration's own, then the mix's own (the later
        wins)."""
        spec = load_json("metrics", name + ".json")
        args = dict(spec.get("args", {}))
        for own in (self.cfg, self.mix):
            args.update(own.get(ARGS_KEY, {}).get(name, {}))
        return spec["reader"], args

    def reference(self):
        return load_module("reference", self.config_name)

    def builder(self):
        """The configuration's builder, with every engine it makes
        observed on the way out (``note_engine``): nothing is passed to
        the program and nothing asked of it."""
        return _Observing(load_module("builders", self.cfg["builder"]), self)

    def note_engine(self, gen):
        """Read what ``gen`` runs in (``observe_engine``) and hold the
        cell to the entry of ``check_tolerance`` for it: from here on
        ``cfg["check_tolerance"]`` IS that entry (the references read the
        near-tie margin there) and ``tolerance`` its number.  What makes
        the program one the configuration does not admit goes to
        ``observed_problems``; the drivers' verdict has it
        (``Cell.admitted``)."""
        seen = observe_engine(gen, self.cfg)
        entry, problems = held_to(self.cfg, seen)
        del seen["kept_not_float32"]
        if self.observed is not None and seen != self.observed:
            problems.append(f"engines of one run differ: {self.observed} "
                            f"then {seen}")
        self.observed = seen
        self.observed_problems += [p for p in problems
                                   if p not in self.observed_problems]
        if entry is not None:
            self.hold_to(seen["weights"])

    def hold_to(self, dtype: str, margin: float = None) -> bool:
        """Make ``cfg["check_tolerance"]`` the file's entry for weights of
        ``dtype`` (with the other entries under their keys still), or
        leave it and say False where the file has none.  ``margin``:
        another near-tie margin, for a control's sweep."""
        whole = self.cfg.setdefault("check_tolerance_file",
                                    self.cfg["check_tolerance"])
        entry = tolerance_entry({"check_tolerance": whole}, dtype)
        if entry is None:
            return False
        entry = dict(entry, **{d: whole[d] for d in ADMITTED if d in whole})
        if margin is not None:
            entry["near_tie_margin_share_of_router_range"] = float(margin)
        self.cfg["check_tolerance"] = entry
        return True

    @property
    def admitted(self) -> bool:
        """Every engine built so far ran in dtypes the configuration
        admits, with what it keeps in float32 in float32."""
        return not self.observed_problems

    @property
    def tolerance(self) -> float:
        """How far the program may be off its plain reference, as a share
        of the reference's largest magnitude; the configuration's file
        gives the number and the reason, by the dtype of the weights the
        program was observed to run in (float32 until an engine is
        built)."""
        return float(self.cfg["check_tolerance"]["share_of_range"])

    @property
    def router_tolerance(self):
        """How far the program's router scores may lie off the
        reference's on a compared row, as a share of the row's range of
        scores, where the entry the cell is held to states it (the
        ``bfloat16`` entries of the routed configurations, whose near-tie
        margins are wide); None where it states none (every ``float32``
        entry: its narrow margin judges the picks themselves)."""
        limit = self.cfg["check_tolerance"].get(ROUTER_LIMIT)
        return None if limit is None else float(limit)


class _Observing:
    """A builder's module whose ``engine`` notes what it built."""

    def __init__(self, module, cell):
        self.__dict__.update(_module=module, _cell=cell)

    def __getattr__(self, name):
        return getattr(self._module, name)

    def __setattr__(self, name, value):
        setattr(self._module, name, value)

    def engine(self, *args, **kwargs):
        gen = self._module.engine(*args, **kwargs)
        self._cell.note_engine(gen)
        return gen


def peaks_for(kind: str) -> dict:
    table = load_json("peaks.json")
    if kind not in table or not isinstance(table[kind], dict):
        raise SystemExit(f"device kind {kind!r} is not in benchmark/"
                         f"peaks.json: no peak, so no run")
    return table[kind]


class Run:
    """State of one run: clock, phases, compile counts, trace."""

    def __init__(self, cell: Cell, args, t_start: float):
        self.cell, self.args = cell, args
        self.t_start = t_start
        self.rehearse = bool(args.rehearse)
        self.trace_on = bool(args.trace) and not self.rehearse
        self.phases = []
        self._t_phase = t_start
        self._compiles = 0
        self._lock = threading.Lock()
        self.trace = None
        self.largest_temp_bytes = 0
        # what the drivers compared for ``correct``, each number beside
        # its limit, by short names: the result line's last key, ``check``
        self.check = None
        self.workdir = tempfile.mkdtemp(prefix="bench_")

    # -- devices ------------------------------------------------------------
    def claim_devices(self):
        """The cell's chips, or no run: a measurement never falls back to
        the CPU."""
        import jax

        devices = jax.devices()
        d0 = devices[0]
        if not self.rehearse:
            if d0.platform != "tpu":
                raise SystemExit(
                    f"benchmark: jax.devices()[0].platform is "
                    f"{d0.platform!r}, not 'tpu'; nothing is measured on "
                    f"another platform (use --rehearse on the CPU)")
            if len(devices) < self.cell.chips:
                raise SystemExit(
                    f"benchmark: cell {self.cell.name} needs "
                    f"{self.cell.chips} chips, JAX found {len(devices)}")
            self.peaks = peaks_for(d0.device_kind)
        self.devices = devices
        return devices

    def device_block(self) -> dict:
        """``memory_peak_bytes``, by one rule in every cell: on the fullest
        chip, the larger of the runtime's ``peak_bytes_in_use`` and what
        is live now plus the temporaries of the largest program compiled
        in this process.  This runtime's peak counts buffers only and
        leaves a running program's temporaries out (``observe_programs``),
        and in a training step they are most of the memory."""
        d0 = self.devices[0]
        peak = counted = live = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            counted = max(counted, int(stats.get("peak_bytes_in_use", 0)))
            live = max(live, int(stats.get("bytes_in_use", 0)))
        peak = max(counted, live + self.largest_temp_bytes)
        self.say(f"memory on the fullest chip: runtime peak "
                 f"{counted / 1e9:.3f} GB, live now {live / 1e9:.3f} GB, "
                 f"largest program's temporaries "
                 f"{self.largest_temp_bytes / 1e9:.3f} GB (its memory "
                 f"analysis); reported peak {peak / 1e9:.3f} GB")
        return {"platform": d0.platform, "kind": d0.device_kind,
                "count": len(self.devices), "memory_peak_bytes": peak}

    # -- compile cache and compile counting -----------------------------------
    def setup_compile_cache(self):
        """The program places the cache (``JAX_COMPILATION_CACHE_DIR`` if
        set, else ``<checkout>/.jax_cache``); the benchmark only lowers
        jax's threshold so that programs that compile in under a second
        are kept too, and counts what compiles."""
        import jax

        from paddle_tpu.compile_cache import ensure_compile_cache

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        where = ensure_compile_cache()

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self._compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        self.observe_programs()
        return where

    def observe_programs(self):
        """Keep the largest ``temp_size_in_bytes`` over every program
        compiled ahead of time in this process (the program's executor and
        the training driver both compile through ``lower().compile()``),
        per device, for ``device_block``."""
        import jax

        compile_ = jax.stages.Lowered.compile
        run = self

        def observed(lowered, *args, **kwargs):
            compiled = compile_(lowered, *args, **kwargs)
            try:
                temp = int(compiled.memory_analysis().temp_size_in_bytes)
            except Exception:  # noqa: BLE001 — no analysis, nothing added
                temp = 0
            with run._lock:
                run.largest_temp_bytes = max(run.largest_temp_bytes, temp)
            return compiled

        jax.stages.Lowered.compile = observed

    def compile_count(self) -> int:
        """XLA compilations so far plus the executor's jit builds."""
        from paddle_tpu.monitor import stat_get

        with self._lock:
            n = self._compiles
        return n + int(stat_get("executor_jit_builds"))

    # -- set-up phases --------------------------------------------------------
    def phase(self, name: str, at: float = None):
        now = time.monotonic() if at is None else at
        self.phases.append((name, now - self._t_phase))
        self._t_phase = now

    def say(self, msg: str):
        print(f"[bench {self.cell.name}] {msg}", flush=True)

    def say_phases(self):
        self.say("set-up by phase (s): " + ", ".join(
            f"{n} {s:.1f}" for n, s in self.phases))

    # -- profiler -------------------------------------------------------------
    def trace_start(self):
        import jax

        self._trace_dir = os.path.join(self.workdir, "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        from xplane import WINDOW_ANNOTATION

        self._trace_ann = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
        self._trace_ann.__enter__()
        self.trace_t0 = time.monotonic()

    def trace_stop(self):
        import jax

        import xplane

        self.trace_t1 = time.monotonic()
        self._trace_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        t0 = time.monotonic()
        raw = xplane.load(xplane.find_xplane(self._trace_dir))
        self.trace = xplane.reduce(raw)
        # trace clock -> host monotonic clock, through the annotation
        self.trace["to_monotonic"] = self.trace_t0 - self.trace["window"][0]
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        self.say(f"trace reduced in {time.monotonic() - t0:.1f} s: window "
                 f"{self.trace['window_s']:.3f} s, busy "
                 f"{self.trace['busy_s']:.3f} s")
        for name, runs in sorted(self.trace["modules"].items(),
                                 key=lambda kv: -len(kv[1]))[:8]:
            mean_ms = 1e3 * sum(e - s for s, e in runs) / len(runs)
            self.say(f"  module {name}: {len(runs)} runs, mean "
                     f"{mean_ms:.3f} ms")

    # -- result ---------------------------------------------------------------
    def read_per_layer(self, ctx: dict) -> dict:
        """Every per-layer metric of the cell through its reader.  A
        reader that finds nothing returns None and the metric is left
        out."""
        out = {}
        for m in self.cell.metrics("per_layer"):
            reader, args = self.cell.reader_of(m["name"])
            value = load_module("readers", reader).read(ctx, **args)
            if value is None or not math.isfinite(value):
                self.say(f"per-layer {m['name']}: nothing to read")
                continue
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def finish(self, *, correct: bool, attempted: int, failed: int,
               end_to_end: dict, ctx: dict) -> int:
        """Print the contract's line, last.  ``end_to_end`` holds every
        end-to-end reading the driver took, by metric name."""
        for problem in self.cell.observed_problems:
            self.say(f"as run: {problem}: NOT correct")
        correct = bool(correct) and self.cell.admitted
        if self.rehearse:
            print(json.dumps({"rehearsal": True, "cell": self.cell.name,
                              "correct": bool(correct),
                              "as_run_observed": self.cell.observed,
                              "attempted": int(attempted),
                              "failed": int(failed),
                              "largest_temp_bytes": self.largest_temp_bytes,
                              "counts": ctx.get("counts", {}),
                              "check": self.said_check(correct)}),
                  flush=True)
            return 0
        device = self.device = self.device_block()
        if self.trace_on:
            metrics = self.read_per_layer(ctx)
            device["busy_s"] = self.trace["busy_s"]
            device["window_s"] = self.trace["window_s"]
        else:
            metrics = {}
            for m in self.cell.metrics("end_to_end"):
                metrics[m["name"]] = {"value": float(end_to_end[m["name"]]),
                                      "unit": m["unit"]}
        line = {"correct": bool(correct), "attempted": int(attempted),
                "failed": int(failed), "metrics": metrics, "device": device}
        if self.cell.observed is not None:
            # the dtypes of the engine's weights, pages and slot state
            line["as_run_observed"] = self.cell.observed
        if self.trace_on:
            line["breakdown"] = {"device_ops": self.trace["device_ops"],
                                 "idle_gaps": self.trace["idle_gaps"]}
        line["check"] = self.said_check(correct)
        print(json.dumps(line), flush=True)
        return 0

    def said_check(self, correct: bool) -> dict:
        """What was compared for ``correct`` (the drivers' ``self.check``
        and what ``as_run`` refused), each number beside its limit: the
        last lines on standard error and the result line's last key, so
        that a run that reads not correct says by which number in what
        the driver's record keeps of it."""
        check = dict(self.check or {}, correct=bool(correct))
        if self.cell.observed_problems:
            check["as_run_problems"] = self.cell.observed_problems
        for name, value in check.items():
            print(f"[bench {self.cell.name}] check: {name} "
                  f"{json.dumps(value)}", file=sys.stderr)
        sys.stderr.flush()
        return check

    def cleanup(self):
        shutil.rmtree(self.workdir, ignore_errors=True)


def seeded_weights(scope, names, seed: int):
    """Redraw every matrix among ``names`` on the device, in one jitted
    call, from ``seed``: normal with the standard deviation the program's
    startup initialisation gave it.  Vectors (norm scales, biases) keep
    their constant initialisation.  The seed is an argument of the call,
    not a constant of a program, so no program is compiled per seed (the
    program's own seeds are constants of its startup and dropout
    programs: a new seed there is a new compilation, 17 s + 99 s for
    BERT-base, my chip run, PR 23)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    @functools.partial(jax.jit, donate_argnums=0)
    def redraw(vals, seed32):
        key = jax.random.key(seed32)
        out = []
        for i, v in enumerate(vals):
            if v.ndim < 2 or not jnp.issubdtype(v.dtype, jnp.floating):
                out.append(v)
                continue
            draw = jax.random.normal(jax.random.fold_in(key, i), v.shape,
                                     v.dtype)
            out.append(draw * jnp.std(v))
        return tuple(out)

    names = list(names)
    new = redraw(tuple(scope.find_var(n) for n in names),
                 np.uint32(int(seed) % 2 ** 32))
    for n, v in zip(names, new):
        scope.set_var(n, v)


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of nothing")
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def share_over(values, times: float) -> float:
    """Share, in percent, of a non-empty list that lies above ``times``
    its own median: of token gaps at 2, the gaps that carried a stall
    (a prefill) rather than a plain decode step."""
    limit = times * quantile(values, 0.5)
    return 100.0 * sum(v > limit for v in values) / len(values)
