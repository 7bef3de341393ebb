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
        if rehearse:
            # toy sizes for the CPU, from the files themselves
            self.cfg.update(self.cfg.get("rehearse", {}))
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
        return load_module("builders", self.cfg["builder"])

    @property
    def tolerance(self) -> float:
        """How far the program may be off its plain reference, as a share
        of the reference's largest magnitude; the configuration's file
        gives the number and the reason."""
        return float(self.cfg["check_tolerance"]["share_of_range"])


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
        if self.rehearse:
            print(json.dumps({"rehearsal": True, "cell": self.cell.name,
                              "correct": bool(correct),
                              "attempted": int(attempted),
                              "failed": int(failed),
                              "largest_temp_bytes": self.largest_temp_bytes,
                              "counts": ctx.get("counts", {})}),
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
        if self.trace_on:
            line["breakdown"] = {"device_ops": self.trace["device_ops"],
                                 "idle_gaps": self.trace["idle_gaps"]}
        print(json.dumps(line), flush=True)
        return 0

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
