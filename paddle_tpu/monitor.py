"""Runtime stat monitor (reference platform/monitor.h StatRegistry /
STAT_ADD macros + the graph_viz_pass program dumps of ir/graph_viz_pass.cc).

StatRegistry: named thread-safe counters any subsystem bumps
(executor steps, PS RPC calls, checkpoint writes, ...); `publish()`
snapshots (optionally resetting) for logging/metrics export.

Async-pipeline counters (framework/executor.py): ``host_syncs`` — every
device→host fence the executor pays (block_until_ready / fetch asarray /
guard resolution; an async 50-step run should book O(1), not O(steps));
``guard_resolutions`` — batched resolutions of the deferred non-finite
guard's pending verdict ring; ``compile_cache_hits`` — XLA binaries
served from the persistent compilation cache (paddle_tpu/compile_cache.py;
jax's cache_hits monitoring event, i.e. a restart skipping a rebuild;
counted process-wide).

program_to_dot / save_program_dot: render a Program's op/var dataflow as
graphviz DOT — the reference attaches graph_viz_pass to pass pipelines;
here it is a plain function usable on any Program (and registered as an
IR pass in framework/ir.py for pipeline parity).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Tuple

from . import watch as _watch

__all__ = ["StatValue", "StatRegistry", "monitor", "stat_add", "stat_get",
           "stat_add_per_device", "process_start_time", "process_uptime_s",
           "program_to_dot", "save_program_dot"]

# one process-wide epoch for every "uptime" the system reports —
# telemetry heartbeat, serving /healthz, and /statusz must agree on it
# (three modules each stamping their own import time drift apart and
# make cross-surface uptime deltas meaningless)
_PROCESS_START = time.time()


def process_start_time() -> float:
    """Wall-clock time this process's monitor was imported (the shared
    epoch for uptime reporting across telemetry/serving surfaces)."""
    return _PROCESS_START


def process_uptime_s() -> float:
    return round(time.time() - _PROCESS_START, 3)


class StatValue:
    """One named int64 stat (reference platform/monitor.h StatValue)."""

    def __init__(self, name: str):
        self.name = name
        self._v = 0
        self._lock = threading.Lock()

    def increase(self, n: int = 1) -> int:
        if _watch.active:      # (a thread that traces a Program: watch.py)
            seen = _watch.current()
            if seen is not None:
                seen.stats[self.name] = seen.stats.get(self.name, 0) + n
        with self._lock:
            self._v += n
            return self._v

    def decrease(self, n: int = 1) -> int:
        return self.increase(-n)

    def reset(self) -> int:
        with self._lock:
            old, self._v = self._v, 0
            return old

    def get(self) -> int:
        with self._lock:
            return self._v


class StatRegistry:
    """Thread-safe name -> StatValue registry
    (reference StatRegistry::Instance)."""

    _instance = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self._stats: Dict[str, StatValue] = {}
        self._lock = threading.Lock()

    @classmethod
    def instance(cls) -> "StatRegistry":
        # double-checked under a class lock: the unlocked check-then-set
        # could hand two racing importers two registries, silently
        # splitting the counters between them
        if cls._instance is None:
            with cls._instance_lock:
                if cls._instance is None:
                    cls._instance = cls()
        return cls._instance

    def get(self, name: str) -> StatValue:
        with self._lock:
            s = self._stats.get(name)
            if s is None:
                s = self._stats[name] = StatValue(name)
            return s

    def publish(self, reset: bool = False) -> List[Tuple[str, int]]:
        """Point-in-time snapshot of every stat, optionally resetting.

        Atomic: all per-stat locks are acquired (in name order) before
        any value is read, so writers racing the publish land either
        entirely before the snapshot or entirely after it — a
        ``reset=True`` publish can no longer tear across stats or lose
        increments from cached StatValue handles that bypass the
        registry."""
        with self._lock:
            stats = sorted(self._stats.items())
            for _, s in stats:
                s._lock.acquire()
            try:
                out = [(name, s._v) for name, s in stats]
                if reset:
                    for _, s in stats:
                        s._v = 0
            finally:
                for _, s in stats:
                    s._lock.release()
        return out


monitor = StatRegistry.instance()


def stat_add(name: str, n: int = 1) -> int:
    """reference STAT_ADD(name, n) macro."""
    return monitor.get(name).increase(n)


def stat_get(name: str) -> int:
    return monitor.get(name).get()


def stat_add_per_device(name: str, n_devices: int, n: int = 1):
    """Bump the device-attributed siblings of a collective/memory stat:
    ``<name>_dev<i>`` for each participating device index, alongside
    the caller's own aggregate ``stat_add(name, ...)``.

    An SPMD program emits each collective once at trace time but every
    device in the group executes it, so multichip attribution (e.g. the
    ``dryrun_multichip`` legs, per-shard ``/statusz`` health) needs the
    per-device series.  Device-suffixed names are dynamic and therefore
    exempt from the README stat-catalog lint; the ``_dev<i>``
    convention itself is documented there."""
    for i in range(max(int(n_devices), 0)):
        monitor.get(f"{name}_dev{i}").increase(n)


# ---------------------------------------------------------------------------
# graphviz program dump (reference ir/graph_viz_pass.cc)
# ---------------------------------------------------------------------------

def _esc(s: str) -> str:
    return s.replace('"', '\\"')


def program_to_dot(program, block_idx: int = 0,
                   max_var_len: int = 40) -> str:
    """Render one block's op/var dataflow as graphviz DOT.

    Ops are boxes, variables ellipses (parameters shaded); edges follow
    def-use. Sub-block-owning ops (while/cond2) are annotated with the
    sub-block index rather than inlined (the reference's
    graph_viz_pass dumps one graph per block too)."""
    block = program.block(block_idx)
    lines = ["digraph G {", '  rankdir="TB";',
             '  node [fontsize=10];']
    var_nodes = set()

    def var_node(name):
        if name in var_nodes:
            return
        var_nodes.add(name)
        v = block._find_var_recursive(name)
        shape_s = ""
        if v is not None and v.shape is not None:
            shape_s = "\\n" + str(tuple(v.shape))
        style = ""
        if v is not None and getattr(v, "persistable", False):
            style = ', style=filled, fillcolor="lightgrey"'
        label = name if len(name) <= max_var_len \
            else name[:max_var_len - 3] + "..."
        lines.append(f'  "v_{_esc(name)}" [label="{_esc(label)}{shape_s}"'
                     f', shape=ellipse{style}];')

    for i, op in enumerate(block.ops):
        extra = ""
        sub = op.attrs.get("sub_block")
        if sub is None:
            sub = op.attrs.get("true_block")
        if sub is not None:
            extra = f"\\n[sub_block {sub}]"
        lines.append(f'  "op_{i}" [label="{_esc(op.type)}{extra}", '
                     'shape=box, style=filled, fillcolor="lightblue"];')
        for name in op.input_arg_names():
            if not name:
                continue
            var_node(name)
            lines.append(f'  "v_{_esc(name)}" -> "op_{i}";')
        for name in op.output_arg_names():
            if not name:
                continue
            var_node(name)
            lines.append(f'  "op_{i}" -> "v_{_esc(name)}";')
    lines.append("}")
    return "\n".join(lines)


def save_program_dot(program, path: str, block_idx: int = 0):
    """Write the DOT dump (reference graph_viz_pass's
    graph_viz_path attribute)."""
    with open(path, "w") as f:
        f.write(program_to_dot(program, block_idx))
    return path
