"""Device cost model: executable manifests + peak-rate table + MFU/BW math.

The single source of truth for "how fast could this chip go" and "what
does this compiled program actually cost".  Three layers use it:

* **Executable manifests** — :func:`executable_manifest` reads XLA's
  ``cost_analysis()`` / ``memory_analysis()`` off an AOT-compiled
  executable: flops, bytes accessed, argument/output/temp/peak HBM.
  The executor captures one per compile-cache entry
  (``Executor.cache_info()``) and the serving ``Predictor`` per feed
  signature (``Predictor.cache_info()`` → ``/statusz``) — the numbers
  behind "why is this signature slow / big".
* **Peak table** — :func:`device_peaks` maps ``device_kind`` → peak
  bf16 FLOP/s and HBM bytes/s (one table; ``FLAGS_device_peak_flops``
  / ``FLAGS_device_peak_bw`` override).  A ``device_kind`` the table
  does not know has no peak, so nothing computed against it reports a
  utilization.
* **Achieved efficiency** — :func:`mfu` / :func:`bw_util` /
  :func:`publish_achieved` turn (manifest, steps/sec) into live
  ``device_mfu`` / ``device_bw_util`` gauges on every training step.

Everything degrades to ``None`` instead of raising: a backend without
cost analysis must not take down the step.
"""
from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Optional

from .flags import flag_value

__all__ = ["device_peaks", "peak_flops", "peak_bw", "executable_manifest",
           "aot_compile", "mfu", "bw_util", "publish_achieved",
           "manifest_summary"]

logger = logging.getLogger("paddle_tpu.costmodel")

# device_kind substring -> (peak bf16 TFLOP/s, peak HBM GB/s) per chip.
# Sources: published TPU specs (v5e 197 TF / 819 GB/s, v5p 459 / 2765,
# v6e 918 / 1640, v4 275 / 1228, v3 123 / 900, v2 45 / 700).  First
# match wins; an unknown kind (the CPU included) has no peak.
PEAK_TABLE = (
    ("v5 lite", 197.0, 819.0),
    ("v5e", 197.0, 819.0),
    ("v5p", 459.0, 2765.0),
    ("v6 lite", 918.0, 1640.0),
    ("v6e", 918.0, 1640.0),
    ("v4", 275.0, 1228.0),
    ("v3", 123.0, 900.0),
    ("v2", 45.0, 700.0),
)


def _kind_of(device) -> str:
    if device is None:
        return ""
    if isinstance(device, str):
        return device
    return str(getattr(device, "device_kind", device))


def device_peaks(device=None) -> Dict[str, Any]:
    """Peak rates for ``device`` (a jax device, a ``device_kind``
    string, or None = the current backend's first device).

    Returns ``{"device_kind", "peak_flops" (FLOP/s), "peak_bw"
    (bytes/s), "source"}`` where source records what produced the
    numbers (``table``, ``FLAGS_device_peak_flops``, or ``unknown`` —
    both peaks None — for a device_kind the table does not list)."""
    if device is None:
        import sys
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                device = jax.devices()[0]
            except Exception as e:  # backend not initialized yet
                logger.debug("device_peaks: no jax device: %s", e)
    kind = _kind_of(device)
    tflops, gbps, source = None, None, "unknown"
    for key, tf, gb in PEAK_TABLE:
        if key in kind.lower():
            tflops, gbps, source = tf, gb, "table"
            break
    f = flag_value("FLAGS_device_peak_flops")
    if f:
        tflops, source = float(f), "FLAGS_device_peak_flops"
    b = flag_value("FLAGS_device_peak_bw")
    if b:
        gbps = float(b)
    return {"device_kind": kind,
            "peak_flops": None if tflops is None else tflops * 1e12,
            "peak_bw": None if gbps is None else gbps * 1e9,
            "source": source}


def peak_flops(device=None) -> Optional[float]:
    """Per-chip peak FLOP/s (see :func:`device_peaks` for overrides);
    None for an unknown device."""
    return device_peaks(device)["peak_flops"]


def peak_bw(device=None) -> Optional[float]:
    """Per-chip peak HBM bytes/s; None for an unknown device."""
    return device_peaks(device)["peak_bw"]


def mfu(flops_per_sec: float, device=None,
        peak: Optional[float] = None) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOP/s over the chip peak;
    None when the device has no known peak."""
    peak = peak if peak is not None else peak_flops(device)
    return flops_per_sec / peak if peak else None


def bw_util(bytes_per_sec: float, device=None,
            peak: Optional[float] = None) -> Optional[float]:
    """HBM-bandwidth utilization: achieved bytes/s over the chip peak;
    None when the device has no known peak."""
    peak = peak if peak is not None else peak_bw(device)
    return bytes_per_sec / peak if peak else None


# ---------------------------------------------------------------------------
# executable manifests
# ---------------------------------------------------------------------------

def _cost_dict(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def executable_manifest(compiled, signature=None) -> Optional[dict]:
    """Read flops / bytes / HBM footprint off an AOT-compiled XLA
    executable (``jit(...).lower(...).compile()`` result).

    Returns::

        {"signature": str|None,
         "flops": float,            # per execution, whole program
         "bytes_accessed": float,   # HBM traffic per execution
         "argument_bytes": int, "output_bytes": int,
         "temp_bytes": int, "alias_bytes": int,
         "peak_hbm_bytes": int,     # arg + out + temp - aliased
         "generated_code_bytes": int}

    or ``None`` when the backend exposes neither analysis.  Never
    raises (an analysis failure logs and degrades — observability must
    not break execution)."""
    out: Dict[str, Any] = {
        "signature": None if signature is None else str(signature)}
    got = False
    try:
        cost = _cost_dict(compiled)
        if cost:
            out["flops"] = float(cost.get("flops", 0.0))
            out["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
            got = True
    except Exception as e:
        logger.debug("cost_analysis unavailable: %s", e)
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            arg = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
            outb = int(getattr(ma, "output_size_in_bytes", 0) or 0)
            tmp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
            alias = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
            out.update(
                argument_bytes=arg, output_bytes=outb, temp_bytes=tmp,
                alias_bytes=alias,
                peak_hbm_bytes=max(arg + outb + tmp - alias, 0),
                generated_code_bytes=int(
                    getattr(ma, "generated_code_size_in_bytes", 0) or 0))
            got = True
    except Exception as e:
        logger.debug("memory_analysis unavailable: %s", e)
    return out if got else None


def manifest_summary(manifest: Optional[dict]) -> Optional[dict]:
    """The compact (``/statusz`` / ``cache_info``) view of a manifest:
    flops, bytes accessed, peak HBM only."""
    if not manifest:
        return None
    return {k: manifest[k] for k in ("flops", "bytes_accessed",
                                     "peak_hbm_bytes") if k in manifest}


def aot_compile(jitted, *args, signature=None, store_digest=None,
                donate_argnums=()):
    """``jitted.lower(*args).compile()`` plus its manifest:
    ``(compiled, manifest)``.  The manifest half never raises; the
    compile half raises exactly as jax would.  With ``store_digest``
    (``program_store.program_digest``: a store is placed and the Program
    has a key) what is lowered and compiled is the stored module of the
    step, loaded or made and kept now, under ``donate_argnums``, the
    donation ``jitted`` was built with."""
    if store_digest is not None:
        from . import program_store

        jitted = program_store.stored_step(jitted, args, store_digest,
                                           donate_argnums)
    compiled = jitted.lower(*args).compile()
    return compiled, executable_manifest(compiled, signature=signature)


# ---------------------------------------------------------------------------
# achieved efficiency gauges
# ---------------------------------------------------------------------------

_peaks_cache: Dict[str, Any] = {}
_peaks_lock = threading.Lock()


def _cached_peaks() -> Dict[str, Any]:
    """device_peaks() for the hot path: resolved once per process
    unless an override flag changes (the flags are read each call, so a
    changed override invalidates the cache)."""
    key = (flag_value("FLAGS_device_peak_flops"),
           flag_value("FLAGS_device_peak_bw"))
    with _peaks_lock:
        if _peaks_cache.get("key") != key:
            _peaks_cache["key"] = key
            _peaks_cache["peaks"] = device_peaks()
        return _peaks_cache["peaks"]


def publish_achieved(manifest: Optional[dict], execs_per_sec: float,
                     n_devices: int = 1) -> Optional[dict]:
    """Feed the live efficiency gauges from one executable's manifest
    and its measured execution rate: ``device_mfu`` (achieved model
    FLOP/s over peak) and ``device_bw_util`` (achieved HBM bytes/s over
    peak), both per chip (the manifest covers the whole SPMD program,
    so totals divide by ``n_devices``).  Returns the computed dict, or
    None when there is nothing to compute.  No-op with telemetry off."""
    from . import telemetry

    if not manifest or execs_per_sec <= 0 or not telemetry.enabled():
        return None
    peaks = _cached_peaks()
    out = {}
    flops = manifest.get("flops")
    if flops and peaks["peak_flops"]:
        out["mfu"] = mfu(flops * execs_per_sec / max(n_devices, 1),
                         peak=peaks["peak_flops"])
        telemetry.gauge_set("device_mfu", out["mfu"])
    ba = manifest.get("bytes_accessed")
    if ba and peaks["peak_bw"]:
        out["bw_util"] = bw_util(ba * execs_per_sec / max(n_devices, 1),
                                 peak=peaks["peak_bw"])
        telemetry.gauge_set("device_bw_util", out["bw_util"])
    return out or None
