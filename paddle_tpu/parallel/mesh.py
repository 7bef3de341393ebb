"""Device mesh construction.

Replaces the reference's NCCL ring/communicator bootstrap
(platform/collective_helper.h:62 NCCLCommContext keyed by ring_id;
c_gen_nccl_id/c_comm_init ops): a ring_id becomes a *named mesh axis*, and
"communicator init" becomes constructing a `jax.sharding.Mesh` once.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np


def shard_map_compat(f, mesh, in_specs, out_specs, check=False):
    """`jax.shard_map` with the VMA checker off by default (our bodies
    mix collectives the checker can't type)."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


# Canonical axis names used across the framework.
DP_AXIS = "dp"      # data parallel (batch)
MP_AXIS = "mp"      # tensor/model parallel
PP_AXIS = "pp"      # pipeline stages
SP_AXIS = "sp"      # sequence/context parallel
EP_AXIS = "ep"      # expert parallel


@dataclass
class MeshConfig:
    """Topology spec: axis name -> size. Unspecified capacity goes to dp."""
    mp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    dp: Optional[int] = None  # None: fill with remaining devices

    def resolve(self, n_devices: int) -> Dict[str, int]:
        denom = self.mp * self.pp * self.sp * self.ep
        if n_devices % denom:
            raise ValueError(
                f"{n_devices} devices not divisible by mp*pp*sp*ep={denom}")
        dp = self.dp if self.dp is not None else n_devices // denom
        if dp * denom != n_devices:
            raise ValueError(
                f"dp({dp})*mp({self.mp})*pp({self.pp})*sp({self.sp})"
                f"*ep({self.ep}) != {n_devices}")
        axes = {DP_AXIS: dp, MP_AXIS: self.mp, PP_AXIS: self.pp,
                SP_AXIS: self.sp, EP_AXIS: self.ep}
        return {k: v for k, v in axes.items() if v > 1} or {DP_AXIS: dp}


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """Parse a serving-mesh topology spec string into ``{axis: size}``.

    Accepts ``"dp=4,mp=2"`` / ``"dp4,mp2"`` / ``"dp=4"`` (axes from the
    canonical set above; size >= 1; sizes of 1 are kept — the caller
    decides whether a trivial axis still materializes in the Mesh).
    The empty string parses to ``{}`` (no mesh configured)."""
    axes: Dict[str, int] = {}
    known = (DP_AXIS, MP_AXIS, PP_AXIS, SP_AXIS, EP_AXIS)
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition("=")
        if not size:  # "dp4" form
            m = re.match(r"([a-z]+)(\d+)$", part)
            if not m:
                raise ValueError(f"bad mesh spec entry {part!r}; expected "
                                 f"'axis=N' or 'axisN' (axes: {known})")
            name, size = m.group(1), m.group(2)
        name = name.strip()
        if name not in known:
            raise ValueError(f"unknown mesh axis {name!r} in spec "
                             f"{spec!r}; known axes: {known}")
        n = int(size)
        if n < 1:
            raise ValueError(f"mesh axis {name}={n} must be >= 1")
        axes[name] = n
    return axes


def axis_size(mesh, *axes: str) -> int:
    """Product of the sizes of the given axes present in ``mesh``
    (absent axes count as 1) — e.g. the dp width of a serving mesh."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= int(sizes.get(a, 1))
    return n


def make_mesh(axis_sizes: Dict[str, int] = None, devices=None, **kw):
    """Build a Mesh. ``make_mesh({'dp': 4, 'mp': 2})``.

    Axis order follows the dict order; put the most bandwidth-hungry axis
    (mp) innermost so its collectives ride the fastest ICI links.
    """
    import jax
    from jax.sharding import Mesh

    axis_sizes = dict(axis_sizes or {}, **kw)
    devices = list(devices if devices is not None else jax.devices())
    sizes = tuple(axis_sizes.values())
    n = int(np.prod(sizes)) if sizes else 1
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    dev_array = np.array(devices[:n]).reshape(sizes)
    return Mesh(dev_array, tuple(axis_sizes))


def dp_mesh(n: Optional[int] = None, devices=None):
    """Pure data-parallel mesh over all (or n) devices."""
    import jax
    devices = list(devices if devices is not None else jax.devices())
    n = n or len(devices)
    return make_mesh({DP_AXIS: n}, devices=devices)
