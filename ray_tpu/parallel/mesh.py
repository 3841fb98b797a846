"""Device meshes and TPU topology.

The unit of accelerator scheduling in this framework is the TPU pod slice;
the unit of numerics is a jitted GSPMD program over a
``jax.sharding.Mesh``. This module builds meshes with the standard axis
vocabulary used across the libraries:

- ``dp``   — pure data parallel (params replicated)
- ``fsdp`` — data parallel with parameter/optimizer sharding (ZeRO-3-like)
- ``tp``   — tensor parallel (within ICI domain)
- ``sp``   — sequence/context parallel (ring attention axis)
- ``ep``   — expert parallel (MoE)
- ``pp``   — pipeline parallel (usually across DCN)

The reference has no in-tree TP/SP/PP (SURVEY.md §2.5); DP arrives via
torch DDP and FSDP via DeepSpeed integration. Here all strategies are mesh
axes of one GSPMD program.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip hardware numbers for MFU accounting."""
    name: str
    bf16_flops: float          # peak bf16 FLOP/s per chip
    hbm_bytes: int
    hbm_gbps: float            # HBM bandwidth GB/s
    ici_gbps: float            # per-link ICI bandwidth GB/s


# Public numbers (cloud.google.com/tpu/docs/system-architecture).
CHIP_SPECS: Dict[str, ChipSpec] = {
    "v4": ChipSpec("v4", 275e12, 32 << 30, 1228.0, 50.0),
    "v5e": ChipSpec("v5e", 197e12, 16 << 30, 819.0, 50.0),
    "v5p": ChipSpec("v5p", 459e12, 95 << 30, 2765.0, 100.0),
    "v6e": ChipSpec("v6e", 918e12, 32 << 30, 1640.0, 100.0),
    "cpu": ChipSpec("cpu", 1e11, 8 << 30, 50.0, 10.0),
}


def chip_spec(kind: Optional[str] = None) -> ChipSpec:
    """Resolve the chip spec for the current platform (or a named one).
    A TPU whose ``device_kind`` is not in the table is an error: peaks
    and block sizes are never borrowed from another chip."""
    if kind is not None:
        return CHIP_SPECS[kind]
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        return CHIP_SPECS["cpu"]
    return chip_spec_for_kind(d.device_kind)


#: ``jax.Device.device_kind`` strings -> CHIP_SPECS row.
_DEVICE_KINDS: Dict[str, str] = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e", "TPU v5e": "v5e",
    "TPU v5": "v5p", "TPU v5p": "v5p",
    "TPU v6 lite": "v6e", "TPU v6e": "v6e",
}


def chip_spec_for_kind(device_kind: str) -> ChipSpec:
    """Table row for a TPU ``device_kind`` string as JAX reports it."""
    name = _DEVICE_KINDS.get(device_kind)
    if name is None:
        raise ValueError(
            f"unknown TPU device_kind {device_kind!r}: add it and its "
            f"published peaks to parallel.mesh (have "
            f"{sorted(_DEVICE_KINDS)})")
    return CHIP_SPECS[name]


@dataclasses.dataclass
class MeshSpec:
    """Named-axis mesh shape; -1 on at most one axis means "infer".

    Example: ``MeshSpec(fsdp=-1, tp=4)`` on a v5e-64 → mesh (fsdp=16, tp=4).
    """
    pp: int = 1
    dp: int = 1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = self.axis_sizes()
        infer = [a for a, s in sizes.items() if s == -1]
        if len(infer) > 1:
            raise ValueError("at most one axis may be -1")
        known = math.prod(s for s in sizes.values() if s != -1)
        if infer:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by {known}")
            sizes[infer[0]] = n_devices // known
        elif known != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {known} devices, have {n_devices}")
        return MeshSpec(**sizes)

    @property
    def nontrivial_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in AXIS_ORDER if getattr(self, a) > 1)


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None):
    """Build a ``jax.sharding.Mesh`` over ``devices`` (default: all).

    Axis order puts ``pp`` outermost (slowest-varying → maps to DCN when
    devices span hosts/slices) and ``tp`` innermost (fastest-varying →
    nearest-neighbor ICI links), the standard layout from the scaling
    playbook.
    """
    import jax
    from jax.sharding import Mesh
    if devices is None:
        devices = jax.devices()
    spec = spec.resolve(len(devices))
    sizes = spec.axis_sizes()
    arr = np.asarray(devices).reshape(*[sizes[a] for a in AXIS_ORDER])
    return Mesh(arr, AXIS_ORDER)


def local_mesh(spec: Optional[MeshSpec] = None):
    """Mesh over this process's local devices only."""
    import jax
    devices = jax.local_devices()
    if spec is None:
        spec = MeshSpec(tp=len(devices))
    return build_mesh(spec, devices)


def mesh_shape_for_slice(pod_type: str, spec: MeshSpec) -> MeshSpec:
    """Resolve a MeshSpec against a named slice type, e.g. ``v5e-64``."""
    n = int(pod_type.rsplit("-", 1)[1])
    return spec.resolve(n)
