"""TPU accelerator detection and isolation.

Equivalent of the reference's ``python/ray/_private/accelerators/tpu.py``
(TPUAcceleratorManager :75): detect chips on this host, the pod type of the
slice this host belongs to, the host's worker index within the slice, and
per-task chip isolation via ``TPU_VISIBLE_CHIPS`` (:158-192). Detection is
env-var driven (GCE/GKE metadata endpoints are not reachable in all
environments; the same env vars the metadata would populate are honored):

- ``TPU_ACCELERATOR_TYPE`` / ``ACCELERATOR_TYPE`` — e.g. ``v5litepod-64``
- ``TPU_WORKER_ID`` — host index within the slice
- ``TPU_CHIPS_PER_HOST_BOUNDS`` / ``TPU_CHIPS`` — chips on this host
- ``TPU_NAME`` — pod/slice name

The chip count comes first from the device files the TPU driver creates,
then from those variables. Nothing here initialises a JAX backend in the asking
process: a TPU chip belongs to the one process that opened it, and the
processes that ask (the driver, the node manager) are not the ones that
compute. :func:`probe_devices` asks a short-lived child instead.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
from typing import Any, Dict, List, Optional

NUM_TPUS_PER_HOST_DEFAULT = 4

#: device nodes of one TPU chip each: ``/dev/accel*`` (PCI driver, v4 and
#: up on TPU VMs) or ``/dev/vfio/<n>`` (VFIO-bound chips)
_CHIP_DEVICE_GLOBS = ("/dev/accel[0-9]*", "/dev/vfio/[0-9]*")


def tpu_chip_count() -> int:
    """Chips on this host — never asked of JAX (module docstring).
    ``TPU_CHIPS`` overrides; then the device files, which are the chips
    this machine really has (a VM handed one chip of a 2x2 host still
    carries the host's ``TPU_CHIPS_PER_HOST_BOUNDS=2,2,1``); the
    ``TPU_*`` topology env only where no device file says otherwise."""
    raw = os.environ.get("TPU_CHIPS")
    if raw:
        return int(raw)
    for pattern in _CHIP_DEVICE_GLOBS:
        found = glob.glob(pattern)
        if found:
            return len(found)
    bounds = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")  # e.g. "2,2,1"
    if bounds:
        n = 1
        for part in bounds.split(","):
            n *= int(part)
        return n
    if os.environ.get("TPU_ACCELERATOR_TYPE") or os.environ.get("ACCELERATOR_TYPE"):
        return NUM_TPUS_PER_HOST_DEFAULT
    return 0


def jax_backend_initialized() -> bool:
    """True once this process has created a JAX backend — from then on
    chip visibility, ``XLA_FLAGS`` and the distributed runtime are
    fixed for its lifetime. Importing jax alone does not count."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


_PROBE_SRC = (
    "import json, jax\n"
    "d = jax.devices()\n"
    "print('RAY_TPU_PROBE ' + json.dumps({'platform': d[0].platform, "
    "'kind': d[0].device_kind, 'count': len(d)}))\n")


def probe_devices(timeout_s: float = 180.0) -> Dict[str, Any]:
    """What JAX finds on this host — ``{"platform", "kind", "count"}``
    as ``jax.devices()`` reports them — learnt from a child process
    that exits (and so lets go of the chips) before this returns. For
    launchers that go on to start the workers which will own the chips.
    Raises if the child fails."""
    proc = subprocess.run([sys.executable, "-c", _PROBE_SRC],
                          capture_output=True, text=True,
                          timeout=timeout_s)
    for line in proc.stdout.splitlines():
        if line.startswith("RAY_TPU_PROBE "):
            return json.loads(line[len("RAY_TPU_PROBE "):])
    raise RuntimeError(
        f"device probe child failed (rc={proc.returncode}):\n"
        f"{proc.stderr[-2000:]}")


def tpu_accelerator_type() -> Optional[str]:
    return os.environ.get("TPU_ACCELERATOR_TYPE") or os.environ.get("ACCELERATOR_TYPE")


def tpu_pod_type() -> Optional[str]:
    """Normalized pod type, e.g. ``v5litepod-64`` -> ``v5e-64`` (reference:
    _get_current_node_tpu_pod_type, tpu.py:199)."""
    acc = tpu_accelerator_type()
    if not acc:
        return None
    acc = acc.lower()
    for raw, norm in (("v5litepod", "v5e"), ("v5p", "v5p"), ("v6e", "v6e"),
                      ("v4", "v4"), ("v3", "v3"), ("v2", "v2")):
        if acc.startswith(raw):
            return acc.replace(raw, norm, 1)
    return acc


def tpu_worker_index() -> int:
    return int(os.environ.get("TPU_WORKER_ID", "0"))


def tpu_pod_name() -> Optional[str]:
    """Reference: ray.util.accelerators.tpu.get_current_pod_name (:7)."""
    return os.environ.get("TPU_NAME")


def tpu_pod_worker_count() -> int:
    """Total hosts in this slice (reference: get_current_pod_worker_count
    :19): chips(pod_type) / chips_per_host."""
    pod = tpu_pod_type()
    if not pod:
        return 1
    try:
        total_chips = int(pod.rsplit("-", 1)[1])
    except (IndexError, ValueError):
        return 1
    per_host = max(1, tpu_chip_count() or NUM_TPUS_PER_HOST_DEFAULT)
    return max(1, total_chips // per_host)


#: libtpu's per-process topology for a subset of a host's chips
#: (reference: tpu.py:158-192): n chips -> TPU_CHIPS_PER_PROCESS_BOUNDS
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def set_visible_chips(chip_ids: List[int]) -> None:
    """Per-worker chip isolation (reference: tpu.py:158-192): this
    process will open exactly ``chip_ids``. Must run before jax
    initializes a backend here — afterwards libtpu has already opened
    whatever it could see, so that is an error, not a no-op."""
    if jax_backend_initialized():
        raise RuntimeError(
            f"cannot pin this process to TPU chips {chip_ids}: its jax "
            f"backend is already initialized. Chip work needs a worker "
            f"that has not touched jax.")
    n = len(chip_ids)
    if n not in _PROCESS_BOUNDS:
        raise ValueError(
            f"a process can hold {sorted(_PROCESS_BOUNDS)} chips of a "
            f"host, not {n}")
    os.environ["TPU_VISIBLE_CHIPS"] = ",".join(str(i) for i in chip_ids)
    os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = _PROCESS_BOUNDS[n]
    os.environ["TPU_PROCESS_BOUNDS"] = "1,1,1"


def gang_resource_name() -> Optional[str]:
    """`TPU-{pod_type}-head` (reference: tpu.py:379-382)."""
    pod = tpu_pod_type()
    return f"TPU-{pod}-head" if pod else None
