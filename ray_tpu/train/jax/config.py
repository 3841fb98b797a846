"""JAX backend: multi-host SPMD rendezvous for the worker group.

Reference shape: ``python/ray/train/torch/config.py:146`` —
``_TorchBackend.on_start`` picks a rendezvous address on rank 0 and runs
``dist.init_process_group`` on every worker. TPU-native equivalent: rank
0 publishes a coordinator address; every worker calls
``jax.distributed.initialize(coordinator, num_processes, process_id)``,
which is the JAX runtime's coordination service (barrier + device mesh
discovery over DCN). Inside a host, no process group exists at all —
collectives are XLA ICI ops compiled into the jitted program.

On a single host (tests, one-chip dev) distributed init is skipped:
``jax.devices()`` already sees every local chip and GSPMD handles the
rest, so ``train_func`` code is identical either way.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Type

from ray_tpu.train.backend import Backend, BackendConfig


@dataclass
class JaxConfig(BackendConfig):
    # Force-enable/disable jax.distributed.initialize; None = auto
    # (enabled iff the group spans >1 node).
    distributed: Optional[bool] = None
    #: 0 picks a free port on the coordinator at start time.
    coordinator_port: int = 0
    #: Per-process device count override (CPU testing: N virtual devices
    #: per worker process; real TPU hosts leave this None — the runtime
    #: discovers the host's chips).
    local_device_count: Optional[int] = None

    @property
    def backend_cls(self) -> Type["_JaxBackend"]:
        return _JaxBackend


def _setup_jax_distributed(rendezvous_key: bytes, port: int,
                           num_processes: int, process_id: int,
                           local_device_count: Optional[int] = None) -> None:
    """Runs on each worker before train_func (reference analog:
    ``_setup_torch_process_group`` torch/config.py:64 — rank 0 publishes
    the rendezvous, everyone joins). Rank 0 probes its port (0 = free)
    and publishes ip:port to the cluster KV IN THE SAME PROCESS that
    immediately binds it via jax.distributed.initialize, so there is no
    cross-RPC window for another process to steal the port; followers
    poll the KV. Must run before the worker's first jax backend init:
    XLA_FLAGS and the coordination service only apply to an
    uninitialized runtime."""
    import time

    import ray_tpu
    from ray_tpu.core.global_state import global_worker

    if local_device_count is not None:
        # replace any inherited count (test harnesses export a
        # driver-wide value that is wrong for per-process workers)
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(
            f"--xla_force_host_platform_device_count={local_device_count}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    import jax

    from ray_tpu.core.accelerators import jax_backend_initialized
    if jax_backend_initialized():
        raise RuntimeError(
            "jax backend already initialized in this worker process; "
            "distributed setup (XLA_FLAGS / coordination service) "
            "cannot apply. Use fresh training workers.")
    w = global_worker()
    if process_id == 0:
        import socket
        ip = socket.gethostbyname(socket.gethostname())
        if port == 0:
            with socket.socket() as s:
                s.bind(("", 0))
                port = s.getsockname()[1]
        address = f"{ip}:{port}"
        w.kv_put(rendezvous_key, address.encode(), ns="__train__")
    else:
        deadline = time.monotonic() + 60.0
        address = None
        while time.monotonic() < deadline:
            raw = w.kv_get(rendezvous_key, ns="__train__")
            if raw:
                address = raw.decode()
                break
            time.sleep(0.05)
        if address is None:
            raise TimeoutError("rank 0 never published the jax "
                               "coordinator address")
    os.environ["RAY_TPU_JAX_COORDINATOR"] = address
    os.environ["RAY_TPU_JAX_NUM_PROCESSES"] = str(num_processes)
    os.environ["RAY_TPU_JAX_PROCESS_ID"] = str(process_id)
    jax.distributed.initialize(
        coordinator_address=address,
        num_processes=num_processes,
        process_id=process_id)


def _shutdown_jax_distributed() -> None:
    import jax
    try:
        jax.distributed.shutdown()
    except Exception:
        pass


class _JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxConfig) -> None:
        worker_group.fetch_metadata()  # refresh even if previously set
        worker_group.sort_workers_by_node()
        n_nodes = len({m.node_ip for m in worker_group.metadata})
        use_distributed = backend_config.distributed
        if use_distributed is None:
            use_distributed = n_nodes > 1
        if not use_distributed:
            return
        import uuid

        import ray_tpu
        key = f"jax-coord-{uuid.uuid4().hex[:12]}".encode()
        futures = []
        for rank, worker in enumerate(worker_group.workers):
            futures.append(worker.execute.remote(
                _setup_jax_distributed, key,
                backend_config.coordinator_port,
                len(worker_group), rank,
                backend_config.local_device_count))
        ray_tpu.get(futures)

    def on_shutdown(self, worker_group, backend_config: JaxConfig) -> None:
        if worker_group.workers:
            try:
                worker_group.execute(_shutdown_jax_distributed)
            except Exception:
                pass
