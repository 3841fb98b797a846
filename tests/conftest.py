"""Test fixtures (modeled on the reference's
``python/ray/tests/conftest.py``: ``ray_start_regular`` /
``ray_start_regular_shared``).

Tests run on a virtual 8-device CPU mesh: ``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count=8`` are set here, before jax is
imported, and worker subprocesses inherit both through the environment.
"""

import atexit
import os
import shutil
import sys
import tempfile

# Isolate the compile-cache root (util/compile_cache.py) per run: an
# autotune winner persisted by one run must not short-circuit the next
# run's autotune tests. Workers inherit the env, so they share the
# run's scratch dir. The XLA compile cache itself stays off in tests —
# every run compiles what it runs.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="ray-tpu-test-cache-")
    atexit.register(shutil.rmtree,
                    os.environ["JAX_COMPILATION_CACHE_DIR"],
                    ignore_errors=True)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Failure-header replay line: any test that fails while chaos
    injection is active prints the seed (and config) that reproduces
    its fault schedule — a red chaos run is replayable from the log
    alone."""
    outcome = yield
    rep = outcome.get_result()
    if rep.failed:
        seed = os.environ.get("RAY_TPU_CHAOS_SEED")
        if seed:
            line = f"replay with: RAY_TPU_CHAOS_SEED={seed}"
            cfg = os.environ.get("RAY_TPU_CHAOS_CONFIG")
            if cfg:
                line += f" RAY_TPU_CHAOS_CONFIG='{cfg}'"
            postmortem = os.environ.get("RAY_TPU_CHAOS_POSTMORTEM_FILE")
            if postmortem:
                line += ("\nflight-recorder postmortem: "
                         f"{postmortem} (render with: python "
                         f"tools/timeline.py --input {postmortem})")
            rep.sections.append(("chaos seed", line))


@pytest.fixture(autouse=True)
def _metrics_registry_isolation():
    """Scoped metric-registry reset (util/metrics.py): metrics a test
    registers are unregistered afterwards, so ``_registry`` doesn't
    grow across the run and one test's labelsets can't bleed into
    another's Prometheus/fleet snapshot. The process-wide runtime
    catalog (core/metric_defs.py) is pinned BEFORE the mark so it is
    never dropped."""
    from ray_tpu.core.metric_defs import runtime_metrics
    from ray_tpu.util import metrics as _mx
    runtime_metrics()
    mark = _mx.registry_snapshot()
    yield
    _mx.restore_registry(mark)


@pytest.fixture
def ray_start_regular():
    import ray_tpu
    info = ray_tpu.init(num_cpus=4, _num_initial_workers=2,
                        ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_start_shared():
    import ray_tpu
    info = ray_tpu.init(num_cpus=4, _num_initial_workers=2,
                        ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"
    return jax.devices()
