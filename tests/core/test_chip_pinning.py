"""One process for each chip: who is asked about TPUs never opens one,
and a worker that is given chips is told which before it imports jax.

CPU-only hosts have no chip to open, but the mechanism is all
observable: what ``TPU_VISIBLE_CHIPS`` a worker sees and when, whether
a backend exists in the driver, which worker chip work lands on."""

import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_DRIVER = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
import jax                      # imported — the case init() must survive
import ray_tpu
from ray_tpu.core.accelerators import jax_backend_initialized

ray_tpu.init(num_cpus=4, num_tpus=2, _num_initial_workers=1)
assert not jax_backend_initialized(), "init() created a jax backend"


def seen():
    from ray_tpu.core.accelerators import jax_backend_initialized as jbi
    return (os.environ.get("TPU_VISIBLE_CHIPS"),
            os.environ.get("TPU_CHIPS_PER_PROCESS_BOUNDS"),
            "jax" in sys.modules, jbi(), os.getpid())


@ray_tpu.remote(num_tpus=1)
class Holder:
    def __init__(self):
        self.at_init = seen()     # before this actor touches jax

    def at_init_(self):
        return self.at_init


@ray_tpu.remote
def touch_jax():
    import jax
    jax.devices()
    return os.getpid()


@ray_tpu.remote(num_tpus=1)
def chip_task():
    return seen()


# a pool worker initialises jax, then goes back to the pool
used_pid = ray_tpu.get(touch_jax.remote(), timeout=120)
a, b = Holder.remote(), Holder.remote()
ea, eb = ray_tpu.get([a.at_init_.remote(), b.at_init_.remote()],
                     timeout=120)
print("ACTORS", sorted([ea[0], eb[0]]), ea[1], ea[2], ea[3], eb[3])
assert used_pid not in (ea[4], eb[4]), "chip work reused a jax worker"
# both chips taken: a third holder waits for a worker to EXIT
c = Holder.remote()
time.sleep(1.5)
ray_tpu.kill(a)
ec = ray_tpu.get(c.at_init_.remote(), timeout=120)
print("THIRD", ec[0], ec[4] not in (ea[4], eb[4]))
ray_tpu.kill(b)
t1 = ray_tpu.get(chip_task.remote(), timeout=120)
# the result reaches the driver straight from the worker: a task submitted
# at once can reach the controller before t1's completion does, and is then
# t1's lease's next task, on the same worker by design (_bind_chips). Let
# the lease close first; what is held is that its worker goes with it.
time.sleep(1.5)
t2 = ray_tpu.get(chip_task.remote(), timeout=120)
print("TASKS", t1[0], t2[0], t1[3], t1[4] != t2[4])
assert not jax_backend_initialized(), "the driver opened a backend"
ray_tpu.shutdown()
print("DONE")
"""


def test_chip_work_is_pinned_before_jax_and_the_driver_stays_off():
    r = subprocess.run(
        [sys.executable, "-c", _DRIVER.format(repo=REPO)],
        capture_output=True, text=True, timeout=300, cwd="/tmp",
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    out = dict(ln.split(" ", 1) for ln in r.stdout.splitlines()
               if " " in ln)
    # two actors, two different chips, env in place with jax not even
    # imported and no backend
    assert out["ACTORS"] == "['0', '1'] 1,1,1 False False False"
    # the third got the killed actor's chip, in a new process
    assert out["THIRD"] == "0 True"
    # chip tasks: pinned too, no backend yet when they start, and the
    # worker is retired with its lease (next task: a new process)
    assert out["TASKS"] == "1 1 False True"
    assert "DONE" in r.stdout


def test_chip_count_never_asks_jax(monkeypatch):
    from ray_tpu.core import accelerators as A
    for var in ("TPU_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS",
                "TPU_ACCELERATOR_TYPE", "ACCELERATOR_TYPE"):
        monkeypatch.delenv(var, raising=False)
    boom = types.SimpleNamespace(
        local_devices=lambda: pytest.fail("asked jax for devices"),
        devices=lambda: pytest.fail("asked jax for devices"))
    monkeypatch.setitem(sys.modules, "jax", boom)
    monkeypatch.setattr(A, "_CHIP_DEVICE_GLOBS", ("/nonexistent/accel*",))
    assert A.tpu_chip_count() == 0
    # device files outrank the host-topology env a one-chip VM inherits
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    assert A.tpu_chip_count() == 4


def test_device_files_outrank_topology_env(monkeypatch, tmp_path):
    from ray_tpu.core import accelerators as A
    (tmp_path / "0").touch()
    (tmp_path / "vfio").touch()           # the container node: no chip
    monkeypatch.delenv("TPU_CHIPS", raising=False)
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setattr(A, "_CHIP_DEVICE_GLOBS",
                        (str(tmp_path / "[0-9]*"),))
    assert A.tpu_chip_count() == 1


def test_pinning_after_backend_init_is_an_error():
    import jax
    from ray_tpu.core import accelerators as A
    jax.devices()
    assert A.jax_backend_initialized()
    with pytest.raises(RuntimeError, match="already initialized"):
        A.set_visible_chips([0])
    assert "TPU_VISIBLE_CHIPS" not in os.environ


def test_probe_devices_reports_what_a_child_sees():
    from ray_tpu.core.accelerators import probe_devices
    out = probe_devices()
    assert out["platform"] == "cpu" and out["count"] >= 1
