"""Controller crash/restart recovery (reference: GCS server restart with
redis persistence + raylet reconnect, node_manager.cc:1114): durable
KV/named actors survive, live nodes/workers/drivers re-announce, and
in-flight work resumes."""

import os
import time

import pytest

import ray_tpu


@pytest.fixture
def cluster():
    info = ray_tpu.init(num_cpus=2, _num_initial_workers=1,
                        ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()


def _crash_and_restart_controller():
    """Simulate kill -9: abandon the old controller object without any
    graceful state flush (durability must come from the synchronous WAL
    alone) and start a fresh controller on the same session."""
    import ray_tpu.api as api
    from ray_tpu.core.controller import Controller
    head = api._head
    old = head.controller
    old.halt()                   # stop loops without any state flush
    head.controller = Controller(head.session_dir, old.config)
    head.controller.start()
    return head.controller


def test_state_survives_controller_restart(cluster):
    from ray_tpu.core.global_state import global_worker

    # durable state before the crash
    w = global_worker()
    w.kv_put(b"persist-key", b"persist-value", ns="testns")

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0
        def inc(self):
            self.n += 1
            return self.n

    c = Counter.options(name="survivor", lifetime="detached").remote()
    assert ray_tpu.get(c.inc.remote(), timeout=60) == 1

    new = _crash_and_restart_controller()

    # wait on the reconnect itself, not on a wall-clock guess: the
    # driver's next ping draws a RECONNECT from the new controller, and
    # handling it stamps the new generation
    deadline = time.time() + 120
    while w._reconnect_gen != new.generation and time.time() < deadline:
        time.sleep(0.05)
    assert w._reconnect_gen == new.generation

    # KV recovered from the WAL. A request written to the dead ROUTER's
    # connection is lost for good (RPCs have no retransmit): short
    # per-request timeouts, so one loss costs 5 s and not rpc_timeout_s
    from ray_tpu.core import protocol as P
    val = None
    while time.time() < deadline:
        try:
            val = w.request(P.KV_OP, {"op": "get", "ns": "testns",
                                      "key": b"persist-key"},
                            timeout=5.0)["value"]
            break
        except Exception:
            time.sleep(0.1)
    assert val == b"persist-value"

    # the existing handle still works: calls ride the direct channel to
    # the surviving worker process
    assert ray_tpu.get(c.inc.remote(), timeout=60) == 2

    # named lookup resolves after the actor worker re-announces itself
    deadline = time.time() + 60
    h = None
    while time.time() < deadline:
        try:
            h = ray_tpu.get_actor("survivor")
            break
        except Exception:
            time.sleep(0.5)
    assert h is not None
    assert ray_tpu.get(h.inc.remote(), timeout=60) == 3

    # brand-new tasks schedule onto re-announced nodes/workers
    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(41), timeout=120) == 42


@pytest.mark.slow
def test_inflight_tasks_resubmitted_after_restart(cluster):
    @ray_tpu.remote
    def slow(x):
        import time as t
        t.sleep(4)
        return x * 2

    # queued/starting when the controller dies
    refs = [slow.remote(i) for i in range(3)]
    time.sleep(0.3)
    _crash_and_restart_controller()
    # owners resubmit on RECONNECT; results still arrive
    assert sorted(ray_tpu.get(refs, timeout=180)) == [0, 2, 4]
