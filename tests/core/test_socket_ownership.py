"""One thread owns each ZeroMQ socket (``core/sockloop.py``).

A libzmq socket may be used by one thread at a time. The recording socket
below notes the thread of every call on every socket of this process while
a cluster runs with every cross-thread sender live; each socket of the
driver's ``Runtime``, of the ``Controller`` and of the ``NodeManager`` must
have seen exactly one thread. The soak at the end runs whole driver lives
in subprocesses, so that a SIGSEGV is a failed test and not a dead worker
of the test run.
"""

import collections
import json
import os
import subprocess
import sys
import threading
import time

import pytest
import zmq

import ray_tpu
from ray_tpu.core import chaos

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ------------------------------------------------------------- recording
class _Record:
    """socket id -> operation -> names of the threads that made it."""

    def __init__(self):
        self.uses = collections.defaultdict(
            lambda: collections.defaultdict(set))
        self.keep = []  # strong references: no id is handed out twice

    def note(self, sock, op):
        self.uses[id(sock)][op].add(threading.current_thread().name)


def _recording_socket(record):
    def noting(op):
        def call(self, *args, **kwargs):
            record.note(self, op)
            return getattr(zmq.Socket, op)(self, *args, **kwargs)
        return call

    class RecordingSocket(zmq.Socket):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            record.keep.append(self)
            record.note(self, "open")

        def close(self, *args, **kwargs):
            if not self.closed:  # __del__ closes again, on any thread
                record.note(self, "close")
            return super().close(*args, **kwargs)

    # send_multipart / recv_multipart go through send / recv
    for op in ("send", "recv", "poll", "bind", "connect"):
        setattr(RecordingSocket, op, noting(op))
    return RecordingSocket


def _sockets_of(owner):
    """Every zmq socket an object holds, by attribute name (dicts and
    lists of sockets, such as the peer DEALERs, included)."""
    found = {}

    def walk(name, value, depth=0):
        if isinstance(value, zmq.Socket):
            found[name] = value
        elif isinstance(value, dict) and depth < 2:
            for k, v in value.items():
                walk(f"{name}[{k!r:.12}]", v, depth + 1)
        elif isinstance(value, (list, tuple)) and depth < 2:
            for i, v in enumerate(value):
                walk(f"{name}[{i}]", v, depth + 1)

    for name, value in vars(owner).items():
        walk(name, value)
    return found


CHAOS_MIX = {
    # timers: delayed sends re-enter from threading.Timer threads
    "delay_prob": 0.3, "delay_range_s": [0.002, 0.04],
    "delay": {"HBT": 0.8},  # the node says little else: delay most of it
    # losses: the reliable layer's retransmit thread resends these
    "drop": {"DON": 0.3, "RES": 0.2, "DSP": 0.2, "ACL": 0.2, "TEV": 0.3,
             "PUT": 0.3, "HBT": 0.2},
}


@pytest.fixture(scope="module")
def recorded():
    """Run a cluster under the recording socket, drive it from many
    threads, shut it down; yield what each process's sockets saw."""
    record = _Record()
    ctx = zmq.Context.instance()
    old_class, old_poll = ctx._socket_class, zmq.Poller.poll

    def recording_poll(self, *args, **kwargs):
        for sock, _ in self.sockets:
            if isinstance(sock, zmq.Socket):
                record.note(sock, "poll")
        return old_poll(self, *args, **kwargs)

    ctx._socket_class = _recording_socket(record)
    zmq.Poller.poll = recording_poll
    os.environ[chaos.ENV_SEED] = "3131"
    os.environ[chaos.ENV_CONFIG] = json.dumps(CHAOS_MIX)
    owners, liveness = {}, {}
    try:
        ray_tpu.init(num_cpus=4, _num_initial_workers=1,
                     ignore_reinit_error=True)
        import ray_tpu.api as api
        from ray_tpu.core.global_state import global_worker
        rt, ctl, node = global_worker(), api._head.controller, api._head.node

        @ray_tpu.remote
        def echo(i):
            return i

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def add(self, k):
                self.n += k
                return self.n

        errors = []

        def drive(k):
            try:
                c = Counter.remote()
                for r in range(3):
                    refs = [echo.remote(k * 100 + r * 10 + i)
                            for i in range(10)]
                    assert ray_tpu.get(refs, timeout=120) == \
                        [k * 100 + r * 10 + i for i in range(10)]
                    assert ray_tpu.get(c.add.remote(1), timeout=120) == r + 1
                    rt.kv_put(f"k{k}".encode(), b"v", ns="own")
                    assert ctl.call_on_loop(lambda: len(ctl.peers)) > 0
                ray_tpu.kill(c)  # the node's reaper reports the exit
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=drive, args=(k,), name=f"drive{k}")
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(240)
        assert not errors and not any(t.is_alive() for t in threads), errors
        # two heartbeat periods and a few retransmit rounds
        time.sleep(2.5)
        for name, owner in (("runtime", rt), ("controller", ctl),
                            ("node", node)):
            owners[name] = _sockets_of(owner)
            liveness[name] = {
                "delayed": sum(n for (kind, _), n in
                               owner._chaos.stats.items() if kind == "delay"),
                "retransmits": owner._reliable.stats["retransmit"],
            }
    finally:
        try:
            ray_tpu.shutdown()
        finally:
            os.environ.pop(chaos.ENV_SEED, None)
            os.environ.pop(chaos.ENV_CONFIG, None)
            ctx._socket_class, zmq.Poller.poll = old_class, old_poll
    yield {name: {attr: dict(record.uses[id(sock)])
                  for attr, sock in socks.items()}
           for name, socks in owners.items()}, liveness


@pytest.mark.parametrize("process", ["runtime", "controller", "node"])
def test_each_socket_has_one_thread(recorded, process):
    seen, liveness = recorded
    socks = seen[process]
    # the traffic was real: the main socket was written, read, polled and
    # closed, and the cross-thread senders (chaos timers, retransmits) ran
    assert {"send", "recv", "poll", "close"} <= set(socks["sock"]), socks
    assert liveness[process]["delayed"] > 0, liveness
    assert sum(v["retransmits"] for v in liveness.values()) > 0, liveness
    shared = {attr: {op: sorted(names) for op, names in ops.items()}
              for attr, ops in socks.items()
              if len(set().union(*ops.values())) != 1}
    assert not shared, f"{process}: sockets used by several threads: {shared}"


# ----------------------------------------------------- the loop's pieces
def _pair(tmp_path):
    """A SocketLoop whose DEALER talks to a ROUTER this test reads."""
    from ray_tpu.core.sockloop import SocketLoop
    ctx = zmq.Context.instance()
    addr = f"ipc://{tmp_path}/loop.sock"
    router = ctx.socket(zmq.ROUTER)
    router.bind(addr)
    got = []

    def open_sockets():
        dealer = ctx.socket(zmq.DEALER)
        dealer.setsockopt(zmq.LINGER, 2000)
        dealer.connect(addr)
        return [(dealer, got.append)]

    return SocketLoop("test-loop", open_sockets), router, got


def _read_all(router, n, timeout_s=20.0):
    out, deadline = [], time.monotonic() + timeout_s
    while len(out) < n and time.monotonic() < deadline:
        if router.poll(100):
            out.append(router.recv_multipart()[1:])
    return out


def test_posts_from_32_threads_lose_no_frame(tmp_path):
    loop, router, _ = _pair(tmp_path)
    loop.start()
    per_thread, n_threads = 200, 32
    barrier = threading.Barrier(n_threads)
    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def post(k):
        barrier.wait(30)
        for i in range(per_thread):
            loop.post([b"%d" % k, b"%d" % i])

    try:
        threads = [threading.Thread(target=post, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
        frames = _read_all(router, per_thread * n_threads)
    finally:
        sys.setswitchinterval(old_interval)
        loop.stop(wait_s=10.0)
        router.close(0)
    assert len(frames) == per_thread * n_threads
    by_thread = collections.defaultdict(list)
    for k, i in frames:
        by_thread[k].append(int(i))
    # nothing lost, and each poster's frames in the order it posted them
    assert all(seq == list(range(per_thread)) for seq in by_thread.values())
    assert len(by_thread) == n_threads


def test_stop_sends_what_was_posted_before_it(tmp_path):
    loop, router, _ = _pair(tmp_path)
    loop.start()
    for i in range(500):
        loop.post([b"late", b"%d" % i])
    loop.stop(wait_s=10.0)
    try:
        frames = _read_all(router, 500)
    finally:
        router.close(0)
    assert [int(i) for _, i in frames] == list(range(500))
    loop.post([b"after", b"0"])  # a late sender finds no fd to write to
    loop.call(lambda: None)


def test_loop_reads_and_survives_a_failing_handler(tmp_path):
    from ray_tpu.core.sockloop import SocketLoop
    ctx = zmq.Context.instance()
    addr = f"ipc://{tmp_path}/in.sock"
    got, cycles = [], []

    def handle(frames):
        if frames[1] == b"bad":
            raise RuntimeError("handler failed")
        got.append(frames[1])

    def open_sockets():
        router = ctx.socket(zmq.ROUTER)
        router.setsockopt(zmq.LINGER, 0)
        router.bind(addr)
        return [(router, handle)]

    loop = SocketLoop("test-loop", open_sockets,
                      each_cycle=lambda: cycles.append(1))
    loop.start()
    dealer = ctx.socket(zmq.DEALER)
    dealer.setsockopt(zmq.LINGER, 0)
    dealer.connect(addr)
    try:
        for body in (b"a", b"bad", b"b"):
            dealer.send_multipart([body])
        deadline = time.monotonic() + 20
        while len(got) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        loop.stop(wait_s=10.0)
        dealer.close(0)
    assert got == [b"a", b"b"] and cycles


def test_open_error_is_raised_by_start():
    from ray_tpu.core.sockloop import SocketLoop

    def open_sockets():
        raise OSError("cannot bind")

    with pytest.raises(OSError, match="cannot bind"):
        SocketLoop("test-loop", open_sockets).start()


def test_runtime_shutdown_delivers_the_last_messages():
    """``Runtime.shutdown``: the flusher drains, then the pump drains its
    outbox, then the sockets close — a message enqueued just before
    shutdown reaches the controller."""
    ray_tpu.init(num_cpus=1, _num_initial_workers=0,
                 ignore_reinit_error=True)
    try:
        import ray_tpu.api as api
        from ray_tpu.core import protocol as P
        from ray_tpu.core.global_state import (
            global_worker, set_global_worker)
        rt, ctl = global_worker(), api._head.controller
        for i in range(200):
            rt._send(P.KV_OP, {"op": "put", "ns": "last", "key": b"%d" % i,
                               "value": b"x", "rid": b"r%d" % i})
        rt.shutdown()
        set_global_worker(None)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            n = ctl.call_on_loop(lambda: len(ctl.kv.get("last", {})))
            if n == 200:
                break
            time.sleep(0.05)
        assert n == 200
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------------------ soak
_DRIVER = r"""
import sys, threading
sys.path.insert(0, sys.argv[1])
import ray_tpu
from ray_tpu import serve

ray_tpu.init(num_cpus=4, _num_initial_workers=2)

@ray_tpu.remote
def echo(i):
    return i

errors = []
def submit(k):
    try:
        for r in range(6):
            want = [k * 1000 + r * 20 + i for i in range(20)]
            assert ray_tpu.get([echo.remote(x) for x in want],
                               timeout=120) == want
    except BaseException as e:
        errors.append(e)

threads = [threading.Thread(target=submit, args=(k,)) for k in range(6)]
for t in threads:
    t.start()

@serve.deployment
def double(x):
    return x * 2

assert serve.run(double.bind()).remote(21).result() == 42
for t in threads:
    t.join(240)
assert not errors and not any(t.is_alive() for t in threads), errors
serve.shutdown()
ray_tpu.shutdown()
"""


def test_driver_lives_end_with_exit_code_0(tmp_path):
    """About ten seconds of whole driver lives (init, concurrent submits
    from six threads while results stream back, a trivial ``serve.run``,
    shutdown), each in a fresh subprocess: the shape of the benchmark run
    that died of a socket shared between threads."""
    script = tmp_path / "driver.py"
    script.write_text(_DRIVER)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    lives, deadline = 0, time.monotonic() + 10.0
    while lives < 2 or time.monotonic() < deadline:
        p = subprocess.run(
            [sys.executable, "-X", "faulthandler", str(script), REPO],
            env=env, capture_output=True, text=True, timeout=300)
        lives += 1
        assert p.returncode == 0, \
            f"life {lives} ended with {p.returncode}:\n{p.stderr[-4000:]}"
