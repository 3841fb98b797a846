"""Ray Client (``ray://``) end-to-end (reference:
``python/ray/util/client/worker.py:81`` + ``server/server.py``): a
process that is NOT part of the cluster drives it over TCP."""

import os
import subprocess
import sys
import textwrap

import pytest

import ray_tpu
from ray_tpu.util.client import ClientServer

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def client_server(ray_start_shared):
    srv = ClientServer(host="127.0.0.1", port=0 or 10055).start()
    yield "ray://127.0.0.1:10055"
    srv.stop()


CLIENT_SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, {repo!r})
    import ray_tpu

    # the standard pattern: decorated at import time, BEFORE init —
    # client mode must route these at call time
    @ray_tpu.remote
    def pre_init_double(x):
        return x * 2

    @ray_tpu.remote
    class PreInitActor:
        def hello(self):
            return "hi"

    info = ray_tpu.init({addr!r})
    assert info.get("client") is True
    assert ray_tpu.is_initialized()

    # put / get / wait
    ref = ray_tpu.put({{"k": [1, 2, 3]}})
    assert ray_tpu.get(ref) == {{"k": [1, 2, 3]}}
    refs = [ray_tpu.put(i) for i in range(4)]
    ready, pending = ray_tpu.wait(refs, num_returns=4, timeout=30)
    assert len(ready) == 4 and not pending

    # remote functions, incl. passing client refs as args
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(2, 3), timeout=60) == 5
    r1 = add.remote(ray_tpu.put(10), 5)
    r2 = add.remote(r1, ray_tpu.put(1))
    assert ray_tpu.get(r2, timeout=60) == 16

    # options pass through
    @ray_tpu.remote(num_returns=2)
    def pair():
        return "x", "y"

    a, b = pair.remote()
    assert ray_tpu.get(a, timeout=60) == "x"
    assert ray_tpu.get(b, timeout=60) == "y"

    # actors
    @ray_tpu.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start
        def incr(self, k=1):
            self.n += k
            return self.n
        def value(self):
            return self.n

    c = Counter.remote(100)
    assert ray_tpu.get(c.incr.remote(), timeout=60) == 101
    assert ray_tpu.get(c.incr.remote(4), timeout=60) == 105
    assert ray_tpu.get(c.value.remote(), timeout=60) == 105
    ray_tpu.kill(c)

    # pre-init decorators route through the client
    assert ray_tpu.get(pre_init_double.remote(21), timeout=60) == 42
    pa = PreInitActor.remote()
    assert ray_tpu.get(pa.hello.remote(), timeout=60) == "hi"
    ray_tpu.kill(pa)

    # cluster introspection
    assert ray_tpu.cluster_resources().get("CPU", 0) > 0
    assert len(ray_tpu.nodes()) >= 1

    ray_tpu.shutdown()
    print("CLIENT-OK")
""")


def test_ray_client_end_to_end(client_server):
    script = CLIENT_SCRIPT.format(repo=REPO, addr=client_server)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=240,
        env=dict(os.environ))
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "CLIENT-OK" in proc.stdout


@pytest.mark.slow
def test_client_get_outlives_connection_timeout(client_server,
                                                ray_start_shared):
    """A task running longer than the client's connection timeout must
    still be gettable with timeout=None (the client re-polls in bounded
    slices; no single RPC spans the task's runtime). Regression for the
    30s-cap bug: get(timeout=None) used to inherit the connect timeout."""
    script = textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {REPO!r})
        import ray_tpu
        ray_tpu.init({client_server!r})
        @ray_tpu.remote
        def slow():
            time.sleep(6.0)
            return "done"
        ref = slow.remote()
        # also exercise wait() blocking past one slice
        ready, pending = ray_tpu.wait([ref], num_returns=1, timeout=None)
        assert len(ready) == 1, (ready, pending)
        assert ray_tpu.get(ref) == "done"
        # and a get() with a too-short timeout raises GetTimeoutError
        from ray_tpu.exceptions import GetTimeoutError
        ref2 = slow.remote()
        t0 = time.monotonic()
        try:
            ray_tpu.get(ref2, timeout=1.0)
            raise AssertionError("expected GetTimeoutError")
        except GetTimeoutError:
            pass
        assert time.monotonic() - t0 < 5.0
        ray_tpu.shutdown()
        print("SLOW-OK")
    """)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=240,
        env={**os.environ,
             "RAY_TPU_CLIENT_TIMEOUT": "4"})
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "SLOW-OK" in proc.stdout


def test_client_disconnect_releases_leases(client_server, ray_start_shared):
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import ray_tpu
        ray_tpu.init({client_server!r})
        ref = ray_tpu.put(list(range(100)))
        assert ray_tpu.get(ref)[-1] == 99
        ray_tpu.shutdown()
        print("DONE")
    """)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=240,
        env=dict(os.environ))
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    # the host cluster is still healthy after the client went away
    assert ray_tpu.get(ray_tpu.put(1)) == 1
