"""Serve tests, modeled on the reference's ``python/ray/serve/tests``:
real controller + replicas on a local cluster, handle composition,
batching, scaling, HTTP."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


def test_basic_deployment_and_handle(serve_session):
    @serve.deployment
    class Greeter:
        def __call__(self, name):
            return f"hello {name}"

        def shout(self, name):
            return f"HELLO {name}!"

    handle = serve.run(Greeter.bind(), route_prefix="/greet")
    assert handle.remote("tpu").result() == "hello tpu"
    assert handle.shout.remote("tpu").result() == "HELLO tpu!"


def test_function_deployment(serve_session):
    @serve.deployment
    def double(x):
        return x * 2

    handle = serve.run(double.bind())
    assert handle.remote(21).result() == 42


def test_multi_replica_routing(serve_session):
    @serve.deployment(num_replicas=3)
    class Worker:
        def __init__(self):
            import os
            self.pid = os.getpid()

        def __call__(self, _):
            return self.pid

    handle = serve.run(Worker.bind())
    pids = {handle.remote(None).result() for _ in range(20)}
    assert len(pids) >= 2  # pow-2 routing spreads load


def test_model_composition(serve_session):
    @serve.deployment
    class Preprocess:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class Model:
        def __init__(self, pre):
            self.pre = pre

        def __call__(self, x):
            y = self.pre.remote(x).result()
            return y * 10

    handle = serve.run(Model.bind(Preprocess.bind()))
    assert handle.remote(4).result() == 50


def test_init_args_and_user_config(serve_session):
    @serve.deployment(user_config={"scale": 3})
    class Scaler:
        def __init__(self, base):
            self.base = base
            self.scale = 1

        def reconfigure(self, config):
            self.scale = config["scale"]

        def __call__(self, x):
            return (x + self.base) * self.scale

    handle = serve.run(Scaler.bind(10))
    assert handle.remote(1).result() == 33


def test_batching(serve_session):
    @serve.deployment
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
        async def __call__(self, items):
            self.batch_sizes.append(len(items))
            return [i * 2 for i in items]

        def get_batch_sizes(self):
            return self.batch_sizes

    handle = serve.run(Batched.bind())
    responses = [handle.remote(i) for i in range(8)]
    assert [r.result() for r in responses] == [i * 2 for i in range(8)]
    sizes = handle.get_batch_sizes.remote().result()
    assert max(sizes) > 1  # requests actually batched


@pytest.mark.slow
def test_replica_failure_recovery(serve_session):
    @serve.deployment(num_replicas=1, health_check_period_s=0.5)
    class Fragile:
        def __call__(self, x):
            return x

        def die(self):
            import os
            os._exit(1)

    handle = serve.run(Fragile.bind())
    assert handle.remote(1).result() == 1
    try:
        handle.die.remote().result(timeout_s=5)
    except Exception:
        pass
    # controller health check replaces the dead replica
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            assert handle.remote(2).result(timeout_s=10) == 2
            break
        except Exception:
            time.sleep(0.5)
    else:
        pytest.fail("replica never recovered")


def test_http_proxy(serve_session):
    @serve.deployment
    class Echo:
        def __call__(self, payload):
            return {"got": payload}

    serve.run(Echo.bind(), route_prefix="/echo")
    serve.start(http_options={"port": 0})
    addr = serve.proxy_address()
    req = urllib.request.Request(
        addr + "/echo", data=json.dumps({"x": 1}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        out = json.loads(resp.read())
    assert out == {"got": {"x": 1}}


def test_grpc_proxy(serve_session):
    """gRPC ingress (reference: serve's RayServeAPIService gRPC proxy
    alongside HTTP)."""
    pytest.importorskip("grpc")
    from ray_tpu.serve._private.grpc_proxy import grpc_call, grpc_healthz

    @serve.deployment
    class Scale:
        def __call__(self, x, factor=10):
            return x * factor

    serve.run(Scale.bind(), name="scaler")
    serve.start(grpc_options={"port": 0})
    addr = serve.grpc_proxy_address()
    assert addr is not None
    assert grpc_healthz(addr) == "OK"
    assert grpc_call(addr, "scaler", 4) == 40
    assert grpc_call(addr, "scaler", 3, factor=7) == 21
    from ray_tpu.serve._private.grpc_proxy import grpc_list_applications
    assert "scaler" in grpc_list_applications(addr)
    with pytest.raises(RuntimeError, match="No application"):
        grpc_call(addr, "nope", 1)


def test_status_and_delete(serve_session):
    @serve.deployment(num_replicas=2)
    class Thing:
        def __call__(self):
            return "ok"

    serve.run(Thing.bind())
    deadline = time.time() + 10
    while time.time() < deadline:
        deps = {d["name"]: d for d in serve.status()["deployments"]}
        if "Thing" in deps and deps["Thing"]["num_replicas"] == 2:
            break
        time.sleep(0.2)
    assert deps["Thing"]["target_num_replicas"] == 2
    serve.delete("Thing")
    deps = {d["name"] for d in serve.status()["deployments"]}
    assert "Thing" not in deps


def test_streaming_response(serve_session):
    @serve.deployment
    class Streamer:
        def gen(self, n):
            for i in range(n):
                yield i * 10

    h = serve.run(Streamer.bind())
    gen = h.options(stream=True).gen.remote(5)
    assert list(gen) == [0, 10, 20, 30, 40]
    # request context is visible inside the generator body
    @serve.deployment
    class CtxStreamer:
        def gen(self):
            yield serve.get_multiplexed_model_id()

    hc = serve.run(CtxStreamer.bind(), name="ctxstream")
    out = list(hc.options(stream=True, multiplexed_model_id="mm-1")
               .gen.remote())
    assert out == ["mm-1"]
    # early break cancels the replica-side stream instead of leaking it
    gen2 = h.options(stream=True).gen.remote(1000)
    next(gen2)
    gen2.cancel()
    # a non-generator method under stream=True must raise at consumption
    @serve.deployment
    class NotAGen:
        def __call__(self):
            return 42

    h2 = serve.run(NotAGen.bind(), name="notagen")
    import pytest as _pytest
    with _pytest.raises(Exception):
        list(h2.options(stream=True).remote())


def test_multiplexed_model_id(serve_session):
    @serve.deployment(num_replicas=2)
    class Model:
        def __call__(self):
            return serve.get_multiplexed_model_id()

    h = serve.run(Model.bind())
    out = h.options(multiplexed_model_id="m-7").remote().result(timeout_s=60)
    assert out == "m-7"
    # plain calls see an empty model id
    assert h.remote().result(timeout_s=60) == ""
    # unknown handle options raise instead of silently no-oping
    import pytest as _pytest
    with _pytest.raises(TypeError):
        h.options(bogus_option=1)


def test_health_check_does_not_kill_a_replica_that_is_still_starting(
        monkeypatch):
    """A replica whose constructor is still running (weights and
    compilation take minutes on a TPU) answers no health check yet:
    that is a timeout, and for a starting replica a timeout is not
    death. A constructor that FAILS raises at once, and a replica that
    has answered before gets the plain 30 s rule."""
    from ray_tpu.exceptions import ActorDiedError, GetTimeoutError
    from ray_tpu.serve._private import controller as C

    class FakeReplica:
        def __init__(self, name):
            self.name = name
            self.check_health = self
            self.killed = False

        def remote(self):
            return ("health-ref", self.name)

    outcomes = {}

    def fake_get(ref, timeout=None):
        exc = outcomes.get(ref[1])
        if exc is not None:
            raise exc
        return None

    monkeypatch.setattr(C.ray_tpu, "get", fake_get)
    monkeypatch.setattr(C.ray_tpu, "kill",
                        lambda r: setattr(r, "killed", True))
    ctl = object.__new__(C.ServeController)
    import threading
    ctl._lock = threading.RLock()
    info = object.__new__(C._DeploymentInfo)
    slow, broken, old, ready = (FakeReplica(n) for n in
                                ("slow", "broken", "old", "ready"))
    info.replicas = [slow, broken, old, ready]
    info.version = 0
    now = __import__("time").time()
    info.starting = {
        slow: (now, ("health-ref", "slow")),
        broken: (now, ("health-ref", "broken")),
        old: (now - C.REPLICA_STARTUP_TIMEOUT_S - 1,
              ("health-ref", "old")),
        ready: (now, ("health-ref", "ready")),
    }
    outcomes.update(slow=GetTimeoutError("still constructing"),
                    broken=ActorDiedError(None, "constructor raised"),
                    old=GetTimeoutError("still constructing"))
    ctl._health_check("d", info)
    assert info.replicas == [slow, ready]
    assert not slow.killed and broken.killed and old.killed
    assert slow in info.starting and ready not in info.starting
    # once it has answered, a timeout does mean unhealthy
    outcomes["ready"] = GetTimeoutError("wedged")
    ctl._health_check("d", info)
    assert info.replicas == [slow] and ready.killed


def test_a_long_sync_handler_does_not_starve_the_replicas_other_calls(
        serve_session):
    """A sync handler runs off the replica's event loop: while one is
    busy for seconds (a profiler trace being reduced, weights being
    dequantised), the replica still answers its controller's health
    check and serves its async methods — the 30 s rule would otherwise
    kill a healthy replica for being asked something slow. Sync handlers
    still never overlap each other, and still see their request's
    context."""
    import threading

    @serve.deployment(max_ongoing_requests=8)
    class Slow:
        def __init__(self):
            self.inside = 0
            self.overlapped = False

        def grind(self, seconds):
            self.inside += 1
            self.overlapped |= self.inside > 1
            time.sleep(seconds)
            self.inside -= 1
            return (serve.get_multiplexed_model_id(),
                    threading.current_thread().name)

        async def quick(self):
            return "quick"

        def report(self):
            return self.overlapped

    serve.run(Slow.bind(), name="slow")
    from ray_tpu.serve.api import CONTROLLER_NAME
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    replica = ray_tpu.get(controller.get_replicas.remote("Slow"))[0]
    ray_tpu.get(replica.check_health.remote(), timeout=30)

    busy = [replica.handle_request_ctx.remote(
        {"multiplexed_model_id": f"m{i}"}, "grind", 1.5) for i in range(2)]
    time.sleep(0.3)                      # the first grind is under way
    t0 = time.monotonic()
    assert ray_tpu.get(replica.check_health.remote(), timeout=30) is True
    assert ray_tpu.get(replica.handle_request.remote("quick"),
                       timeout=30) == "quick"
    assert time.monotonic() - t0 < 1.0, "waited for the sync handler"
    got = ray_tpu.get(busy, timeout=60)
    assert [g[0] for g in got] == ["m0", "m1"]
    assert all(g[1] != "actor-asyncio" for g in got)
    assert ray_tpu.get(replica.handle_request.remote("report"),
                       timeout=30) is False
