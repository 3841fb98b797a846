"""Core API smoke tests (modeled on the reference's
``python/ray/tests/test_basic.py``)."""

import time

import numpy as np
import pytest

import ray_tpu


def test_put_get(ray_start_shared):
    ref = ray_tpu.put(42)
    assert ray_tpu.get(ref) == 42
    ref2 = ray_tpu.put({"a": [1, 2, 3]})
    assert ray_tpu.get(ref2) == {"a": [1, 2, 3]}


def test_put_get_large_array(ray_start_shared):
    arr = np.arange(1_000_000, dtype=np.float32)  # 4MB > inline threshold
    ref = ray_tpu.put(arr)
    out = ray_tpu.get(ref)
    np.testing.assert_array_equal(arr, out)


def test_remote_function(ray_start_shared):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2)) == 3


def test_remote_function_with_ref_args(ray_start_shared):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    x = ray_tpu.put(10)
    y = add.remote(x, 5)
    z = add.remote(y, x)
    assert ray_tpu.get(z) == 25


def test_large_args_and_returns(ray_start_shared):
    @ray_tpu.remote
    def double(a):
        return a * 2

    arr = np.ones(500_000, dtype=np.float64)
    ref = double.remote(arr)
    np.testing.assert_array_equal(ray_tpu.get(ref), arr * 2)


def test_multiple_returns(ray_start_shared):
    @ray_tpu.remote(num_returns=2)
    def two():
        return 1, 2

    a, b = two.remote()
    assert ray_tpu.get(a) == 1
    assert ray_tpu.get(b) == 2


def test_task_error_propagates(ray_start_shared):
    @ray_tpu.remote
    def boom():
        raise ValueError("kaboom")

    with pytest.raises(ray_tpu.TaskError) as ei:
        ray_tpu.get(boom.remote())
    assert "kaboom" in str(ei.value)


def test_nested_tasks(ray_start_shared):
    @ray_tpu.remote
    def inner(x):
        return x + 1

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x)) + 10

    assert ray_tpu.get(outer.remote(1)) == 12


def test_wait(ray_start_shared):
    @ray_tpu.remote
    def fast():
        return "fast"

    @ray_tpu.remote
    def slow():
        time.sleep(60)
        return "slow"

    f, s = fast.remote(), slow.remote()
    ready, pending = ray_tpu.wait([f, s], num_returns=1, timeout=20)
    assert ready == [f]
    assert pending == [s]


def test_get_timeout(ray_start_shared):
    @ray_tpu.remote
    def slow():
        time.sleep(10)

    with pytest.raises(ray_tpu.GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.5)


def test_cluster_resources(ray_start_shared):
    res = ray_tpu.cluster_resources()
    assert res.get("CPU") == 4.0
    nodes = ray_tpu.nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]


def test_main_module_class_round_trip():
    """Classes defined in the driver's __main__ must survive both
    directions (arg and return). Regression for the C-pickle fast path:
    plain pickle encodes __main__ globals BY REFERENCE without raising,
    which a worker can't resolve — serialization must detect that and
    fall back to cloudpickle's by-value treatment."""
    import os
    import subprocess
    import sys
    import textwrap
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {repo!r})
        from dataclasses import dataclass
        import ray_tpu

        @dataclass
        class Point:
            x: int
            y: int

        @ray_tpu.remote
        def bump(p):
            return Point(p.x + 1, p.y + 1)

        ray_tpu.init(num_cpus=2, _num_initial_workers=1)
        out = ray_tpu.get(bump.remote(Point(1, 2)), timeout=120)
        assert (out.x, out.y) == (2, 3), out
        # __main__ function object as an arg too
        def double(v):
            return v * 2

        @ray_tpu.remote
        def apply(fn, v):
            return fn(v)

        assert ray_tpu.get(apply.remote(double, 21), timeout=120) == 42
        ray_tpu.shutdown()
        print("MAIN-OK")
    """)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=240,
        env=dict(os.environ))
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "MAIN-OK" in proc.stdout
