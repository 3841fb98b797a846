"""Device-array serialization fast path (core/serialization.py).

A jax.Array anywhere in a stored value must ship as an out-of-band
buffer — one memcpy into shm, a zero-copy ``np.frombuffer`` view back
out — instead of riding the pickle stream in-band. This is what keeps
MPMD pipeline activations (and any (value, aux) tuples containing
device arrays) off the pickle path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.core.serialization import SerializationContext, to_host


@pytest.fixture
def ctx():
    return SerializationContext()


def _roundtrip(ctx, value):
    so = ctx.serialize(value)
    out, _refs, bufs = ctx.deserialize_from_view_tracked(
        memoryview(so.to_bytes()))
    return so, out, bufs


def test_nested_device_array_ships_out_of_band(ctx):
    act = jnp.arange(64 * 1024, dtype=jnp.float32).reshape(256, 256)
    so, out, _ = _roundtrip(ctx, {"act": act, "tag": ("F", 3)})
    # the payload must NOT be in the pickle stream: meta stays tiny
    assert len(so.meta) < 4096, len(so.meta)
    assert any(b.nbytes == act.nbytes for b in so.buffers)
    np.testing.assert_array_equal(np.asarray(act), out["act"])
    assert out["tag"] == ("F", 3)


def test_restore_is_zero_copy_view(ctx):
    act = jnp.ones((512, 64), jnp.float32)
    _, out, _ = _roundtrip(ctx, [act])
    restored = out[0]
    # frombuffer view: backed by the wire buffer, not a fresh copy
    assert restored.base is not None


def test_bfloat16_roundtrips(ctx):
    # extension dtypes refuse the buffer protocol; the fast path ships
    # a uint8 view and restores the dtype by name via ml_dtypes
    act = (jnp.arange(128 * 128, dtype=jnp.float32)
           .reshape(128, 128).astype(jnp.bfloat16))
    so, out, _ = _roundtrip(ctx, {"h": act})
    assert len(so.meta) < 4096
    host = np.asarray(act)
    assert out["h"].dtype == host.dtype
    np.testing.assert_array_equal(host, out["h"])


def test_small_device_arrays_roundtrip(ctx):
    # below the OOB threshold the fast path defers to numpy's own
    # reduce — correctness is the contract, not the wire layout
    small = jnp.arange(8, dtype=jnp.float32)
    _, out, _ = _roundtrip(ctx, {"x": small})
    np.testing.assert_array_equal(np.asarray(small), out["x"])
    assert out["x"].dtype == np.float32


def test_top_level_device_array_unchanged_contract(ctx):
    a = jnp.arange(4096, dtype=jnp.float32)
    _, out, _ = _roundtrip(ctx, a)
    assert isinstance(out, np.ndarray)
    np.testing.assert_array_equal(np.asarray(a), out)


def test_to_host():
    a = jnp.ones((4, 4))
    h = to_host(a)
    assert isinstance(h, np.ndarray)
    assert to_host("x") == "x"
    arr = np.zeros(3)
    assert to_host(arr) is arr


def test_plain_pickle_semantics_untouched():
    """The dispatch entry is scoped to the object-store pickler: a
    plain pickle.dumps of a jax array still round-trips as a
    jax-loadable value (jax's own reducer)."""
    import pickle
    a = jnp.arange(16, dtype=jnp.float32)
    out = pickle.loads(pickle.dumps(a))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(out))


def test_half_imported_jax_is_not_an_error(monkeypatch):
    """Workers import jax lazily inside a task while other threads
    serialize results: a `jax` found in sys.modules mid-import has no
    `Array` yet, and must read as "no jax here", not AttributeError."""
    import sys
    import types

    from ray_tpu.core import serialization as S
    monkeypatch.setitem(sys.modules, "jax", types.ModuleType("jax"))
    monkeypatch.setattr(S, "_jax_dispatch", None)
    assert S._pre_serialize({"w": 1.5}) == {"w": 1.5}
    assert S.to_host(7) == 7
    assert S._device_array_dispatch() is None
