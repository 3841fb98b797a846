"""Native segment store tests (C++ data plane), mirroring the plasma
semantics the reference tests in
``src/ray/object_manager/plasma/test/`` cover: create/seal/get,
duplicate create, capacity, delete/reuse, cross-process visibility."""

import multiprocessing
import os
import uuid

import pytest

from ray_tpu import _native
from ray_tpu.core.ids import ObjectID

lib = _native.load()
pytestmark = pytest.mark.skipif(lib is None, reason="no native lib")


@pytest.fixture
def session():
    from ray_tpu.core.native_store import NativeShmStore, _seg_path
    name = f"raytpu-test-{uuid.uuid4().hex[:8]}"
    store = NativeShmStore(name, 1 << 20)
    yield name, store
    store.destroy()


def _oid():
    return ObjectID.from_random()


def test_create_seal_get_roundtrip(session):
    from ray_tpu.core.native_store import NativeShmClient
    name, store = session
    client = NativeShmClient(name)
    oid = _oid()
    data = b"hello native store" * 100
    view = client.create(oid, len(data))
    view[:] = data
    assert client.seal(oid) == len(data)
    got = client.get_view(oid)
    assert bytes(got) == data
    assert client.contains(oid)
    assert store.contains(oid)
    client.close()


def test_unsealed_not_visible(session):
    from ray_tpu.core.native_store import NativeShmClient
    name, _ = session
    client = NativeShmClient(name)
    oid = _oid()
    client.create(oid, 10)
    assert client.get_view(oid, timeout=0.05) is None
    assert not client.contains(oid)
    client.seal(oid)
    assert client.contains(oid)
    client.close()


def test_duplicate_create_raises(session):
    from ray_tpu.core.native_store import NativeShmClient
    name, _ = session
    client = NativeShmClient(name)
    oid = _oid()
    client.put_bytes(oid, b"x")
    with pytest.raises(FileExistsError):
        client.create(oid, 5)
    client.close()


def test_capacity_and_delete_reuse(session):
    from ray_tpu.core.native_store import NativeShmClient
    from ray_tpu.exceptions import ObjectStoreFullError
    name, store = session
    client = NativeShmClient(name)
    big = (1 << 20) - 4096
    # physical segment = 4x nominal (plasma-style fallback-allocation
    # headroom: the in-flight working set may exceed the budget): four
    # "big" objects fit, the fifth does not.
    fits = [_oid() for _ in range(4)]
    for i, oid in enumerate(fits):
        client.put_bytes(oid, bytes([97 + i]) * big)
    with pytest.raises(ObjectStoreFullError):
        client.create(_oid(), big)
    store.delete(fits[0])
    c = _oid()
    client.put_bytes(c, b"z" * big)  # space reused after delete
    assert bytes(client.get_view(c))[:1] == b"z"
    client.close()


def test_many_objects_index(session):
    from ray_tpu.core.native_store import NativeShmClient
    name, store = session
    client = NativeShmClient(name)
    oids = [_oid() for _ in range(500)]
    for i, oid in enumerate(oids):
        client.put_bytes(oid, str(i).encode())
    for i, oid in enumerate(oids):
        assert bytes(client.get_view(oid)) == str(i).encode()
    used, cap, n = store.seg.stats()
    assert n == 500
    # the gets above hold read references: release them, then delete
    for oid in oids:
        client.release(oid)
    for oid in oids:
        store.delete(oid)
    used, cap, n = store.seg.stats()
    assert n == 0 and used == 0
    client.close()


def test_delete_under_live_reader_is_safe(session):
    """A deleted object's extent must NOT be reused while a reader holds
    a zero-copy view (zombie semantics); it is reclaimed on release."""
    from ray_tpu.core.native_store import NativeShmClient
    name, store = session
    client = NativeShmClient(name)
    oid = _oid()
    data = b"A" * 4096
    client.put_bytes(oid, data)
    view = client.get_view(oid)          # holds a reference
    store.delete(oid)                    # zombie, not freed
    # new allocations cannot land on the zombie's extent
    other = _oid()
    client.put_bytes(other, b"B" * 4096)
    assert bytes(view) == data           # reader's bytes intact
    assert client.get_view(other, timeout=1) is not None
    used_before = store.seg.stats()[0]
    client.release(oid)                  # last ref -> extent freed
    assert store.seg.stats()[0] < used_before
    client.close()


def test_reap_dead_reader(session):
    """References of a crashed process are reclaimed by the reaper."""
    from ray_tpu.core.native_store import NativeShmClient
    name, store = session
    oid = _oid()

    def child(name, oid_bin):
        from ray_tpu.core.native_store import NativeShmClient
        from ray_tpu.core.ids import ObjectID
        c = NativeShmClient(name)
        c.put_bytes(ObjectID(oid_bin), b"z" * 1024)
        c.get_view(ObjectID(oid_bin))    # acquire, then die hard
        os._exit(0)

    ctx = multiprocessing.get_context("fork")
    proc = ctx.Process(target=child, args=(name, oid.binary()))
    proc.start()
    proc.join(timeout=60)
    store.on_sealed(oid, 1024)
    store.delete(oid)                    # zombie: dead child's ref
    used_zombie = store.seg.stats()[0]
    assert store.reap_dead_readers() >= 1
    assert store.seg.stats()[0] < used_zombie


def _child_put(name, oid_bin, data):
    from ray_tpu.core.native_store import NativeShmClient
    client = NativeShmClient(name)
    client.put_bytes(ObjectID(oid_bin), data)
    client.close()


def test_cross_process_visibility(session):
    from ray_tpu.core.native_store import NativeShmClient
    name, _ = session
    oid = _oid()
    data = b"written by child process"
    proc = multiprocessing.get_context("spawn").Process(
        target=_child_put, args=(name, oid.binary(), data))
    proc.start()
    proc.join(timeout=60)
    assert proc.exitcode == 0
    client = NativeShmClient(name)
    assert bytes(client.get_view(oid, timeout=5)) == data
    client.close()


def test_spill_and_restore(tmp_path):
    from ray_tpu.core.native_store import NativeShmClient, NativeShmStore
    name = f"raytpu-test-{uuid.uuid4().hex[:8]}"
    store = NativeShmStore(name, 64 * 1024, spill_dir=str(tmp_path))
    client = NativeShmClient(name)
    try:
        oids = []
        for i in range(8):
            oid = _oid()
            client.put_bytes(oid, bytes([i]) * (16 * 1024))
            store.on_sealed(oid, 16 * 1024)
            oids.append(oid)
        # capacity forced spills of LRU objects
        assert store.stats()["num_spilled"] > 0
        first = oids[0]
        assert store.maybe_restore(first)
        assert bytes(client.get_view(first, timeout=5))[:1] == bytes([0])
    finally:
        client.close()
        store.destroy()


def test_crash_recovery_rebuilds_allocator(session):
    """EOWNERDEAD-style recovery: scramble derived allocator state
    (bump/used), run ns_recover, and verify sealed data survives, stats
    are recomputed, and the allocator still works (gap reuse)."""
    import ctypes
    from ray_tpu.core.native_store import _Segment
    name, store = session
    seg = _Segment(lib, name)
    oids, blobs = [], []
    for i in range(4):
        oid = _oid()
        blob = bytes([i + 1]) * (3 * 1024)
        off = seg.alloc(oid, len(blob))
        seg.view[off:off + len(blob)] = blob
        seg.seal(oid)
        oids.append(oid)
        blobs.append(blob)
    # free one in the middle so recovery must reconstruct a gap extent
    freed = oids.pop(1)
    blobs.pop(1)
    assert seg.delete(freed) > 0
    used_before, _, _ = seg.stats()
    # simulate a torn crash: trash the derived header fields
    base = lib.ns_base(seg.handle)
    hdr = (ctypes.c_uint64 * 6).from_address(base)
    hdr[4] = 7   # bump: absurd
    hdr[5] = 1   # used: absurd
    lib.ns_recover(seg.handle)
    used, _, nobjects = seg.stats()
    assert used == used_before
    assert nobjects == 3
    for oid, blob in zip(oids, blobs):
        state, off, size = seg.lookup(oid)
        assert state == 2 and size == len(blob)
        assert bytes(seg.view[off:off + size]) == blob
    # allocator still functional after rebuild: the freed gap is reusable
    oid = _oid()
    off = seg.alloc(oid, 3 * 1024)
    assert off not in (2 ** 64 - 1, 2 ** 64 - 2)
    seg.view[off:off + 3 * 1024] = b"z" * (3 * 1024)
    assert seg.seal(oid) == 3 * 1024
    seg.close()


def test_failed_build_is_an_error_not_a_quiet_switch(monkeypatch,
                                                     tmp_path):
    """With a compiler present and no library built, a build that fails
    raises (and a later call tries again): the store never falls to
    the Python implementation unannounced. Without any g++ — or
    switched off — it is the Python store, and says so."""
    import subprocess
    import types

    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_tried", False)
    monkeypatch.setattr(_native, "_LIB_PATH",
                        str(tmp_path / "libnativestore-test.so"))
    monkeypatch.setattr(
        subprocess, "run",
        lambda *a, **k: types.SimpleNamespace(
            returncode=1, stderr=b"store.cpp:1: error: boom"))
    for _ in range(2):
        with pytest.raises(RuntimeError, match="error: boom"):
            _native.load()
    monkeypatch.setattr(_native.shutil, "which", lambda name: None)
    assert _native.load() is None
    assert _native.store_kind().startswith("python (no g++")
    monkeypatch.setattr(_native, "_tried", False)
    monkeypatch.setenv("RAY_TPU_NATIVE_STORE", "0")
    assert _native.store_kind() == "python (RAY_TPU_NATIVE_STORE=0)"
