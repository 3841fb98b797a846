"""Serialization: cloudpickle + pickle-5 out-of-band zero-copy buffers.

Equivalent of the reference's ``python/ray/_private/serialization.py``
(SerializationContext :110, serialize :482, deserialize_objects :393):

- cloudpickle for arbitrary Python (functions, classes, closures);
- pickle protocol 5 with out-of-band ``PickleBuffer``s so large numpy /
  jax-host arrays are written to the shared-memory store without a copy and
  mapped back as zero-copy views on read;
- custom reducers for ObjectRef (borrowing) and ActorHandle.

Wire format of a serialized object:
    [u32 n_buffers][u64 len_meta][meta pickle bytes][buffer 0][buffer 1]...
buffers 8-byte aligned, each prefixed by u64 length.
"""

from __future__ import annotations

import io
import pickle
import struct
import threading
from typing import List, Optional, Tuple

import cloudpickle

_ALIGN = 64  # align buffers for vectorized readers / dlpack import


class SerializedObject:
    """A serialized value: metadata bytes + zero-copy buffer views."""

    __slots__ = ("meta", "buffers", "contained_refs")

    def __init__(self, meta: bytes, buffers: List[memoryview],
                 contained_refs: list):
        self.meta = meta
        self.buffers = buffers
        self.contained_refs = contained_refs

    def total_bytes(self) -> int:
        n = 12 + len(self.meta)
        for b in self.buffers:
            n = _aligned(n + 8) + b.nbytes
        return n

    def write_to(self, target: memoryview) -> int:
        """Write the wire format into ``target``; returns bytes written."""
        struct.pack_into("<IQ", target, 0, len(self.buffers), len(self.meta))
        off = 12
        target[off:off + len(self.meta)] = self.meta
        off += len(self.meta)
        for b in self.buffers:
            off = _aligned(off + 8) - 8
            struct.pack_into("<Q", target, off, b.nbytes)
            off += 8
            flat = b.cast("B") if b.ndim != 1 or b.format != "B" else b
            target[off:off + b.nbytes] = flat
            off += b.nbytes
        return off

    def to_bytes(self) -> bytes:
        out = bytearray(self.total_bytes())
        n = self.write_to(memoryview(out))
        return bytes(out[:n])


def _aligned(n: int) -> int:
    return (n + _ALIGN - 1) // _ALIGN * _ALIGN


_thread_local = threading.local()


def get_active_context() -> Optional["SerializationContext"]:
    return getattr(_thread_local, "active_ctx", None)


class SerializationContext:
    """Per-worker serializer. Tracks refs contained in serialized values
    (for the borrowing protocol) and refs found while deserializing."""

    def __init__(self, worker=None):
        self.worker = worker
        self._custom_serializers = {}

    # -- hooks called from ObjectRef.__reduce__ --
    # ref lists live in thread-local state so concurrent (de)serialize calls
    # (threaded actors) don't clobber each other's tracking
    def record_contained_ref(self, ref) -> None:
        getattr(_thread_local, "contained", []).append(ref)

    def record_deserialized_ref(self, ref) -> None:
        getattr(_thread_local, "deserialized", []).append(ref)

    def register_custom_serializer(self, cls, serializer, deserializer):
        self._custom_serializers[cls] = (serializer, deserializer)

    # -- main entry points --
    def serialize(self, value) -> SerializedObject:
        buffers: List[pickle.PickleBuffer] = []
        _thread_local.active_ctx = self
        _thread_local.contained = contained = []
        try:
            value = _pre_serialize(value)
            try:
                # C-pickle fast path: ~5x cheaper than building a
                # CloudPickler per call, and every __reduce__ hook
                # (ObjectRef borrowing, custom serializers applied in
                # _pre_serialize) fires identically. Task results are
                # overwhelmingly plain data; closures/local classes
                # raise and fall back. __main__ globals DON'T raise —
                # C-pickle happily encodes them by reference, which a
                # worker (whose __main__ is worker.py) can't resolve —
                # so any STACK_GLOBAL against __main__ (its module name
                # appears literally in the stream) also falls back to
                # cloudpickle's by-value treatment.
                # The pickler carries a scoped dispatch-table entry for
                # device arrays: any jax.Array ANYWHERE in the value
                # (streamed pipeline activations, (loss, aux) tuples)
                # ships as a raw out-of-band buffer instead of riding
                # the pickle stream in-band.
                sink = io.BytesIO()
                p = pickle.Pickler(sink, protocol=5,
                                   buffer_callback=buffers.append)
                dt = _device_array_dispatch()
                if dt is not None:
                    p.dispatch_table = dt
                p.dump(value)
                meta = sink.getvalue()
                if b"__main__" in meta:
                    raise pickle.PicklingError("__main__ global")
            except (pickle.PicklingError, pickle.PickleError, TypeError,
                    AttributeError):
                buffers.clear()
                contained.clear()
                meta = cloudpickle.dumps(
                    value, protocol=5, buffer_callback=buffers.append)
        finally:
            _thread_local.active_ctx = None
            _thread_local.contained = []
        views = []
        for pb in buffers:
            v = pb.raw()
            views.append(v)
        return SerializedObject(meta, views, contained)

    def deserialize(self, meta: bytes, buffers: List[memoryview]) -> Tuple[object, list]:
        """Returns (value, deserialized_refs)."""
        _thread_local.active_ctx = self
        _thread_local.deserialized = deserialized = []
        try:
            value = pickle.loads(meta, buffers=buffers)
        finally:
            _thread_local.active_ctx = None
            _thread_local.deserialized = []
        return value, list(deserialized)

    def deserialize_from_view(self, view: memoryview) -> Tuple[object, list]:
        value, refs, _ = self.deserialize_from_view_tracked(view)
        return value, refs

    def deserialize_from_view_tracked(
            self, view: memoryview) -> Tuple[object, list, list]:
        """Like deserialize_from_view, but also returns the out-of-band
        buffer views handed to pickle. Zero-copy consumers (arrow
        buffers, numpy bases) hold references to EXACTLY these
        memoryview objects for as long as any alias of the data lives —
        they are the correct anchors for reader-lease lifetime (a
        finalizer on the VALUE fires too early: a table can die while
        its sliced/united buffers live on in other arrow objects)."""
        n_buffers, len_meta = struct.unpack_from("<IQ", view, 0)
        off = 12
        meta = bytes(view[off:off + len_meta])
        off += len_meta
        buffers = []
        for _ in range(n_buffers):
            off = _aligned(off + 8) - 8
            (blen,) = struct.unpack_from("<Q", view, off)
            off += 8
            buffers.append(view[off:off + blen])
            off += blen
        value, refs = self.deserialize(meta, buffers)
        return value, refs, buffers


_OOB_BYTES_THRESHOLD = 4096


class _OOBBytes:
    """Ships a large bytes/bytearray payload out-of-band: the pickle stream
    carries only a reconstructor; the payload rides as a zero-copy
    PickleBuffer (one memcpy into shm at write, one back out at get —
    instead of an extra full copy through the pickle stream)."""

    __slots__ = ("ctor", "value")

    def __init__(self, ctor, value):
        self.ctor = ctor
        self.value = value

    def __reduce_ex__(self, protocol):
        return self.ctor, (pickle.PickleBuffer(self.value),)


def _jax_array_type():
    """``jax.Array`` if this process has imported jax, else None —
    without importing it. A worker imports jax lazily, inside whatever
    task first needs it, while other threads serialize: a module found
    half-imported in ``sys.modules`` has no ``Array`` yet (and no array
    of it can exist yet either)."""
    import sys
    return getattr(sys.modules.get("jax"), "Array", None)


def _pre_serialize(value):
    """Convert device-resident jax arrays to host numpy so the object store
    stays host-side (TPU HBM is not host-mappable; SURVEY.md §7 hard part 4).
    The array round-trips back to device via ``jax.device_put`` on use.
    Large raw bytes go out-of-band (see _OOBBytes)."""
    if type(value) is bytes and len(value) > _OOB_BYTES_THRESHOLD:
        return _OOBBytes(bytes, value)
    if type(value) is bytearray and len(value) > _OOB_BYTES_THRESHOLD:
        return _OOBBytes(bytearray, value)
    jax_array = _jax_array_type()
    if jax_array is not None and isinstance(value, jax_array):
        import numpy as np
        return np.asarray(value)
    return value


# ---- device-array serialization fast path ----------------------------
# A jax.Array nested anywhere inside a value (a streamed pipeline
# activation tuple, an actor-call argument tree) used to ride jax's own
# __reduce__ THROUGH the pickle stream: a full in-band copy of the
# payload, then a second copy out at load. The scoped dispatch-table
# entry below turns any device array into (dtype, shape, PickleBuffer):
# the host view goes out-of-band — one memcpy into shm at write — and
# reconstructs as a zero-copy ``np.frombuffer`` view at read. Scoped to
# the object-store pickler (NOT copyreg-global) so user pickling
# semantics elsewhere are untouched.

_jax_dispatch: Optional[dict] = None


def _device_array_dispatch() -> Optional[dict]:
    global _jax_dispatch
    if _jax_dispatch is not None:
        return _jax_dispatch or None
    if _jax_array_type() is None:
        return None  # keep probing until jax is (fully) imported here
    # the class by import, never by allocating an array to take its
    # type: that would create a backend (and grab a TPU) in whatever
    # process happens to pickle
    from jax._src.array import ArrayImpl
    _jax_dispatch = {ArrayImpl: _reduce_device_array}
    return _jax_dispatch


def _reduce_device_array(a):
    import numpy as np
    host = np.asarray(a)
    if host.nbytes < _OOB_BYTES_THRESHOLD:
        return (np.array, (host,))
    if not host.flags["C_CONTIGUOUS"]:
        host = np.ascontiguousarray(host)
    # ship as raw bytes: extension dtypes (bfloat16, float8_*) refuse
    # the buffer protocol, a uint8 view never does
    return (_restore_ndarray,
            (pickle.PickleBuffer(host.view(np.uint8)),
             host.dtype.name, host.shape))


def _restore_ndarray(buf, dtype_name: str, shape):
    import numpy as np
    try:
        dtype = np.dtype(dtype_name)
    except TypeError:
        # extension dtypes (bfloat16, float8_*) register via ml_dtypes
        import ml_dtypes
        dtype = np.dtype(getattr(ml_dtypes, dtype_name))
    return np.frombuffer(buf, dtype=np.uint8).view(dtype).reshape(shape)


def to_host(value):
    """Eagerly move a top-level device array to host numpy (no-op for
    anything else). The streaming worker calls this at yield time so
    the device fetch happens outside the store/report critical path."""
    jax_array = _jax_array_type()
    if jax_array is not None and isinstance(value, jax_array):
        import numpy as np
        return np.asarray(value)
    return value


_default_ctx: Optional[SerializationContext] = None


def default_context() -> SerializationContext:
    global _default_ctx
    if _default_ctx is None:
        _default_ctx = SerializationContext()
    return _default_ctx
