"""ctypes loader for the native store (builds on first use).

The C++ extension is optional in exactly two stated cases, in which the
Python mmap store is used: ``RAY_TPU_NATIVE_STORE=0``, or no ``g++`` on
this machine (and no library already built for this ``store.cpp``).
With a compiler present, a build or load that fails is an error — the
store never switches implementation quietly. :func:`store_kind` says
which one a process got.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC_PATH = os.path.join(_HERE, "store.cpp")


def _lib_path() -> str:
    """Build artifact keyed by a source hash: editing store.cpp naturally
    invalidates the old binary (mtime comparison breaks under git checkout,
    which restores old mtimes), and no binary is ever committed."""
    with open(_SRC_PATH, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_HERE, f"libnativestore-{digest}.so")


_LIB_PATH = _lib_path()

_lock = threading.Lock()
_lib = None
_tried = False


def _build() -> None:
    """Compile store.cpp into ``_LIB_PATH``; raises if g++ fails."""
    # Sanitizer-instrumented builds live in tests/core/test_store_sanitize.py
    # (a standalone stress binary over the same TU) — the loader builds
    # the production library only.
    # pid-unique temp output: concurrent builders (several node
    # managers starting at once) must not clobber each other mid-write.
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
           "-o", tmp, _SRC_PATH, "-lpthread"]
    out = subprocess.run(cmd, capture_output=True, timeout=300)
    if out.returncode != 0:
        try:
            os.unlink(tmp)
        except FileNotFoundError:
            pass
        raise RuntimeError(
            "native store build failed (set RAY_TPU_NATIVE_STORE=0 to "
            "run on the Python store):\n"
            + out.stderr.decode(errors="replace")[-2000:])
    os.replace(tmp, _LIB_PATH)
    # reap binaries for older source revisions (processes that still have
    # one mapped keep it alive via the inode; the name can go)
    cur = os.path.basename(_LIB_PATH)
    for name in os.listdir(_HERE):
        if name.startswith("libnativestore") and name.endswith(".so") \
                and name != cur:
            try:
                os.unlink(os.path.join(_HERE, name))
            except OSError:
                pass


def store_kind() -> str:
    """"native", or why this process runs on the Python store."""
    if load() is not None:
        return "native"
    if os.environ.get("RAY_TPU_NATIVE_STORE", "1") == "0":
        return "python (RAY_TPU_NATIVE_STORE=0)"
    return "python (no g++ to build the native store)"


def load() -> Optional[ctypes.CDLL]:
    """The native library, building it if needed. None only when it is
    switched off or cannot be built for want of a compiler (module
    docstring); a failed build or load raises."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        if os.environ.get("RAY_TPU_NATIVE_STORE", "1") == "0" or (
                not os.path.exists(_LIB_PATH)
                and shutil.which("g++") is None):
            _tried = True
            return None
        if not os.path.exists(_LIB_PATH):
            _build()   # raises on failure; the next call tries again
        lib = ctypes.CDLL(_LIB_PATH)
        lib.ns_create.restype = ctypes.c_void_p
        lib.ns_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                  ctypes.c_uint32]
        lib.ns_open.restype = ctypes.c_void_p
        lib.ns_open.argtypes = [ctypes.c_char_p]
        lib.ns_alloc.restype = ctypes.c_uint64
        lib.ns_alloc.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_uint64]
        lib.ns_seal.restype = ctypes.c_uint64
        lib.ns_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.ns_lookup.restype = ctypes.c_uint32
        lib.ns_lookup.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.ns_delete.restype = ctypes.c_uint64
        lib.ns_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.ns_evict.restype = ctypes.c_uint64
        lib.ns_evict.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.ns_acquire.restype = ctypes.c_uint32
        lib.ns_acquire.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.ns_release.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_int32]
        lib.ns_release_all.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.ns_reap.restype = ctypes.c_uint32
        lib.ns_reap.argtypes = [ctypes.c_void_p]
        lib.ns_recover.argtypes = [ctypes.c_void_p]
        lib.ns_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32)]
        lib.ns_list.restype = ctypes.c_uint32
        lib.ns_list.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_uint32]
        lib.ns_base.restype = ctypes.c_void_p
        lib.ns_largest_free.restype = ctypes.c_uint64
        lib.ns_largest_free.argtypes = [ctypes.c_void_p]
        lib.ns_compact.restype = ctypes.c_uint64
        lib.ns_compact.argtypes = [ctypes.c_void_p]
        lib.ns_base.argtypes = [ctypes.c_void_p]
        lib.ns_total_size.restype = ctypes.c_uint64
        lib.ns_total_size.argtypes = [ctypes.c_void_p]
        lib.ns_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        _tried = True
        return _lib
