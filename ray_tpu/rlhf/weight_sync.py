"""Learner→engine weight refresh over the int8 blockwise wire.

The wire format is :mod:`ray_tpu.parallel.quantization`'s (values int8
``[nblocks, block_size]`` + f32 per-block scales — the EQuARX
collective format reused as a transport codec): each float leaf of the
param tree ships ~4x smaller than f32, which is what makes per-round
in-flight refresh affordable when the learner and engines are on
different slices (sebulba). Non-float leaves (and anything a caller
marks raw) ship verbatim.

The refresh is **version-stamped at the source**: ``pack_weights``
bakes the monotone policy version into the payload, the engine's
double-buffered swap applies it between decode steps, and every token
the engine emits afterwards carries that version — so a trajectory's
per-token version column is an exact record of which policy generated
each token (the staleness ledger PPO importance ratios are audited
against).

Dequantization runs on the *caller's* thread (the actor call that
delivers the payload), never on the engine step thread: the step
thread's only cost is a pointer swap.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.parallel.quantization import (DEFAULT_BLOCK_SIZE,
                                           dequantize_int8_np,
                                           quantize_int8_np)

_SEP = "/"


def _flatten(tree: Dict[str, Any], prefix: str = ""
             ) -> List[Tuple[str, Any]]:
    out: List[Tuple[str, Any]] = []
    for k in sorted(tree):
        v = tree[k]
        key = f"{prefix}{_SEP}{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.extend(_flatten(v, key))
        else:
            out.append((key, v))
    return out


def _unflatten(entries: Dict[str, Any]) -> Dict[str, Any]:
    root: Dict[str, Any] = {}
    for key, v in entries.items():
        parts = key.split(_SEP)
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return root


def pack_weights(params: Dict[str, Any], version: int,
                 block_size: int = DEFAULT_BLOCK_SIZE
                 ) -> Dict[str, Any]:
    """Quantize a (nested-dict) param tree to the int8 wire payload.
    Float leaves become ``{"q", "scales", "shape", "dtype"}``; integer
    and boolean leaves ship raw. The payload is pure numpy — it crosses
    the object store with the zero-copy serializer."""
    entries: Dict[str, Dict[str, Any]] = {}
    for key, leaf in _flatten(params):
        arr = np.asarray(leaf)
        if arr.dtype.kind == "f":
            q, scales = quantize_int8_np(arr, block_size)
            entries[key] = {"q": q, "scales": scales,
                            "shape": arr.shape, "dtype": str(arr.dtype)}
        else:
            entries[key] = {"raw": arr}
    return {"version": int(version), "block_size": int(block_size),
            "entries": entries}


def unpack_weights(packed: Dict[str, Any]
                   ) -> Tuple[Dict[str, Any], int]:
    """Invert :func:`pack_weights` → ``(params, version)``."""
    out: Dict[str, Any] = {}
    for key, e in packed["entries"].items():
        if "raw" in e:
            out[key] = e["raw"]
        else:
            out[key] = dequantize_int8_np(
                e["q"], e["scales"], shape=e["shape"],
                dtype=np.dtype(e["dtype"]))
    return _unflatten(out), int(packed["version"])


def packed_wire_bytes(packed: Dict[str, Any]) -> int:
    """Actual payload bytes of one refresh (int8 values + f32 scales +
    raw leaves)."""
    total = 0
    for e in packed["entries"].values():
        if "raw" in e:
            total += e["raw"].nbytes
        else:
            total += e["q"].nbytes + e["scales"].nbytes
    return total


def _f32_bytes(packed: Dict[str, Any]) -> int:
    total = 0
    for e in packed["entries"].values():
        if "raw" in e:
            total += e["raw"].nbytes
        else:
            total += 4 * int(np.prod(e["shape"])) if e["shape"] else 4
    return total


class WeightPublisher:
    """Monotone-versioned weight fan-out to a set of engines.

    Targets may be in-process :class:`~ray_tpu.serve.llm_engine.
    LLMEngine` objects (``stage_weights`` — dequantized HERE, on the
    publisher's thread) or remote handles exposing ``sync_weights``
    (the packed payload ships; the replica dequantizes on its own actor
    thread). Either way the engine step thread only ever pointer-swaps.
    """

    def __init__(self, engines: List[Any],
                 block_size: int = DEFAULT_BLOCK_SIZE,
                 recorder=None):
        self._engines = list(engines)
        self._block_size = block_size
        self._recorder = recorder
        self._lock = threading.Lock()
        self._version = 0
        self._publishes = 0
        self._wire_bytes = 0
        self._f32_bytes = 0
        self._publish_wall_s = 0.0

    @property
    def version(self) -> int:
        with self._lock:
            return self._version

    def publish(self, params: Dict[str, Any]) -> int:
        """Pack + fan out one refresh; returns the new version."""
        t0 = time.monotonic()
        with self._lock:
            self._version += 1
            version = self._version
        packed = pack_weights(params, version, self._block_size)
        unpacked = None
        for eng in self._engines:
            if hasattr(eng, "stage_weights"):
                if unpacked is None:
                    unpacked, _ = unpack_weights(packed)
                eng.stage_weights(unpacked, version)
            else:
                eng.sync_weights(packed)
        wall = time.monotonic() - t0
        with self._lock:
            self._publishes += 1
            self._wire_bytes += packed_wire_bytes(packed)
            self._f32_bytes += _f32_bytes(packed)
            self._publish_wall_s += wall
        return version

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "version": self._version,
                "publishes": self._publishes,
                "wire_bytes_total": self._wire_bytes,
                "f32_bytes_total": self._f32_bytes,
                "compression": (round(self._f32_bytes
                                      / self._wire_bytes, 3)
                                if self._wire_bytes else None),
                "publish_wall_s": round(self._publish_wall_s, 4),
            }
