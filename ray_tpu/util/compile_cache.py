"""Where compiled programs and autotune winners persist across processes.

One root holds everything a process would otherwise recompute at start:
JAX's persistent compilation cache and the flash/paged autotune table
(:mod:`ray_tpu.ops.flash_attention`). When ``JAX_COMPILATION_CACHE_DIR``
is set the root is that directory and nothing is set in code — JAX reads
the variable itself. Otherwise the root is ``.jax_cache`` at the top of
the checkout: a fixed path, never a temp name, pid or timestamp, because
a cache that moves is never hit twice.

:func:`enable` also exports the variable, so every process started
afterwards (node manager, zygote, workers, benchmark children) inherits
the same root without calling anything.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Dict

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_DEFAULT_ROOT = os.path.join(_CHECKOUT, ".jax_cache")

_lock = threading.Lock()
_counts: Dict[str, int] = {"hits": 0, "misses": 0}
_listening = False

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}


def cache_root() -> str:
    """The directory compiled programs and autotune winners live in."""
    return os.environ.get(ENV_VAR) or _DEFAULT_ROOT


def enable() -> str:
    """Point this process, and through the environment every process it
    starts, at :func:`cache_root`. Call before the first compilation
    (JAX decides once per process whether it caches); idempotent."""
    root = os.environ.get(ENV_VAR)
    if not root:
        root = os.environ[ENV_VAR] = _DEFAULT_ROOT
        config = getattr(sys.modules.get("jax"), "config", None)
        if config is not None:
            # jax was imported before the variable existed, so its
            # config never saw it (a jax still mid-import in another
            # thread has no config yet and will read the variable)
            config.update("jax_compilation_cache_dir", root)
    return root


def _on_event(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        with _lock:
            _counts[name] += 1


def stats() -> Dict[str, int]:
    """Programs this process loaded from the cache (``hits``) and
    compiled then wrote to it (``misses``) since the first call, which
    starts the count. Compilations under JAX's one-second persistence
    threshold are neither."""
    global _listening
    import jax.monitoring
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
        return dict(_counts)
