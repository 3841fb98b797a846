"""What every process that holds a chip does the same way: say what
device it is on (and refuse a CPU), count compilations, take a profiler
trace between two marks, read the device's memory. Imports JAX: never
import this from the parent (``run.py``)."""
import os
import shutil
from typing import Any, Dict

import jax

from benchmarks import trace as T

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def device_facts(chips: int, rehearse: bool) -> Dict[str, Any]:
    """platform / kind / count as JAX reports them. Fewer chips than
    the cell asks for, or no accelerator at all, ends the run with a
    non-zero code and no result (``--rehearse``, for the tests, lets
    the CPU through and says so in what it prints)."""
    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if rehearse:
        return facts
    if facts["platform"] != "tpu":
        raise SystemExit(f"benchmarks: no accelerator: jax.devices() reports "
                     f"{facts}; a benchmark number comes from a chip only")
    if facts["count"] < chips:
        raise SystemExit(f"benchmarks: the cell needs {chips} chip(s) and JAX "
                     f"finds {facts['count']}")
    return facts


def memory_facts(devices=None) -> Dict[str, Any]:
    """Peak and limit on the fullest chip of ``devices``."""
    peak, limit, in_use = 0, 0, 0
    for d in devices or jax.devices():
        ms = d.memory_stats() or {}
        if ms.get("peak_bytes_in_use", 0) >= peak:
            peak = ms.get("peak_bytes_in_use", 0)
            in_use = ms.get("bytes_in_use", 0)
            limit = ms.get("bytes_limit", 0)
    return {"memory_peak_bytes": int(peak), "bytes_limit": int(limit),
            "bytes_in_use": int(in_use)}


class CompileCounter:
    """Counts programs this process compiled or loaded (every backend
    compile, cached or not) since ``reset``."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1

    def reset(self) -> None:
        self.n = 0


class Tracer:
    """A profiler trace between two marks. ``close`` writes the second
    mark and costs nothing; ``finish`` stops the profiler (tens of
    seconds after a few seconds of serving: it converts every host
    event) and turns what it wrote into the summary, of the stretch
    between the marks. Only the process that holds the chip can trace
    it; a process that serves finishes when nothing is being served any
    more."""

    def __init__(self, out_dir: str, rehearse: bool = False):
        self.dir, self.rehearse = out_dir, rehearse
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir, exist_ok=True)

    def start(self) -> None:
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation(T.OPEN_MARK):
            pass

    def close(self) -> None:
        with jax.profiler.TraceAnnotation(T.CLOSE_MARK):
            pass

    def finish(self) -> Dict[str, Any]:
        jax.profiler.stop_trace()
        path = T.find_xplane(self.dir)
        if path is None:
            raise RuntimeError(f"the profiler wrote no trace to {self.dir}")
        loaded = T.load(path)
        if self.rehearse and not loaded["chips"]:
            # the CPU backend has no device plane: the rehearsal walks
            # the code, it has nothing to reduce
            loaded["chips"] = {"(none)": {"ops": [], "async": [],
                                          "modules": []}}
        summary = T.summarize(loaded)
        shutil.rmtree(self.dir, ignore_errors=True)
        return summary


def scale_stream(params, weights: Dict[str, Any], donate: bool = False):
    """``params`` with every leaf the configuration lists under
    ``weights.residual_writers`` (dotted paths) times
    ``weights.stream_scale``: the whole residual stream scales, every
    layer keeps its share of it, and an RMSNorm's eps stops mattering
    (the configuration file says why it needs that). ``donate`` scales
    the leaves in place: the tree handed in is then spent."""
    scale = float(weights["stream_scale"])
    if scale == 1.0 or not weights["residual_writers"]:
        return params
    mul = jax.jit(lambda x: x * scale, donate_argnums=(0,) if donate else ())
    out = dict(params)
    for path in weights["residual_writers"]:
        *groups, leaf = path.split(".")
        node = out
        for g in groups:
            node[g] = dict(node[g])
            node = node[g]
        node[leaf] = mul(node[leaf])
    return out


def resolve_dtype(name: str):
    import jax.numpy as jnp
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


def rel_err(got, want) -> float:
    """max |got - want| over max |want|, in float32."""
    import jax.numpy as jnp
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def matmul_params(params) -> int:
    """Parameters that take part in a matmul: all but the embedding
    table, which is a lookup."""
    total = sum(int(x.size) for x in jax.tree.leaves(params))
    return total - int(params["embed"].size)


def trace_dir(name: str) -> str:
    """Scratch for one run's raw trace, inside the checkout (removed
    once reduced)."""
    from benchmarks.spec import ROOT
    return os.path.join(ROOT, ".bench_tmp", name)
