"""Cells as data: BENCHMARK.json names a cell, and everything that
belongs to its configuration, its traffic mix or one of its metrics is a
file found by that name. A later PR adds files and entries; nothing
here needs an edit for a new cell. No JAX in this module."""
import dataclasses
import importlib
import json
import os
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: a weight seed must fit the signed 32 bits jax.random.PRNGKey takes;
#: the driver's seeds are larger
SEED_MOD = 2**31 - 1


def log(msg: str) -> None:
    """Chatter goes to stderr: stdout carries the result line only."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


class Heartbeat:
    """A thread that only sleeps, 20 ms at a time, and remembers its
    worst oversleep since ``reset``: when a run stalls for seconds
    (PERF.md, PR 24), the log then says whether this whole process
    stood still or only what it was waiting for."""

    def __init__(self):
        self.worst = [0.0, 0.0]       # seconds late, seconds since reset
        self._t0 = time.perf_counter()
        threading.Thread(target=self._beat, daemon=True).start()

    def reset(self) -> None:
        self.worst, self._t0 = [0.0, 0.0], time.perf_counter()

    def _beat(self) -> None:
        while True:
            before = time.perf_counter()
            time.sleep(0.02)
            late = time.perf_counter() - before - 0.02
            if late > self.worst[0]:
                self.worst = [late, before - self._t0]


def session_dir(pid: int) -> str:
    """The runtime session of a serving cell whose process is ``pid``;
    its workers log under ``logs/``."""
    return os.path.join(tempfile.gettempdir(), f"rtb{pid}")


def _load(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    rehearse: bool = False

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def params(self) -> Dict[str, Any]:
        """The traffic file's parameters; a rehearsal's overrides win."""
        over = self.traffic.get("rehearse", {}) if self.rehearse else {}
        return {**self.traffic, **over}

    @property
    def depth(self) -> int:
        """Layers run: the traffic file's ``n_layers``, the one home of
        the cut in depth (``reduced`` in BENCHMARK.json lists it)."""
        return int(self.params["n_layers"])

    def model_kwargs(self) -> Dict[str, Any]:
        """``TransformerConfig`` keyword arguments as plain data (dtype
        by name), at the published widths; the rehearsal narrows them."""
        kw = dict(self.config["program"], n_layers=self.depth)
        kw.update({k: v for k, v in self.config["blocks"].items()
                   if k != "why"})
        if self.rehearse:
            kw.update(self.config["rehearse"])
        return kw

    def reference_hp(self) -> tuple:
        hp = dict(self.config["reference_hp"])
        if self.rehearse:
            hp.update(self.config.get("rehearse_hp", {}))
        return tuple(sorted(hp.items()))


def _applies(metric: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, rehearse: bool = False) -> Cell:
    bench = benchmark()
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{[w['name'] for w in bench['workloads']]}")
    w = rows[0]
    cfg = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]), config=config,
        traffic=_load("traffic", w["traffic"] + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        rehearse=rehearse)


def metric_reader(metric_name: str):
    """(read, args) for a per-layer metric: ``metrics/<base>.json``
    names a module under ``readers/`` and its arguments. ``<base>`` is
    the name up to its first dot, so ``decode_step_ms.tpot`` and
    ``decode_step_ms.tok`` (one quantity, two end-to-end metrics to
    move) share a file."""
    base = metric_name.split(".", 1)[0]
    desc = _load("metrics", base + ".json")
    mod = importlib.import_module(f"benchmarks.readers.{desc['reader']}")
    return mod.read, desc.get("args", {})


def read_metrics(metrics: List[Dict[str, Any]], obs: Dict[str, Any]
                 ) -> Dict[str, Dict[str, Any]]:
    """name -> {"value", "unit"}; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for m in metrics:
        read, args = metric_reader(m["name"])
        value = read(obs, **args)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def weight_seed(seed: int) -> int:
    return int(seed) % SEED_MOD


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Any], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]],
                beats: Dict[str, Any], compared: Dict[str, Any]) -> str:
    """The run's last line. After the keys the driver reads:
    ``heartbeat_late_s``, by process the worst [seconds late, at] an
    idle thread woke inside the window (a machine that stood still shows
    in every process at the same instant; a fault does not), and last
    ``compared``, every number ``correct`` rests on as [number, limit]."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["heartbeat_late_s"] = beats
    line["compared"] = compared
    return json.dumps(line)
