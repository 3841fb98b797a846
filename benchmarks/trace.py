"""From a profiler trace to numbers. ``load`` reads the ``.xplane.pb``
that ``jax.profiler`` wrote into plain lists; everything after it works
on those lists, so the tests check the arithmetic on a small recorded
trace. Times are seconds on the trace's own clock.

What a v5e trace looks like (jax 0.9.0): one plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per executed
program), ``XLA Ops`` (one per HLO op, the name being the op's HLO text,
``%flash_fwd.1 = (bf16[1,16,2048,256]{...}, ...) custom-call(...``) and
``Async XLA Ops`` (start-to-done spans of asynchronous copies and
collectives); one plane ``/host:CPU`` with a line per thread, holding
the runtime's own events, the Python tracer's (``$file.py:12 fn``) and
``TraceAnnotation`` spans.
"""
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OPEN_MARK, CLOSE_MARK = "bench_window_open", "bench_window_close"
SMALL_GAP_S = 20e-6
#: ops that only contain other ops: their time is their children's
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")

_OP = re.compile(r"^%([\w\-.]+) = ")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


# --------------------------------------------------------------- loading
def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> Dict[str, Any]:
    """{"chips": {plane: {"ops", "async", "modules"}}, "host": [...]},
    each event ``[name, start_s, duration_s]`` (host: line name first)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    chips: Dict[str, Dict[str, list]] = {}
    host: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"XLA Ops": "ops", "Async XLA Ops": "async",
                     "XLA Modules": "modules"}
            chip = chips.setdefault(plane.name, {v: [] for v in
                                                 lines.values()})
            for line in plane.lines:
                if line.name in lines:
                    chip[lines[line.name]] = [
                        [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend([line.name, e.name, e.start_ns * 1e-9,
                             e.duration_ns * 1e-9] for e in line.events)
    return {"chips": chips, "host": host}


# ------------------------------------------------------------ op naming
def op_id(name: str) -> str:
    """``flash_fwd.1`` from the op's HLO text (or the name itself)."""
    m = _OP.match(name)
    return m.group(1) if m else name


def result_shape(name: str) -> Tuple[str, Tuple[int, ...]]:
    """(dtype, dims) of the op's (first) result, ("", ()) if none."""
    m = _OP.match(name)
    s = _SHAPE.search(name, m.end() if m else 0)
    if not s:
        return "", ()
    return s.group(1), tuple(int(d) for d in s.group(2).split(",") if d)


def short_name(name: str) -> str:
    """``fusion.332_f32_4096_50400_``: the op and its result's shape."""
    dtype, dims = result_shape(name)
    return f"{op_id(name)}_{dtype}_" + "".join(f"{d}_" for d in dims)


def op_kind(name: str) -> str:
    """The op id without its numeric suffix: ``copy``, ``flash_fwd``."""
    return re.sub(r"[.\d]+$", "", op_id(name))


def is_collective(name: str) -> bool:
    kind = op_kind(name)
    return any(kind.startswith(c) or f"_{c}" in kind or f"{c}_" in kind
               for c in COLLECTIVES)


# ------------------------------------------------------------ intervals
def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of union ``a`` that no interval of union ``b`` covers."""
    out, j = [], 0
    b = list(b)
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _spans(events: Sequence[list], lo: float, hi: float) -> List[Interval]:
    return clip([(e[-2], e[-2] + e[-1]) for e in events], lo, hi)


# -------------------------------------------------------------- summary
def window_of(trace: Dict[str, Any]) -> Interval:
    """The traced window: between the two marks the cell wrote, else
    from the first to the last device op."""
    marks = {e[1]: e[2] + e[3] for e in trace["host"]
             if e[1] in (OPEN_MARK, CLOSE_MARK)}
    if OPEN_MARK in marks and CLOSE_MARK in marks:
        return marks[OPEN_MARK], marks[CLOSE_MARK]
    ops = [e for c in trace["chips"].values() for e in c["ops"]]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def _module_of(modules: Sequence[list]):
    """start time -> name of the program that was running then."""
    import bisect
    mods = sorted(modules, key=lambda e: e[1])
    starts = [e[1] for e in mods]

    def find(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t <= mods[i][1] + mods[i][2]:
            return mods[i][0].split("(")[0]
        return ""
    return find


def _name_gaps(gaps: Sequence[Interval], host: Sequence[list]
               ) -> Dict[str, float]:
    """Seconds of device idleness by what the host was doing: a gap goes
    to the shortest host event that covers its middle (the most specific
    thing any thread was inside); gaps under 20 us are pooled."""
    import heapq
    named: Dict[str, float] = {}
    big = sorted(g for g in gaps if g[1] - g[0] >= SMALL_GAP_S)
    small = sum(b - a for a, b in gaps if b - a < SMALL_GAP_S)
    if small:
        named["(gaps under 20 us)"] = small
    # only the thread that launches the device's programs: what the
    # others are inside says nothing about why the device waits
    launches: Dict[str, int] = {}
    for e in host:
        if e[1].startswith("PjitFunction"):
            launches[e[0]] = launches.get(e[0], 0) + 1
    driver = max(launches, key=launches.get) if launches else None
    events = sorted((e for e in host
                     if e[1] not in (OPEN_MARK, CLOSE_MARK)
                     and driver in (None, e[0])),
                    key=lambda e: e[2])
    active: list = []          # (end, duration, name)
    i = 0
    for a, b in big:
        mid = 0.5 * (a + b)
        while i < len(events) and events[i][2] <= mid:
            e = events[i]
            heapq.heappush(active, (e[2] + e[3], e[3], e[1]))
            i += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        name = min(active, key=lambda x: x[1])[2] if active \
            else "(no host event)"
        named[name] = named.get(name, 0.0) + (b - a)
    return named


def summarize(trace: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Per chip and averaged: busy seconds (union of the intervals in
    which an op ran), seconds and calls by op and by (program, kind of
    op), exposed collective seconds (a collective in flight and no other
    op running on that chip); idle gaps named from the first chip's
    timeline."""
    lo, hi = window_of(trace)
    chips = trace["chips"]
    if not chips:
        raise ValueError("the trace holds no device plane")
    by_module_kind: Dict[str, float] = {}
    op_calls: Dict[str, Dict[str, Any]] = {}
    busy_each, exposed_each, gaps_first = [], [], None
    for plane in sorted(chips):
        chip = chips[plane]
        module_of = _module_of(chip["modules"])
        compute, coll = [], []
        for name, start, dur in chip["ops"]:
            a, b = max(start, lo), min(start + dur, hi)
            if b <= a:
                continue
            kind = op_kind(name)
            (coll if is_collective(name) else compute).append((a, b))
            if kind in CONTAINERS:
                continue
            module = module_of(start)
            mk = f"{module}|{kind}"
            by_module_kind[mk] = by_module_kind.get(mk, 0.0) + (b - a)
            rec = op_calls.setdefault(short_name(name), {
                "kind": kind, "module": module, "name": name[:400],
                "calls": 0, "seconds": 0.0})
            rec["calls"] += 1
            rec["seconds"] += b - a
        coll += _spans([e for e in chip["async"] if is_collective(e[0])],
                       lo, hi)
        busy = union(compute + coll)
        busy_each.append(total(busy))
        exposed_each.append(total(subtract(union(coll), union(compute))))
        if gaps_first is None:
            gaps_first = subtract([(lo, hi)], busy)
    n = len(chips)
    gaps = _name_gaps(gaps_first or [], trace["host"])
    ops = sorted(((k, r["seconds"] / n) for k, r in op_calls.items()),
                 key=lambda kv: -kv[1])
    return {
        "window_s": hi - lo, "chips": n,
        "busy_s": sum(busy_each) / n,
        "exposed_collective_s": sum(exposed_each) / n,
        # seconds summed over chips; the readers divide by chips
        "by_module_kind": by_module_kind, "op_calls": op_calls,
        "idle_gaps": gaps,
        "breakdown": {
            "device_ops": [list(kv) for kv in ops[:top]],
            "idle_gaps": [list(kv) for kv in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]]},
    }
