"""From a profiler trace to numbers. ``load`` reads the ``.xplane.pb``
that ``jax.profiler`` wrote into plain lists; everything after it works
on those lists, so the tests check the arithmetic on a small recorded
trace. Times are seconds on the trace's own clock.

What a v5e trace looks like (jax 0.9.0): one plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per executed
program, ``jit__decode_fn(<program id>)``), ``XLA Ops`` (one per HLO op,
the name being the op's HLO text,
``%flash_fwd.1 = (bf16[1,16,2048,256]{...}, ...) custom-call(...``) and
``Async XLA Ops`` (start-to-done spans of asynchronous copies and
collectives); one plane ``/host:CPU`` with a line per thread
(``<thread name>/<thread id>``), holding the runtime's own events, the
Python tracer's (``$file.py:12 fn``) and ``TraceAnnotation`` spans.

What the program put into it, and where it is kept. A ``PhaseClock``'s
annotation (``engine.*``, ``train.*``) carries the stat ``tick`` (the
outermost phase of a stepping clock ``step_num``) on the event itself,
which ``ProfileData`` gives. A device op's scope path
(``jax.named_scope``: ``jit(_decode_fn)/while/body/closed_call/layer/
attn/kv_write/scatter:``) is the stat ``tf_op`` of the op's
``XEventMetadata`` in the device plane, beside ``program_id``;
``ProfileData`` gives an event's own stats only, so ``op_scopes`` reads
those two out of the file's protobuf wire format itself (the device
planes' metadata tables alone: a few thousand entries, whatever the
host plane weighs).
"""
import bisect
import glob
import heapq
import mmap
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

#: names for idleness that no annotation covers, for a gap too short to
#: name, for ops the program gave no scope
OUTSIDE, SHORT, NO_SCOPE = "(outside a tick)", "(gaps under 20 us)", \
    "(no scope)"
#: components of an op's path that JAX's own machinery writes, not a
#: ``jax.named_scope``
STRUCTURAL = {"while", "body", "cond", "closed_call", "checkpoint",
              "rematted_computation", "remat", "custom_vjp_call",
              "custom_jvp_call", "custom_vjp_call_jaxpr", "shard_map",
              "pallas_call", "core_call", "scan"}

_OP = re.compile(r"^%([\w\-.]+) = ")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_WRAPPED = re.compile(r"^([\w.\-]+)\((.*)\)$")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# --------------------------------------------------------------- loading
def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _varint(buf, i: int) -> Tuple[int, int]:
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) over the protobuf message ``buf[lo:hi]``:
    an integer for a varint, ``(start, end)`` for a length-delimited
    field, None for a fixed-width one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _map_entry(buf, span: Interval) -> Tuple[int, Optional[Interval]]:
    key, value = 0, None
    for n, v in _fields(buf, *span):
        if n == 1:
            key = v
        elif n == 2:
            value = v
    return key, value


def op_scopes(path: str) -> Dict[str, Dict[Tuple[int, str], str]]:
    """device plane -> {(program id, the op's HLO text): its ``tf_op``},
    from the planes' ``event_metadata`` and ``stat_metadata`` tables
    (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5; XStat
    .metadata_id = 1, .uint64 = 3, .int64 = 4, .str = 5, .ref = 7;
    XStatMetadata.name = 2)."""
    out: Dict[str, Dict[Tuple[int, str], str]] = {}
    with open(path, "rb") as f, \
            mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as buf:
        def text(span):
            return buf[span[0]:span[1]].decode("utf-8", "replace")

        for num, plane in _fields(buf, 0, len(buf)):
            if num != 1:
                continue
            name, events, stats = "", [], []
            for n, v in _fields(buf, *plane):
                if n == 2:
                    name = text(v)
                elif n == 4:
                    events.append(v)
                elif n == 5:
                    stats.append(v)
            if not name.startswith("/device:TPU:"):
                continue
            stat_name: Dict[int, str] = {}
            for span in stats:
                key, value = _map_entry(buf, span)
                for n, v in _fields(buf, *value):
                    if n == 2:
                        stat_name[key] = text(v)
            table = out.setdefault(name, {})
            for span in events:
                _, meta = _map_entry(buf, span)
                hlo, scope, program = "", None, 0
                for n, v in _fields(buf, *meta):
                    if n == 2:
                        hlo = text(v)
                    elif n == 5:
                        stat = dict(_fields(buf, *v))
                        what = stat_name.get(stat.get(1))
                        if what == "tf_op":
                            scope = text(stat[5]) if 5 in stat \
                                else stat_name.get(stat.get(7))
                        elif what == "program_id":
                            program = stat.get(4, stat.get(3, 0))
                if scope:
                    table[(program, hlo)] = scope
    return out


def load(path: str) -> Dict[str, Any]:
    """{"chips": {plane: {"ops", "async", "modules"}}, "host": [...]}:
    a device event is ``[name, start_s, duration_s]``, an op with its
    scope path as the file has it (``tf_op``, "" where it has none) as a
    fourth item; a host event ``[line, name, start_s, duration_s]``, an
    annotation with its ``tick`` (or ``step_num``) as a fifth item."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    scopes = op_scopes(path)
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
            table = scopes.get(plane.name, {})
            module_at = _module_of(chip["modules"])
            for op in chip["ops"]:
                program = _PROGRAM_ID.search(module_at(op[1]))
                op.append(table.get(
                    (int(program.group(1)) if program else 0, op[0]))
                    or table.get((0, op[0]), ""))   # no program id kept
        elif plane.name == "/host:CPU":
            # the runtime's own lines are ``<thread name>/<thread id>``,
            # but every Python thread's line (its frames, its
            # annotations) is named after the process, ``python3``: a
            # thread is its line, so a name met again gets its ordinal
            seen: Dict[str, int] = {}
            for line in plane.lines:
                nth = seen[line.name] = seen.get(line.name, 0) + 1
                name = line.name if nth == 1 else f"{line.name}#{nth}"
                for e in line.events:
                    what = e.name
                    event = [name, what, e.start_ns * 1e-9,
                             e.duration_ns * 1e-9]
                    if not what.startswith("$"):     # not a Python frame
                        for key, value in e.stats:
                            if key in ("tick", "step_num"):
                                event.append(int(value))
                                break
                    host.append(event)
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


def scope_path(tf_op: str) -> str:
    """``layer/attn/kv_write`` from ``jit(_decode_fn)/while/body/
    closed_call/layer/attn/kv_write/scatter:``: the ``jax.named_scope``
    names alone. The last component is the primitive; ``jit(...)`` names
    a function; ``transpose(jvp(layer))`` is the scope ``layer`` seen
    through a transformation; an einsum writes its equation; the rest
    is ``STRUCTURAL``. A scope repeated by a transformation
    (``jvp(lm_head_loss)/lm_head_loss``, ``layer/layer/checkpoint``)
    counts once. Where the compiler joined several ops' paths with
    ``;`` (a fusion of theirs), the first stands for all."""
    out: List[str] = []
    for part in tf_op.split(";", 1)[0].rsplit(":", 1)[0].split("/")[:-1]:
        m = _WRAPPED.match(part)
        while m:
            part = "" if m.group(1) in ("jit", "pjit") else m.group(2)
            m = _WRAPPED.match(part)
        if not part or part in STRUCTURAL or "," in part or "->" in part \
                or part.startswith("branch_") or (out and out[-1] == part):
            continue
        out.append(part)
    return "/".join(out)


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
    return clip([(e[1], e[1] + e[2]) for e in events], lo, hi)


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
    """start time -> the event name of the program that was running
    then, ``jit__decode_fn(<program id>)``, "" if none."""
    mods = sorted(modules, key=lambda e: e[1])
    starts = [e[1] for e in mods]

    def find(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t <= mods[i][1] + mods[i][2]:
            return mods[i][0]
        return ""
    return find


def host_lines(host: Sequence[list]) -> Dict[str, List[int]]:
    """line -> [events, annotations, program launches], of the lines
    that hold an annotation or launch a program: what the choice of the
    driving thread is made from."""
    out: Dict[str, List[int]] = {}
    for e in host:
        rec = out.setdefault(e[0], [0, 0, 0])
        rec[0] += 1
        rec[1] += len(e) > 4
        rec[2] += e[1].startswith("PjitFunction")
    return {k: v for k, v in out.items() if v[1] or v[2]}


def driver_line(lines: Dict[str, List[int]]) -> Optional[str]:
    """Of ``host_lines``, the line of the thread that drives the device:
    the one holding the most annotations (a ``PhaseClock`` belongs to
    one thread), else the one launching the most programs, else None."""
    by = 1 if any(v[1] for v in lines.values()) else 2
    return max(lines, key=lambda k: lines[k][by]) if lines else None


def _innermost(annotations: Sequence[Tuple[float, float, str]]
               ) -> List[Tuple[float, float, str]]:
    """One thread's nested annotations ``(start, end, name)`` as
    disjoint pieces in time order, each under the name of the innermost
    annotation that covers it."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []        # (end, name), innermost last
    at = 0.0

    def close(upto: float) -> None:
        nonlocal at
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > at:
                pieces.append((at, end, name))
                at = end

    for a, b, name in sorted(annotations, key=lambda x: (x[0], -x[1])):
        close(a)
        if stack and a > at:
            pieces.append((at, a, stack[-1][1]))
        at = max(at, a) if stack else a
        stack.append((b, name))
    close(float("inf"))
    return pieces


def _name_gaps(gaps: Sequence[Interval], host: Sequence[list],
               driver: Optional[str]) -> Dict[Tuple[str, str], float]:
    """Seconds of device idleness by what the driving thread was doing,
    on two levels: ``(annotation, frame)``. Every gap is cut along the
    thread's annotations and each part goes to the innermost one that
    covers it, what none covers to ``OUTSIDE``; within that, a part goes
    to the shortest other event of the thread over its middle (the most
    specific frame it was inside), parts under 20 us pooled as
    ``SHORT``. Only the thread that drives the device (``driver``, its
    line; None takes every line): what the others are inside says
    nothing about why the device waits."""
    mine = [e for e in host if driver in (None, e[0])
            and e[1] not in (OPEN_MARK, CLOSE_MARK)]
    pieces = _innermost([(e[2], e[2] + e[3], e[1])
                         for e in mine if len(e) > 4])
    parts: List[Tuple[float, float, str]] = []
    j = 0
    for a, b in sorted(gaps):
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        at, k = a, j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi, name = pieces[k]
            if lo > at:
                parts.append((at, lo, OUTSIDE))
            parts.append((max(at, lo), min(hi, b), name))
            at = min(hi, b)
            k += 1
        if at < b:
            parts.append((at, b, OUTSIDE))
    frames = sorted((e for e in mine if len(e) == 4), key=lambda e: e[2])
    active: list = []          # (end, duration, name)
    named: Dict[Tuple[str, str], float] = {}
    i = 0
    for a, b, phase in parts:
        frame = SHORT
        if b - a >= SMALL_GAP_S:
            mid = 0.5 * (a + b)
            while i < len(frames) and frames[i][2] <= mid:
                e = frames[i]
                heapq.heappush(active, (e[2] + e[3], e[3], e[1]))
                i += 1
            while active and active[0][0] < mid:
                heapq.heappop(active)
            frame = min(active, key=lambda x: x[1])[2] if active \
                else "(no host event)"
        named[(phase, frame)] = named.get((phase, frame), 0.0) + (b - a)
    return named


def summarize(trace: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Per chip and averaged: busy seconds (union of the intervals in
    which an op ran), seconds and calls by op, by (program, kind of op)
    and by named scope (``by_scope``: seconds a chip, under the op's
    innermost scope and under each prefix of its path, so ``layer/attn``
    sums its children; ops without one under ``NO_SCOPE``), exposed
    collective seconds (a collective in flight and no other op running
    on that chip); the first chip's idle gaps by the driving thread's
    annotation (``idle_by_phase``, what none covers being
    ``idle_outside_tick_s``) and by annotation and frame
    (``idle_by_phase_frame``; flat, as ``<annotation>: <frame>``, in
    ``idle_gaps``)."""
    lo, hi = window_of(trace)
    chips = trace["chips"]
    if not chips:
        raise ValueError("the trace holds no device plane")
    n = len(chips)
    by_module_kind: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    prefixes: Dict[str, List[str]] = {}      # an op's path -> its prefixes
    op_calls: Dict[str, Dict[str, Any]] = {}
    busy_each, exposed_each, gaps_first = [], [], None
    for plane in sorted(chips):
        chip = chips[plane]
        module_of = _module_of(chip["modules"])
        compute, coll = [], []
        for name, start, dur, *tf_op in chip["ops"]:
            a, b = max(start, lo), min(start + dur, hi)
            if b <= a:
                continue
            kind = op_kind(name)
            (coll if is_collective(name) else compute).append((a, b))
            if kind in CONTAINERS:
                continue
            module = module_of(start).split("(")[0]
            mk = f"{module}|{kind}"
            by_module_kind[mk] = by_module_kind.get(mk, 0.0) + (b - a)
            raw = tf_op[0] if tf_op else ""
            if raw not in prefixes:        # a few thousand distinct paths
                parts = scope_path(raw).split("/")
                prefixes[raw] = ["/".join(parts[:d + 1])
                                 for d in range(len(parts))] \
                    if parts[0] else [NO_SCOPE]
            for prefix in prefixes[raw]:
                by_scope[prefix] = by_scope.get(prefix, 0.0) + (b - a) / n
            scope = prefixes[raw][-1]
            rec = op_calls.setdefault(short_name(name), {
                "kind": kind, "module": module, "name": name[:400],
                "scope": "" if scope == NO_SCOPE else scope,
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
    lines = host_lines(trace["host"])
    driver = driver_line(lines)
    named = _name_gaps(gaps_first or [], trace["host"], driver)
    by_phase: Dict[str, float] = {}
    by_phase_frame: Dict[str, Dict[str, float]] = {}
    gaps: Dict[str, float] = {}
    for (phase, frame), sec in named.items():
        by_phase[phase] = by_phase.get(phase, 0.0) + sec
        by_phase_frame.setdefault(phase, {})[frame] = sec
        gaps[f"{phase}: {frame}"] = sec
    ops = sorted(((f"{r['scope']}: {k}" if r["scope"] else k,
                   r["seconds"] / n) for k, r in op_calls.items()),
                 key=lambda kv: -kv[1])
    return {
        "window_s": hi - lo, "chips": n,
        "busy_s": sum(busy_each) / n,
        "exposed_collective_s": sum(exposed_each) / n,
        # seconds summed over chips; the readers divide by chips
        "by_module_kind": by_module_kind, "op_calls": op_calls,
        # seconds a chip
        "by_scope": by_scope,
        "driver_line": driver, "host_lines": lines,
        "idle_by_phase": by_phase, "idle_by_phase_frame": by_phase_frame,
        "idle_outside_tick_s": by_phase.get(OUTSIDE, 0.0),
        "idle_gaps": gaps,
        "breakdown": {
            "device_ops": [list(kv) for kv in ops[:top]],
            "idle_gaps": [list(kv) for kv in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]]},
    }
