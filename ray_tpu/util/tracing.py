"""Distributed tracing: spans around tasks/actors.

Reference: ``python/ray/util/tracing/tracing_helper.py`` — Ray wraps
task submission/execution in OpenTelemetry spans when the user enables
tracing with an exporter. Here the same layering: if ``opentelemetry``
is importable AND a real (SDK) tracer provider is configured, spans go
to its tracer; otherwise spans fall back to the runtime's built-in
timeline (``ray-tpu timeline`` renders them in the Chrome trace), so
tracing works out of the box with zero extra dependencies.

Cross-process propagation: a ``span()`` also installs a flight-recorder
trace context (``ray_tpu.core.events``) on the current thread, and the
runtime threads that context through task/actor-call submission
(``TaskSpec.trace``) — so both OpenTelemetry (when configured) and the
built-in timeline show parent→child links across processes. On the
executing side, :func:`task_execution_span` re-parents the task's span
under the propagated remote context.

:class:`PhaseClock` is the hot loops' own clock (the engine's tick, the
train step): always-on counts and seconds per phase, a bounded ring of
the newest spans, and a ``jax.profiler`` annotation around the same
interval so that a running profiler session holds the span on the
device trace's clock; and the outermost phase's books BY KIND of tick
(what the loop says the tick did): counts, seconds, waits, idle gap, a
histogram of lengths, and the slow ones with the phase that held them.
:func:`clocks` is how readers find them.
"""

from __future__ import annotations

import collections
import contextlib
import math
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

_enabled = False
_lock = threading.Lock()


def enable_tracing() -> None:
    """Turn on span recording (reference: ``ray.init(_tracing_startup_
    hook=...)``)."""
    global _enabled
    _enabled = True


def disable_tracing() -> None:
    global _enabled
    _enabled = False


def tracing_enabled() -> bool:
    return _enabled


def _is_noop_provider(provider) -> bool:
    """True for OpenTelemetry's built-in exporterless providers. Name
    checks are case-insensitive and paired with a module check because
    the API has renamed these classes across releases (``DefaultTracer
    Provider`` → ``NoOpTracerProvider`` in ≥1.25; ``ProxyTracer
    Provider`` proxies to one until an SDK provider is installed): any
    provider defined inside the ``opentelemetry.trace``/``opentelemetry
    .util`` API packages is exporterless by construction — only an SDK
    (or third-party) provider can actually export spans."""
    cls = type(provider)
    mod = getattr(cls, "__module__", "") or ""
    if mod == "opentelemetry.trace" or \
            mod.startswith(("opentelemetry.trace.", "opentelemetry.util")):
        return True
    name = cls.__name__.lower()
    return any(s in name for s in ("noop", "proxy", "default"))


def _otel_tracer():
    """A real OpenTelemetry tracer, or None. The default/proxy/no-op
    provider doesn't count: with no user-configured exporter the spans
    would vanish — the timeline fallback is strictly more useful."""
    try:
        from opentelemetry import trace
    except ImportError:
        return None
    try:
        provider = trace.get_tracer_provider()
    except Exception:
        return None
    if _is_noop_provider(provider):
        return None
    return trace.get_tracer("ray_tpu")


def _otel_ids(span) -> Optional[Tuple[str, str]]:
    """``(trace_id, span_id)`` hex of an OTel span, or None."""
    try:
        ctx = span.get_span_context()
        return (format(ctx.trace_id, "032x"), format(ctx.span_id, "016x"))
    except Exception:
        return None


@contextlib.contextmanager
def span(name: str, attributes: Optional[Dict[str, Any]] = None
         ) -> Iterator[None]:
    """Record one span. OpenTelemetry when available; else the span
    lands in the runtime timeline as a complete event. Either way the
    span becomes the current flight-recorder trace context, so tasks
    submitted inside it carry a parent→child link across processes."""
    if not _enabled:
        yield
        return
    from ray_tpu.core import events as EV
    tracer = _otel_tracer()
    if tracer is not None:
        with tracer.start_as_current_span(name) as s:
            for k, v in (attributes or {}).items():
                s.set_attribute(k, v)
            ids = _otel_ids(s)
            token = EV.set_context(*ids) if ids else None
            try:
                yield
            finally:
                if ids:
                    EV.restore(token)
        return
    # built-in fallback: new span id, inherit (or root) the trace id
    cur = EV.current()
    span_id = EV.new_span_id()
    trace_id = cur[0] if cur is not None else span_id * 2
    token = EV.set_context(trace_id, span_id)
    start = time.time()
    try:
        yield
    finally:
        EV.restore(token)
        dur = time.time() - start
        from ray_tpu.core.global_state import try_global_worker
        w = try_global_worker()
        if w is not None:
            try:
                w.record_span(name, start, dur, trace_id=trace_id,
                              span_id=span_id,
                              parent=cur[1] if cur else None,
                              **(attributes or {}))
            except Exception:
                pass


@contextlib.contextmanager
def task_execution_span(name: str, trace: Optional[tuple]
                        ) -> Iterator[None]:
    """Executing-side half of cross-process propagation: when tracing
    is enabled and a real OTel provider is configured, run the task
    body inside a span whose REMOTE parent is the propagated
    ``TaskSpec.trace`` context — OTel backends then render the same
    parent→child links the flight recorder records. No-op (single
    boolean check) when tracing is off."""
    if not _enabled:
        yield
        return
    tracer = _otel_tracer()
    if tracer is None:
        yield
        return
    try:
        from opentelemetry import trace as otrace
        from opentelemetry.trace import (
            NonRecordingSpan, SpanContext, TraceFlags)
        parent_ctx = None
        if trace and trace[0]:
            parent_span = trace[1]
            sc = SpanContext(
                trace_id=int(trace[0][:32].ljust(32, "0"), 16),
                span_id=int((parent_span or trace[0][:16]).ljust(16, "0"),
                            16),
                is_remote=True, trace_flags=TraceFlags(1))
            parent_ctx = otrace.set_span_in_context(NonRecordingSpan(sc))
    except Exception:
        yield
        return
    with tracer.start_as_current_span(name, context=parent_ctx):
        yield


# ------------------------------------------------------------ phase clock
#: newest spans a clock keeps (a few hundred ticks of a dozen phases)
PHASE_RING = 4096
#: buckets an octave of the histogram of tick lengths (a bucket is 9% wide)
HIST_PER_OCTAVE = 8
#: a tick is slow beyond this many medians of its kind; the median is
#: the kind's histogram's, read again every ``SLOW_REFRESH`` ticks of the
#: kind (none before the first); the newest ``SLOW_KEPT`` are kept whole
SLOW_FACTOR = 4.0
SLOW_REFRESH = 32
SLOW_KEPT = 64

_clocks: Dict[str, "PhaseClock"] = {}


def clocks() -> Dict[str, "PhaseClock"]:
    """owner -> the newest :class:`PhaseClock` registered under that
    name in this process (``stats()``, the dashboard's stats and an
    in-process reader find the loops' clocks here)."""
    with _lock:
        return dict(_clocks)


class _Phase:
    """One named phase of one clock: its totals, and the context
    manager ``clock.phase(name)`` hands out (one object per name, so
    entering a phase allocates nothing but its ring entry and, under a
    profiler session, its annotation). ``stat`` is what this entry's
    annotation carries beside ``tick``. A phase never nests inside
    itself."""

    __slots__ = ("clock", "name", "count", "seconds", "t0", "parent",
                 "ann", "dispatch", "wait", "stat")

    def __init__(self, clock: "PhaseClock", name: str):
        self.clock, self.name = clock, name
        self.count, self.seconds = 0, 0.0
        self.t0, self.parent, self.ann = 0.0, None, None
        self.stat: Dict[str, Any] = {}
        self.dispatch = name.endswith(".dispatch")
        self.wait = name.endswith(".wait")

    def __enter__(self) -> "_Phase":
        c = self.clock
        stack = c._stack
        self.parent = stack[-1] if stack else None
        # with no profiler session running this is a flag test
        if c._annotation.is_enabled():
            if self.parent is None and c._step_annotation is not None:
                self.ann = c._step_annotation(
                    self.name, step_num=c.tick_no, **self.stat)
            else:
                self.ann = c._annotation(self.name, tick=c.tick_no,
                                         **self.stat)
            self.ann.__enter__()
        stack.append(self.name)
        self.t0 = t = time.perf_counter()
        if self.parent is None:
            c._root_t0 = t
            # a program launched in the last root phase may still be out
            c._idle_from = None if c._in_flight else t
            c.kind, c._tick_wait = None, 0.0    # the loop names the tick
        elif self.dispatch:
            c._in_flight += 1
            if c._idle_from is not None:
                c.gap_s += t - c._idle_from
                c._idle_from = None
        return self

    def __exit__(self, *exc) -> None:
        c = self.clock
        t1 = time.perf_counter()
        c._stack.pop()
        if self.ann is not None:
            self.ann.__exit__(*exc)
            self.ann = None
        if c._in_flight and (self.wait or
                             (self.dispatch and exc[0] is not None)):
            c._in_flight -= 1       # fetched, or the launch itself raised
        if self.t0 < c._since:
            return              # began before a reset: not in its books
        if self.parent is None:
            c._seq_begin += 1   # books() reads none of a tick half booked
        self.count += 1
        self.seconds += t1 - self.t0
        c.ring.append((self.name, c.tick_no, self.t0, t1, self.parent))
        if self.wait:
            c._tick_wait += t1 - self.t0
            if c._root_t0 >= c._since and not c._in_flight:
                c._idle_from = t1
        if self.parent is None:
            if c._idle_from is not None:
                c.gap_s += t1 - c._idle_from
                c._idle_from = None
            c._book_tick(self, t1 - self.t0)
            c._seq_end += 1


class _Kind:
    """One kind of tick's books, cumulative since ``reset``: how many,
    their seconds, the seconds of the ``*.wait`` phases that ended in
    them, their part of ``gap_s``, a histogram of their lengths
    (bucket number -> ticks; bucket ``b`` starts at :func:`bucket_edge`),
    the slow ones, and the length beyond which one is slow (infinite
    with no median yet, and for a kind the rule leaves out)."""

    __slots__ = ("bounded", "count", "seconds", "wait_s", "gap_s", "hist",
                 "slow", "slow_above")

    def __init__(self, bounded: bool):
        self.bounded = bounded
        self.clear()

    def clear(self) -> None:
        self.count = self.slow = 0
        self.seconds = self.wait_s = self.gap_s = 0.0
        self.hist: Dict[int, int] = {}
        self.slow_above = math.inf

    def quantile(self, q: float) -> Optional[float]:
        """Seconds below which ``q`` of the kind's ticks lie, placed
        inside its bucket by rank (geometrically: a bucket is a ratio
        wide); None with no tick."""
        hist = sorted(self.hist.items())
        rank, below = q * sum(n for _, n in hist), 0
        for b, n in hist:
            if below + n >= rank:
                return bucket_edge(b + (rank - below) / n)
            below += n
        return None


def bucket_edge(b: float) -> float:
    """Seconds at which histogram bucket ``b`` starts
    (``HIST_PER_OCTAVE`` buckets double it)."""
    return 2.0 ** (b / HIST_PER_OCTAVE)


class PhaseClock:
    """The clock a hot loop owns. One thread drives it::

        clock.tick()
        with clock.phase("engine.tick"):
            with clock.phase("engine.admit"):
                ...

    Always: per phase a count and a sum of seconds
    (``time.perf_counter``), and a ring of the newest ``(name, tick, t0,
    t1, parent)`` spans, ``parent`` being the name of the phase it ran
    inside. Around the same interval a ``jax.profiler.TraceAnnotation``
    carrying ``tick`` (``StepTraceAnnotation`` with ``step_num`` for the
    outermost phase of a ``steps=True`` clock), so a profiler trace
    holds the span in its host plane and joins the ring's entry by tick.
    ``clock.phase(name, ready=1)`` puts a further stat on that one
    entry's annotation; with no session it is kept and never read.

    ``gap_s`` is the clock's account of the device having nothing
    queued. A ``*.dispatch`` phase launches a program (unless it
    raises) and a ``*.wait`` phase fetches the oldest one out;
    ``gap_s`` is the time inside an outermost phase with none out:
    from the end of the ``*.wait`` that
    brought the count to zero (or the outermost phase's start, if none
    was out then) to the start of the next ``*.dispatch``. A loop with
    one program out at a time reads the time outside [dispatch start,
    wait end]; a program launched in one outermost phase and fetched in
    the next keeps the count up between them.

    An outermost phase is a tick, and when it ends it is booked under
    its KIND: ``clock.kind`` if the loop set it inside the tick (the
    engine: what the tick fetched), else the clock's one ``kind``. By
    kind, cumulative since ``reset()``: the count, the seconds (the
    same ``t1 - t0`` the phase's own total sums), the seconds of the
    ``*.wait`` phases that ended inside, the tick's part of ``gap_s``,
    and one count in a geometric histogram of its length. A tick longer
    than ``SLOW_FACTOR`` medians of its kind is slow: one count under
    the kind, its overrun (length less that median) under the name of
    the phase directly inside it that held the most of it (the
    outermost phase's own name: the time between its phases), read back
    from the ring, and the tick kept whole in ``slow_ticks`` and handed
    to ``on_slow``. Kinds whose name starts with one of ``unbounded``
    are left out of that rule (a median bounds nothing where the work
    of a tick is anything up to a bound). ``books()`` reads them all.

    Other threads may read ``totals()``, ``spans()`` and ``books()`` at
    any time.
    """

    def __init__(self, owner: str, steps: bool = False,
                 kind: str = "step", unbounded: Tuple[str, ...] = (),
                 on_slow: Optional[Callable[[tuple, str], None]] = None):
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        self.owner = owner
        self.tick_no = 0
        self.gap_s = 0.0
        self.kind: Optional[str] = None     # the running tick's, if named
        self.default_kind, self.unbounded = kind, tuple(unbounded)
        self.on_slow = on_slow
        #: newest slow ticks: (tick, kind, wall-clock start, seconds,
        #: {phase directly inside: seconds})
        self.slow_ticks: "collections.deque[tuple]" = collections.deque(
            maxlen=SLOW_KEPT)
        self._kinds: Dict[str, _Kind] = {}
        self._slow_s: Dict[str, float] = {}     # phase -> overrun seconds
        self._tick_wait = self._gap_booked = 0.0
        self._seq_begin = self._seq_end = 0
        self.ring: "collections.deque[tuple]" = collections.deque(
            maxlen=PHASE_RING)
        self._phases: Dict[str, _Phase] = {}
        self._stack: List[str] = []
        self._idle_from: Optional[float] = None
        self._in_flight = 0     # programs dispatched and not yet waited for
        self._root_t0 = self._since = 0.0
        self._annotation = TraceAnnotation
        self._step_annotation = StepTraceAnnotation if steps else None
        with _lock:
            _clocks[owner] = self

    def tick(self) -> int:
        """Start the next tick; every span until the next call carries
        its number."""
        self.tick_no += 1
        return self.tick_no

    def phase(self, name: str, **stat: Any) -> _Phase:
        p = self._phases.get(name)
        if p is None:
            p = self._phases[name] = _Phase(self, name)
        p.stat = stat
        return p

    def totals(self) -> Dict[str, List[float]]:
        """name -> [count, seconds] over every finished phase."""
        return {name: [p.count, p.seconds]
                for name, p in list(self._phases.items())}

    def seconds(self, name: str) -> float:
        p = self._phases.get(name)
        return p.seconds if p is not None else 0.0

    def spans(self) -> List[tuple]:
        """The ring, oldest first: (name, tick, t0, t1, parent)."""
        return list(self.ring)

    def reset(self) -> None:
        """Zero the totals and empty the ring (tick numbers go on). A
        phase another thread is inside of stays out of the new books."""
        self._since = time.perf_counter()
        self._idle_from = None
        self._in_flight = 0
        for p in list(self._phases.values()):
            p.count, p.seconds = 0, 0.0
        for k in list(self._kinds.values()):
            k.clear()           # a kind once met keeps its (empty) books
        self._slow_s.clear()
        self.slow_ticks.clear()
        self.gap_s = self._gap_booked = 0.0
        self.ring.clear()

    # ----------------------------------------------------- books by kind
    def _book_tick(self, root: _Phase, dt: float) -> None:
        """An outermost phase has ended after ``dt`` seconds."""
        kind = self.kind or self.default_kind
        k = self._kinds.get(kind)
        if k is None:
            k = self._kinds[kind] = _Kind(
                not kind.startswith(self.unbounded))
        k.count += 1
        k.seconds += dt
        k.wait_s += self._tick_wait
        k.gap_s += self.gap_s - self._gap_booked
        self._gap_booked = self.gap_s
        # (a clock that did not move: a bucket under any a tick can reach)
        b = math.floor(math.log2(dt) * HIST_PER_OCTAVE) if dt > 0 else -512
        k.hist[b] = k.hist.get(b, 0) + 1
        if dt > k.slow_above:
            self._book_slow(root, kind, k, dt)
        if k.bounded and not k.count % SLOW_REFRESH:
            k.slow_above = SLOW_FACTOR * k.quantile(0.5)

    def _book_slow(self, root: _Phase, kind: str, k: _Kind,
                   dt: float) -> None:
        """A slow tick: what each phase directly inside it held, from
        the ring's entries of this tick (newest first, the tick's own
        entry among them)."""
        held: Dict[str, float] = {}
        for name, _, t0, t1, parent in reversed(self.ring):
            if t0 < root.t0:
                break
            if parent == root.name:
                held[name] = held.get(name, 0.0) + (t1 - t0)
        held[root.name] = max(0.0, dt - sum(held.values()))
        phase = max(held, key=held.get)
        k.slow += 1
        self._slow_s[phase] = self._slow_s.get(phase, 0.0) \
            + dt - k.slow_above / SLOW_FACTOR
        tick = (self.tick_no, kind,
                time.time() - (time.perf_counter() - root.t0), dt, held)
        self.slow_ticks.append(tick)
        if self.on_slow is not None:
            self.on_slow(tick, phase)

    def books(self) -> Dict[str, Any]:
        """One reading of the books as they stood at a tick's end (read
        again if a tick was booked meanwhile): ``phases`` (``totals()``),
        ``gap_s`` (up to the last tick booked; ``clock.gap_s`` runs ahead
        of it inside a tick), by kind ``tick_kind_total``, ``tick_kind_s``,
        ``tick_kind_wait_s``, ``tick_kind_gap_s`` and ``tick_slow_total``,
        ``tick_hist_<kind>`` (the bucket's lower edge in seconds ->
        ticks), ``tick_slow_s`` (phase -> overrun seconds) and
        ``slow_ticks``. The kinds' counts add up to the outermost
        phase's, their seconds to its seconds, their gaps to ``gap_s``,
        a kind's histogram to its count."""
        for _ in range(8):
            end = self._seq_end
            out = self._read_books()
            if self._seq_begin == end:
                break
        return out

    def _read_books(self) -> Dict[str, Any]:
        kinds = list(self._kinds.items())
        out: Dict[str, Any] = {
            "phases": self.totals(), "gap_s": self._gap_booked,
            "tick_kind_total": {kind: k.count for kind, k in kinds},
            "tick_kind_s": {kind: k.seconds for kind, k in kinds},
            "tick_kind_wait_s": {kind: k.wait_s for kind, k in kinds},
            "tick_kind_gap_s": {kind: k.gap_s for kind, k in kinds},
            "tick_slow_total": {kind: k.slow for kind, k in kinds},
            "tick_slow_s": dict(self._slow_s),
            "slow_ticks": list(self.slow_ticks)}
        for kind, k in kinds:
            out[f"tick_hist_{kind}"] = {
                bucket_edge(b): n for b, n in list(k.hist.items())}
        return out

    def quantile(self, kind: str, q: float) -> Optional[float]:
        """Seconds below which ``q`` (0 to 1) of the kind's ticks lie,
        from its histogram: within a bucket's width; None for a kind
        with no tick."""
        k = self._kinds.get(kind)
        return k.quantile(q) if k is not None else None
