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
device trace's clock. :func:`clocks` is how readers find them.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

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
        self.count += 1
        self.seconds += t1 - self.t0
        c.ring.append((self.name, c.tick_no, self.t0, t1, self.parent))
        if self.wait:
            if c._root_t0 >= c._since and not c._in_flight:
                c._idle_from = t1
        elif self.parent is None and c._idle_from is not None:
            c.gap_s += t1 - c._idle_from
            c._idle_from = None


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

    Other threads may read ``totals()`` and ``spans()`` at any time.
    """

    def __init__(self, owner: str, steps: bool = False):
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        self.owner = owner
        self.tick_no = 0
        self.gap_s = 0.0
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
        self.gap_s = 0.0
        self.ring.clear()
