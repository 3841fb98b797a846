"""The phase clock (``util/tracing.PhaseClock``): sums, nesting, the
ring's bound, the tick that joins a span to the profiler's annotation,
the account of the device having nothing queued, and the registry."""
import time

from ray_tpu.util import tracing
from ray_tpu.util.tracing import PhaseClock


def _run_ticks(clock, n, wait_s=0.002):
    for _ in range(n):
        clock.tick()
        with clock.phase("loop.tick"):
            with clock.phase("loop.stage"):
                pass
            with clock.phase("loop.dispatch"):
                pass
            with clock.phase("loop.wait"):
                time.sleep(wait_s)
            with clock.phase("loop.emit"):
                pass


def test_counts_and_seconds_sum_where_the_work_happens():
    clock = PhaseClock("t-sums")
    _run_ticks(clock, 5)
    totals = clock.totals()
    assert {n: c for n, (c, _) in totals.items()} == {
        "loop.tick": 5, "loop.stage": 5, "loop.dispatch": 5,
        "loop.wait": 5, "loop.emit": 5}
    children = sum(s for n, (_, s) in totals.items() if n != "loop.tick")
    tick = totals["loop.tick"][1]
    assert totals["loop.wait"][1] >= 5 * 0.002
    assert children <= tick
    assert clock.seconds("loop.tick") == tick
    assert clock.seconds("never.ran") == 0.0


def test_a_phase_inside_a_phase_records_its_parent_and_lies_inside_it():
    clock = PhaseClock("t-nest")
    _run_ticks(clock, 3)
    by_tick = {}
    for name, tick, t0, t1, parent in clock.spans():
        by_tick.setdefault(tick, {})[name] = (t0, t1, parent)
    assert sorted(by_tick) == [1, 2, 3]
    for spans in by_tick.values():
        lo, hi, parent = spans.pop("loop.tick")
        assert parent is None
        last = lo
        for name in ("loop.stage", "loop.dispatch", "loop.wait",
                     "loop.emit"):
            t0, t1, up = spans[name]
            assert up == "loop.tick"
            assert last <= t0 <= t1 <= hi
            last = t1


def test_the_ring_is_bounded_and_keeps_the_newest_ticks():
    clock = PhaseClock("t-ring")
    n = tracing.PHASE_RING // 5 + 50
    _run_ticks(clock, n, wait_s=0.0)
    spans = clock.spans()
    assert len(spans) == tracing.PHASE_RING
    assert spans[-1][0] == "loop.tick" and spans[-1][1] == n
    assert min(s[1] for s in spans) > 1          # the oldest are gone
    assert clock.totals()["loop.tick"][0] == n   # the totals forget none


def test_gap_is_the_tick_outside_dispatch_to_wait():
    clock = PhaseClock("t-gap")
    clock.tick()
    with clock.phase("loop.tick"):
        time.sleep(0.004)                  # nothing queued yet
        with clock.phase("loop.dispatch"):
            pass
        with clock.phase("loop.wait"):
            time.sleep(0.01)               # the device has the work
        time.sleep(0.003)                  # waited for: nothing queued
    tick = clock.seconds("loop.tick")
    busy = clock.seconds("loop.dispatch") + clock.seconds("loop.wait")
    assert 0.007 <= clock.gap_s <= tick
    assert abs(clock.gap_s - (tick - busy)) < 1e-3
    # a tick that dispatches nothing is all gap
    before = clock.gap_s
    clock.tick()
    with clock.phase("loop.tick"):
        time.sleep(0.002)
    assert clock.gap_s - before >= 0.002


def test_reset_zeroes_totals_and_keeps_counting_ticks():
    clock = PhaseClock("t-reset")
    _run_ticks(clock, 2, wait_s=0.0)
    clock.reset()
    assert clock.spans() == [] and clock.gap_s == 0.0
    assert all(c == 0 and s == 0.0 for c, s in clock.totals().values())
    _run_ticks(clock, 1, wait_s=0.0)
    assert clock.spans()[-1][1] == 3


def test_registry_finds_the_newest_clock_of_an_owner():
    a = PhaseClock("t-owner")
    assert tracing.clocks()["t-owner"] is a
    b = PhaseClock("t-owner")
    assert tracing.clocks()["t-owner"] is b
    found = tracing.clocks()
    found.pop("t-owner")                   # a copy: the registry stays
    assert tracing.clocks()["t-owner"] is b


def test_a_raising_phase_is_closed_and_counted():
    clock = PhaseClock("t-raise")
    clock.tick()
    try:
        with clock.phase("loop.tick"):
            with clock.phase("loop.stage"):
                raise ValueError("boom")
    except ValueError:
        pass
    assert clock.totals()["loop.stage"][0] == 1
    assert clock.totals()["loop.tick"][0] == 1
    with clock.phase("loop.tick"):         # the stack is empty again
        pass
    assert clock.spans()[-1][4] is None
