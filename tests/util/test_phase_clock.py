"""The phase clock (``util/tracing.PhaseClock``): sums, nesting, the
ring's bound, the tick that joins a span to the profiler's annotation,
the account of the device having nothing queued, and the registry."""
import time

import pytest

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


def _outside_dispatch_to_wait(clock, root):
    """What ``gap_s`` read before it counted programs: every ``root``
    span less its [``*.dispatch`` start, ``*.wait`` end]."""
    by_tick = {}
    for name, tick, t0, t1, _ in clock.spans():
        by_tick.setdefault(tick, {})[name.rsplit(".", 1)[1]] = (t0, t1)
    return sum(s[root][1] - s[root][0]
               - (s["wait"][1] - s["dispatch"][0])
               for s in by_tick.values())


@pytest.mark.parametrize("loop,tail", [("loop", "emit"),
                                       ("train", "tail")])
def test_one_program_out_at_a_time_reads_what_it_read(loop, tail):
    """The train step's pattern (dispatch, wait, tail) and the serial
    tick's: the count of programs out is 0 or 1, and the gap is the
    root span outside [dispatch start, wait end], to the clock's own
    arithmetic."""
    clock = PhaseClock(f"t-one-{loop}")
    for _ in range(6):
        clock.tick()
        with clock.phase(f"{loop}.step"):
            time.sleep(0.001)
            with clock.phase(f"{loop}.dispatch"):
                time.sleep(0.001)
            with clock.phase(f"{loop}.wait"):
                time.sleep(0.002)
            with clock.phase(f"{loop}.{tail}"):
                time.sleep(0.001)
    assert clock.gap_s == pytest.approx(
        _outside_dispatch_to_wait(clock, "step"), abs=1e-9)
    assert clock.gap_s >= 6 * 0.002


def test_with_two_programs_out_the_device_idles_only_at_none():
    """One program ahead: B is dispatched while A is out, A is waited
    for, C is dispatched in the next tick's place while B is out. The
    gap is the time with nothing out, inside a root span; a program
    out across two root spans keeps the second's start from counting
    as idle."""
    clock = PhaseClock("t-two")
    idle = 0.0

    def nap(s, counts):
        nonlocal idle
        t0 = time.perf_counter()
        time.sleep(s)
        if counts:
            idle += time.perf_counter() - t0

    clock.tick()
    with clock.phase("loop.tick"):
        nap(0.009, True)                    # nothing launched yet
        with clock.phase("a.dispatch"):
            nap(0.003, False)
        with clock.phase("b.dispatch"):     # ahead: A still out
            nap(0.003, False)
        with clock.phase("a.wait"):
            nap(0.006, False)
        nap(0.009, False)                   # A fetched, B still out
        with clock.phase("c.dispatch"):     # ahead again, under B
            nap(0.003, False)
        with clock.phase("b.wait"):
            nap(0.006, False)
        nap(0.006, False)                   # C is out
    clock.tick()
    with clock.phase("loop.tick"):
        nap(0.009, False)                   # C still out at the start
        with clock.phase("c.wait"):
            nap(0.006, False)
        nap(0.012, True)                    # none out: idle to the end
    # (every stretch that must not count is 3 ms or more)
    assert clock.gap_s == pytest.approx(idle, abs=2.5e-3)
    assert clock.gap_s >= 0.021
    # a reset with a program out: its wait is no launch's, and the next
    # launch starts from none out
    clock.tick()
    with clock.phase("loop.tick"):
        with clock.phase("a.dispatch"):
            pass
        clock.reset()
        with clock.phase("a.wait"):
            pass
    idle = 0.0
    clock.tick()
    with clock.phase("loop.tick"):
        nap(0.006, True)
        with clock.phase("a.dispatch"):
            pass
        with clock.phase("a.wait"):
            pass
    assert clock.gap_s == pytest.approx(idle, abs=2.5e-3)
    assert clock.gap_s >= 0.006
    # a launch that raises put nothing out: the next tick idles again
    clock.tick()
    with pytest.raises(RuntimeError), clock.phase("loop.tick"):
        with clock.phase("a.dispatch"):
            raise RuntimeError("the launch failed")
    clock.tick()
    with clock.phase("loop.tick"):
        nap(0.006, True)
    assert clock.gap_s == pytest.approx(idle, abs=2.5e-3)
    assert clock.gap_s >= 0.012


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


@pytest.mark.parametrize("steps", [False, True], ids=["plain", "steps"])
def test_a_phases_stat_reaches_its_annotation_and_nothing_else(steps):
    """``clock.phase(name, ready=1)``: under a profiler session that
    entry's annotation carries ``ready`` beside ``tick`` (``step_num``
    on a ``steps`` clock's outermost phase), the same phase's next entry
    does not; with no session no annotation is built and the totals are
    what they are without a stat."""
    class Fake:
        """Stands where ``jax.profiler.TraceAnnotation`` does: keeps
        what each one was built with, under a session switched by
        ``on``."""
        on, built = False, []

        def __init__(self, name, **stats):
            Fake.built.append((name, stats))

        is_enabled = classmethod(lambda cls: cls.on)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    clock = PhaseClock(f"t-stat-{steps}", steps=steps)
    clock._annotation = Fake
    if steps:
        clock._step_annotation = Fake
    number = "step_num" if steps else "tick"

    def run():
        clock.tick()
        with clock.phase("loop.tick", launched=2):
            with clock.phase("loop.wait", ready=1):
                pass
            with clock.phase("loop.wait"):
                pass

    run()                                   # no session: a flag test
    assert Fake.built == []
    assert clock.totals()["loop.wait"][0] == 2
    Fake.on = True
    run()
    assert Fake.built == [
        ("loop.tick", {number: 2, "launched": 2}),
        ("loop.wait", {"tick": 2, "ready": 1}),
        ("loop.wait", {"tick": 2})]
    assert clock.totals()["loop.wait"][0] == 4
    assert [s[:2] for s in clock.spans()[-3:]] == [
        ("loop.wait", 2), ("loop.wait", 2), ("loop.tick", 2)]


# ------------------------------------------------------- the books by kind
class _FakeTime:
    """Stands where the module's ``time`` does: the test moves it."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self):
        return self.now

    def time(self):
        return 1.7e9 + self.now

    def sleep(self, s):
        self.now += s


@pytest.fixture
def fake(monkeypatch):
    fake = _FakeTime()
    monkeypatch.setattr(tracing, "time", fake)
    return fake


def _tick(clock, fake, kind=None, stage=0.001, wait=0.008, emit=0.001,
          between=0.0):
    """One tick of ``stage + wait + emit + between`` seconds; named
    ``kind`` inside it, as a loop names it where it fetches."""
    clock.tick()
    with clock.phase("loop.tick"):
        with clock.phase("loop.stage"):
            fake.sleep(stage)
        with clock.phase("loop.dispatch"):
            pass
        with clock.phase("loop.wait"):
            fake.sleep(wait)
        if kind is not None:
            clock.kind = kind
        fake.sleep(between)             # in the tick, in none of its phases
        with clock.phase("loop.emit"):
            fake.sleep(emit)


def test_the_kinds_books_add_up_to_the_outermost_phases(fake):
    clock = PhaseClock("t-kinds", kind="idle")
    for i in range(60):
        _tick(clock, fake, kind=(None, "decode", "full_decode")[i % 3],
              wait=0.002 * (1 + i % 5), between=0.0005 * (i % 2))
    b = clock.books()
    assert b["tick_kind_total"] == {"idle": 20, "decode": 20,
                                    "full_decode": 20}
    # the four identities: counts, seconds, gaps, a histogram's counts
    assert sum(b["tick_kind_total"].values()) == b["phases"]["loop.tick"][0]
    assert sum(b["tick_kind_s"].values()) == pytest.approx(
        clock.seconds("loop.tick"), rel=1e-12)
    assert sum(b["tick_kind_gap_s"].values()) == pytest.approx(
        clock.gap_s, rel=1e-12) and clock.gap_s > 0
    assert b["gap_s"] == clock.gap_s        # no tick is running
    for kind, n in b["tick_kind_total"].items():
        assert sum(b[f"tick_hist_{kind}"].values()) == n
    # and the waits that ended inside the ticks
    assert sum(b["tick_kind_wait_s"].values()) == pytest.approx(
        clock.seconds("loop.wait"), rel=1e-12)
    # a bucket's lower edge lies at or under the lengths it counts
    # (every `decode` tick here is 4 to 12.5 ms)
    assert all(0.004 / 1.1 < edge <= 0.0125
               for edge in b["tick_hist_decode"])
    assert b["tick_slow_total"] == dict.fromkeys(b["tick_kind_total"], 0)
    assert b["tick_slow_s"] == {} and b["slow_ticks"] == []


def test_a_clock_whose_loop_names_nothing_books_one_kind(fake):
    clock = PhaseClock("t-one-kind")
    for _ in range(3):
        _tick(clock, fake)
    assert clock.books()["tick_kind_total"] == {"step": 3}


def test_inside_a_tick_the_books_stand_at_the_last_ticks_end(fake):
    clock = PhaseClock("t-mid")
    _tick(clock, fake)
    clock.tick()
    with clock.phase("loop.tick"):
        fake.sleep(0.004)
        with clock.phase("loop.dispatch"):
            b = clock.books()               # as another thread would
            assert clock.gap_s == pytest.approx(b["gap_s"] + 0.004)
            assert b["gap_s"] == sum(b["tick_kind_gap_s"].values())
            assert b["tick_kind_total"] == {"step": 1}
            assert b["phases"]["loop.tick"][0] == 1


@pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
def test_a_percentile_reads_back_within_a_buckets_width(fake, q):
    clock = PhaseClock("t-quantile")
    lengths = [0.001 * 1.01 ** i for i in range(500)]   # 1 to 143 ms
    for i, s in enumerate(lengths):
        # in no order, as ticks come
        _tick(clock, fake, stage=0.0, emit=0.0,
              wait=lengths[(i * 137) % 500])
    exact = sorted(lengths)[int(q * 500) - 1]
    width = 2 ** (1 / tracing.HIST_PER_OCTAVE)
    assert exact / width <= clock.quantile("step", q) <= exact * width
    assert clock.quantile("never", q) is None


def _slow_books(clock):
    b = clock.books()
    return b["tick_slow_total"], b["tick_slow_s"], b["slow_ticks"]


def test_a_slow_tick_books_its_overrun_under_the_phase_that_held_it(fake):
    seen = []
    clock = PhaseClock("t-slow", kind="idle",
                       on_slow=lambda tick, phase: seen.append((tick, phase)))
    # a compiling first tick, then 10 ms ticks: the median is a 10 ms
    # tick's (a mean would stand at 0.33 s and see no stall under 1.3 s)
    _tick(clock, fake, kind="decode", wait=9.998)
    for _ in range(tracing.SLOW_REFRESH - 3):
        _tick(clock, fake, kind="decode")
    # the 31st of its kind: no median yet, and nothing is slow
    _tick(clock, fake, kind="decode", wait=0.5)
    assert _slow_books(clock) == ({"decode": 0}, {}, [])
    _tick(clock, fake, kind="decode")               # the 32nd
    median = clock.quantile("decode", 0.5)
    assert 0.01 / 1.1 < median < 0.01 * 1.1
    _tick(clock, fake, kind="decode", wait=0.03)    # under four medians
    assert _slow_books(clock)[0] == {"decode": 0}
    start = fake.time()
    _tick(clock, fake, kind="decode", wait=0.098)   # a 100 ms tick
    total, by_phase, kept = _slow_books(clock)
    assert total == {"decode": 1}
    assert by_phase == {"loop.wait": pytest.approx(0.1 - median)}
    (number, kind, began, seconds, held), = kept
    assert (number, kind) == (clock.tick_no, "decode")
    assert began == pytest.approx(start) and seconds == pytest.approx(0.1)
    assert held == pytest.approx({
        "loop.stage": 0.001, "loop.dispatch": 0.0, "loop.wait": 0.098,
        "loop.emit": 0.001, "loop.tick": 0.0}, abs=1e-9)
    assert seen == [(kept[0], "loop.wait")]
    # time in the tick and in none of its phases is the tick's own
    _tick(clock, fake, kind="decode", between=0.2)
    total, by_phase, kept = _slow_books(clock)
    assert total == {"decode": 2} and len(kept) == 2 and len(seen) == 2
    assert by_phase["loop.tick"] == pytest.approx(0.21 - median)
    # another kind keeps its own median: 40 ticks of 10 ms do not make
    # an idle tick of 1 ms fast, nor one of 30 ms slow
    for _ in range(40):
        _tick(clock, fake, stage=0.0, wait=0.001, emit=0.0)
    _tick(clock, fake, wait=0.03)
    assert _slow_books(clock)[0] == {"decode": 2, "idle": 1}


def test_a_kind_the_rule_leaves_out_books_no_slow_tick(fake):
    clock = PhaseClock("t-unbounded", unbounded=("part",))
    for kind in ("part", "part_decode", "full"):
        for _ in range(40):
            _tick(clock, fake, kind=kind)
        _tick(clock, fake, kind=kind, wait=1.0)
    total, by_phase, kept = _slow_books(clock)
    assert total == {"part": 0, "part_decode": 0, "full": 1}
    assert [t[1] for t in kept] == ["full"]
    # its stall still shows in its histogram
    assert max(clock.books()["tick_hist_part"]) > 0.5


def test_the_newest_slow_ticks_are_kept(fake):
    clock = PhaseClock("t-kept")
    for _ in range(tracing.SLOW_REFRESH):
        _tick(clock, fake)
    for _ in range(tracing.SLOW_KEPT + 5):
        _tick(clock, fake, wait=0.5)
        for _ in range(3):
            _tick(clock, fake)              # the median stays a 10 ms tick's
    total, _, kept = _slow_books(clock)
    assert total == {"step": tracing.SLOW_KEPT + 5}
    assert len(kept) == tracing.SLOW_KEPT
    assert kept[-1][0] == clock.tick_no - 3


def test_reset_zeroes_the_books_and_the_median(fake):
    clock = PhaseClock("t-reset-books")
    for _ in range(40):
        _tick(clock, fake, kind="decode")
    _tick(clock, fake, kind="decode", wait=1.0)
    assert _slow_books(clock)[0] == {"decode": 1}
    clock.reset()
    b = clock.books()
    # a kind once met keeps its name, so that a reader who differences
    # two readings finds its keys in both
    assert b["tick_kind_total"] == {"decode": 0}
    assert b["tick_kind_s"] == b["tick_kind_wait_s"] \
        == b["tick_kind_gap_s"] == {"decode": 0.0}
    assert b["tick_hist_decode"] == {} and b["gap_s"] == 0.0
    assert _slow_books(clock) == ({"decode": 0}, {}, [])
    # and no median until the kind has had its ticks again
    _tick(clock, fake, kind="decode", wait=1.0)
    assert _slow_books(clock)[0] == {"decode": 0}
    assert clock.books()["tick_kind_total"] == {"decode": 1}
