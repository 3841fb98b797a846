"""The tick's books by kind (``ray_tpu.util.tracing.PhaseClock.books``):
what each tick fetched (``idle``, ``decode``, ``full``, ``part``,
``full_decode``, ``part_decode``, the ``_verify`` forms under
speculation; a training step ``step``, or ``compile`` where it compiled
its program), and under each kind the ticks, their seconds, the seconds
of their ``*.wait`` phases, their part of ``host_gap_s``, a histogram of
their lengths (``tick_hist_<kind>``: a bucket's lower edge in seconds ->
ticks, eight buckets an octave), and the overruns of the slow ones by
the phase that held them (``tick_slow_s``). A serving cell's are
``LLMEngine.stats()`` keys, differenced over the window by
``serve_cell.counters_delta``; a training cell's are read off the loop's
clock (``owner``) in the process that ran it, whole. None where the
program keeps no such books or the stretch met no such tick."""

#: the histograms' buckets an octave (``tracing.HIST_PER_OCTAVE``; kept
#: here too: the reader has to load beside a program without the books)
PER_OCTAVE = 8


def _books(obs, owner):
    if owner is None:
        return obs.get("engine") or {}
    try:
        from ray_tpu.util import tracing
        return tracing.clocks()[owner].books()
    except (ImportError, AttributeError, KeyError):
        return {}               # a program from before the books


def _sum(books, key, kinds):
    by_kind = books.get(key) or {}
    return sum(by_kind.get(k, 0) for k in kinds)


def quantile(hist, q):
    """Seconds below which ``q`` of a histogram's ticks lie, placed
    inside its bucket by rank (geometrically: a bucket is a ratio wide);
    None for an empty one."""
    rank, below = q * sum(hist.values()), 0
    for edge, n in sorted(hist.items()):
        if n > 0 and below + n >= rank:
            return float(edge) * 2.0 ** ((rank - below) / n / PER_OCTAVE)
        below += n
    return None


def read(obs, what, kinds=(), q=None, owner=None):
    books = _books(obs, owner)
    if "tick_kind_total" not in books:
        return None
    if what == "slow_share":
        # the stretch's seconds lost to slow ticks: overruns over all
        wall = books.get("tick_wall_s")
        return 100.0 * sum(books["tick_slow_s"].values()) / wall \
            if wall else None
    if what == "share":
        # the kinds' seconds over all ticks' seconds: the mix
        wall = sum(books["tick_kind_s"].values())
        return 100.0 * _sum(books, "tick_kind_s", kinds) / wall \
            if wall else None
    if what == "quantile_ms":
        merged = {}
        for kind in kinds:
            for edge, n in (books.get(f"tick_hist_{kind}") or {}).items():
                merged[edge] = merged.get(edge, 0) + n
        value = quantile(merged, q)
        return None if value is None else 1e3 * value
    ticks = _sum(books, "tick_kind_total", kinds)
    if not ticks:
        return None
    if what == "mean_ms":
        seconds = _sum(books, "tick_kind_s", kinds)
    elif what == "host_ms":
        # a tick's time outside the phases that wait for the device
        seconds = _sum(books, "tick_kind_s", kinds) \
            - _sum(books, "tick_kind_wait_s", kinds)
    elif what == "gap_ms":
        # a tick's seconds with no program out
        seconds = _sum(books, "tick_kind_gap_s", kinds)
    else:
        raise ValueError(f"unknown quantity {what!r}")
    return 1e3 * seconds / ticks
