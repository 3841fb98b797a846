"""Spans the program records of itself (``ray_tpu.util.tracing``: a
``PhaseClock`` per hot loop, found through ``tracing.clocks()``), read
in the process that ran the loop. The share of ``parent`` spans not
spent inside their ``child`` span, as the median over the clock's ring,
so the compiling first step and the traced steps do not move it. None
where the program keeps no such clock or the ring holds no such span."""
from benchmarks import stats


def read(obs, owner, parent, child):
    try:
        from ray_tpu.util import tracing
        clock = tracing.clocks().get(owner)
    except (ImportError, AttributeError):
        return None            # a program from before the phase clock
    if clock is None:
        return None
    whole, inside = {}, {}
    for name, tick, t0, t1, up in clock.spans():
        if name == parent:
            whole[tick] = t1 - t0
        elif name == child and up == parent:
            inside[tick] = inside.get(tick, 0.0) + (t1 - t0)
    shares = [100.0 * (whole[t] - inside.get(t, 0.0)) / whole[t]
              for t in whole if whole[t] > 0]
    return stats.median(shares) if shares else None
