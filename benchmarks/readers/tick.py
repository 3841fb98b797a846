"""The tick's own account of itself (``LLMEngine.stats()``: the
``PhaseClock``'s ``phases``, ``tick_wall_s``, the programs launched
ahead), differenced over the window and over the traced stretch by
``serve_cell.counters_delta``. None where the program keeps no such
books or the stretch saw no such phase."""


def _mean_s(eng, phase):
    p = ((eng or {}).get("phases") or {}).get(phase)
    return p["seconds"] / p["count"] if p and p["count"] else None


def read(obs, what, phase=None, waits=()):
    eng = obs.get("engine") or {}
    if what == "host_ms":
        # a tick's time outside the phases that wait for the device
        phases = eng.get("phases") or {}
        ticks = (phases.get("engine.tick") or {}).get("count")
        if not ticks or "tick_wall_s" not in eng:
            return None
        waited = sum(phases[w]["seconds"] for w in waits if w in phases)
        return 1e3 * (eng["tick_wall_s"] - waited) / ticks
    if what == "ahead_share":
        if "programs_ahead_total" not in eng:
            return None
        programs = eng.get("prefill_chunks", 0) + eng.get("decode_steps", 0)
        return 100.0 * eng["programs_ahead_total"] / programs \
            if programs else None
    if what == "profiler_stretch":
        # how much longer the phase reads under the profiler: its mean
        # over the traced stretch over its mean in the untraced window
        traced = _mean_s((obs.get("trace") or {}).get("engine"), phase)
        plain = _mean_s(eng, phase)
        return traced / plain if traced and plain else None
    raise ValueError(f"unknown quantity {what!r}")
