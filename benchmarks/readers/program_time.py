"""The engine's own timing of its programs on the device
(``LLMEngine.stats()``: each step program's wall booked by class, from
the engine's fetches alone, no profiler): ``*_device_*`` the programs
launched behind a running one, fetch to fetch with both fetches
blocking, so their time on the chip; ``*_serial_*`` those launched with
none out, launch + wait; ``fetch_found_ready_total`` the fetches whose
result was there before the host asked. Differenced over the window by
``serve_cell.counters_delta``. None where the program keeps no such
books or the window held no sample of a class."""


def _mean_ms(eng, kind, cls, unit):
    n = eng.get(f"{kind}_{cls}_{unit}")
    return 1e3 * eng[f"{kind}_{cls}_s"] / n if n else None


def read(obs, what):
    eng = obs.get("engine") or {}
    if what == "decode_exposed_ms":
        # what a decode step launched with nothing out costs beyond a
        # decode step's time on the chip
        serial = _mean_ms(eng, "decode", "serial", "steps")
        device = _mean_ms(eng, "decode", "device", "steps")
        if serial is None or device is None:
            return None
        return serial - device
    if what == "found_ready_share":
        found = eng.get("fetch_found_ready_total")
        programs = eng.get("h2d_transfers_total")    # one a step program
        if found is None or not programs:
            return None
        return 100.0 * sum(found.values()) / programs
    raise ValueError(f"unknown quantity {what!r}")
