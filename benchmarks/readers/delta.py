"""What the delta-rule configuration adds to read, from the reduced
trace: the least time for the traced stretch's recurrence (rows, tokens,
calls and snapshots from the engine's counters over that stretch, widths
from ``obs["model"]``) over the device time under the scan's and the
convolution's scopes in the decode or in the prefill programs. None
where there is nothing to read (no trace, a rehearsal's CPU trace, a
program without these scopes or counters)."""
from benchmarks import roofline, roofline_delta

#: the scopes whose ops are the recurrence's (``layer/delta`` has the
#: projections and the gated norm beside them)
SCOPES = ("layer/delta/delta_scan", "layer/delta/delta_conv")


def _under(scope: str) -> bool:
    return any(scope == s or scope.startswith(s + "/") for s in SCOPES)


def _seconds(tr, module: str) -> float:
    """Seconds a chip in ops under ``SCOPES`` inside programs whose name
    contains ``module``."""
    return sum(op["seconds"] for op in tr["op_calls"].values()
               if module in op["module"] and _under(op["scope"])) \
        / tr["chips"]


def read(obs, what):
    tr = obs.get("trace")
    if not tr or obs["device"]["platform"] != "tpu":
        return None
    m, eng = obs["model"], tr.get("engine") or {}
    if "delta_heads" not in m or "delta_decode_rows_total" not in eng:
        return None
    layers = roofline_delta.delta_layers(m, m["n_layers"])
    if what == "decode_roofline":
        spent = _seconds(tr, "decode")
        flops, nbytes = roofline_delta.scan_decode(
            eng["delta_decode_rows_total"] * layers, m, m["itemsize"])
    elif what == "prefill_roofline":
        spent = _seconds(tr, "prefill")
        flops, nbytes = roofline_delta.scan_prefill(
            eng["delta_prefill_tokens_total"] * layers,
            eng["delta_prefill_calls_total"] * layers,
            eng.get("state_snapshots_taken_total", 0) * layers, m,
            m["itemsize"])
    else:
        raise ValueError(f"unknown quantity {what!r}")
    if not spent:
        return None
    return 100.0 * roofline.min_seconds(
        flops, nbytes, obs["device"]["kind"]) / spent
