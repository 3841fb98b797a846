"""What the configuration with window and full attention layers adds to
read from the reduced trace: the paged kernel's share of its roofline
in decode and in prefill programs, counted BY KIND of layer from the
engine's own counters over the traced stretch (``roofline_window.py``).
None where there is nothing to read: no trace, a rehearsal's CPU trace,
a program without the kernel or without the counters by kind."""
from benchmarks import roofline, roofline_window
from benchmarks.readers.device_trace import _kernel_seconds


def read(obs, what, kinds=("paged_attention",), module=None):
    tr = obs.get("trace")
    if not tr or obs["device"]["platform"] != "tpu":
        return None
    spent = _kernel_seconds(tr, kinds, module)
    eng, m = tr.get("engine") or {}, obs["model"]
    if what not in ("decode_roofline", "prefill_roofline"):
        raise ValueError(f"unknown quantity {what!r}")
    stem = what.split("_")[0]
    names = {kind: f"{stem}_pages_live_{kind}"
             for kind in roofline_window.KINDS}
    if not spent or any(n not in eng for n in names.values()):
        return None
    pages = {kind: eng[n] for kind, n in names.items()}
    if what == "decode_roofline":
        work = roofline_window.decode(pages, m)
    else:
        keys = {kind: eng[f"prefill_keys_live_{kind}"]
                for kind in roofline_window.KINDS}
        work = roofline_window.prefill(
            pages, keys, eng["prefill_chunks"] * m["prefill_chunk"], m)
    least = sum(roofline.min_seconds(f, b, obs["device"]["kind"])
                for f, b in work.values())
    return 100.0 * least / spent
