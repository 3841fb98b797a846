"""What the latent-expert configuration adds to read, from the reduced
trace: the least time for the traced stretch's grouped expert products
(``roofline_latent_moe.grouped``: assignments and calls from the engine's
counters over that stretch, widths from ``obs["model"]``) over the device
time in them, XLA's ``ragged-dot`` kernels by op name (the compiler keeps
no scope path on them, so ``moe_experts`` cannot find them; every grouped
product of such a program is the experts'), in the decode or in the chunk
programs. None where there is nothing to read (no trace, a rehearsal's
CPU trace, a program without these kernels or counters, a stretch without
such a call)."""
from benchmarks import roofline, roofline_latent_moe

#: how the engine's counters and programs name the two kinds of call
CALLS = {"decode": "decode_steps", "prefill": "prefill_chunks"}


def _seconds(tr, module: str) -> float:
    """Seconds a chip in grouped products inside programs whose name
    contains ``module``."""
    return sum(sec for key, sec in tr["by_module_kind"].items()
               if module in key.split("|", 1)[0]
               and key.split("|", 1)[1].startswith("ragged-dot")) \
        / tr["chips"]


def read(obs, what):
    tr = obs.get("trace")
    if not tr or obs["device"]["platform"] != "tpu":
        return None
    m, eng = obs["model"], tr.get("engine") or {}
    layers = (obs.get("engine_end") or {}).get("ffn_layers")
    kind = {"gmm_decode_roofline": "decode",
            "gmm_prefill_roofline": "prefill"}.get(what)
    if kind is None:
        raise ValueError(f"unknown quantity {what!r}")
    counter = f"moe_{kind}_assignments_total"
    if not m.get("moe_latent") or not layers or counter not in eng:
        return None
    spent = _seconds(tr, kind)
    if not spent:
        return None
    flops, nbytes = roofline_latent_moe.grouped(
        eng[counter], eng[CALLS[kind]] * layers, m, m["itemsize"])
    return 100.0 * roofline.min_seconds(
        flops, nbytes, obs["device"]["kind"]) / spent
