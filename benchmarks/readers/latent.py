"""What the latent-attention configuration adds to read, from the
reduced trace: the latent kernel's share of device time and of its
roofline in decode and in prefill programs, and the held experts'
grouped products against theirs. The widths are the named configuration
file's (``obs`` carries the attention's head count alone). None where
there is nothing to read (no trace, a rehearsal's CPU trace, a program
without these kernels)."""
import json
import os

from benchmarks import roofline, roofline_latent, spec
from benchmarks.readers.device_trace import _kernel_seconds


def _prefill_least(obs, tr, widths):
    """Least seconds for the prefill chunks inside the traced stretch:
    the window's requests give the work of an average chunk, the
    stretch's own counter how many it ran."""
    m, w = obs["model"], obs["window_s"]
    flops = nbytes = 0.0
    for r in obs["requests"]:
        if 0.0 <= r["due"] < w and r["tokens"]:
            bs = m["kv_block_size"]
            f, b = roofline_latent.latent_prefill(
                r["prompt_len"], (r["shared"] // bs) * bs,
                m["prefill_chunk"], bs, widths, m["itemsize"])
            flops, nbytes = flops + f, nbytes + b
    chunks = obs["engine"]["prefill_chunks"]
    if not chunks:
        return None
    share = tr["engine"]["prefill_chunks"] / chunks
    return m["n_layers"] * share * roofline.min_seconds(
        flops, nbytes, obs["device"]["kind"])


def read(obs, what, kinds=(), module=None, config=None):
    tr = obs.get("trace")
    if not tr or obs["device"]["platform"] != "tpu":
        return None
    spent = _kernel_seconds(tr, kinds, module)
    if not spent:
        return None
    if what == "kernel_share":
        return 100.0 * spent / tr["busy_s"]
    with open(os.path.join(spec.HERE, "configs", config + ".json")) as f:
        widths = json.load(f)["program"]
    m, eng, kind = obs["model"], tr["engine"], obs["device"]["kind"]
    if what == "decode_roofline":
        flops, nbytes = roofline_latent.latent_decode(
            eng["decode_pages_live"] * m["n_layers"], m["kv_block_size"],
            widths, m["itemsize"])
        return 100.0 * roofline.min_seconds(flops, nbytes, kind) / spent
    if what == "prefill_roofline":
        least = _prefill_least(obs, tr, widths)
        return None if least is None else 100.0 * least / spent
    if what == "moe_held_roofline":
        layers = m["n_layers"] - widths["n_dense_layers"]
        least = 0.0
        for calls, tokens in (
                (eng["prefill_chunks"], m["prefill_chunk"]),
                (eng["decode_steps"], obs["engine_config"]["decode_slots"])):
            flops, nbytes = roofline_latent.moe_held(
                tokens, widths, m["itemsize"])
            least += calls * layers * roofline.min_seconds(
                flops, nbytes, kind)
        return 100.0 * least / spent
    raise ValueError(f"unknown quantity {what!r}")
