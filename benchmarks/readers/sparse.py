"""What the selecting, routing configuration adds to read: how much of
what a query could see it attended (from the request records, positions
alone), and from the reduced trace the share and the roofline of the
kernels the trace can name. None where there is nothing to read (no
trace, a rehearsal's CPU trace, a program without these kernels)."""
from benchmarks import roofline, roofline_sparse, stats


def _select_share(obs, topk):
    reqs, w = obs.get("requests"), obs.get("window_s")
    if not reqs or not w:
        return None
    bs = obs["model"]["kv_block_size"]
    visible = attended = 0
    for r in stats.due_in_window(reqs, w):
        if not r["tokens"]:
            continue
        cached = (r["shared"] // bs) * bs
        # prefilled positions, then one decoded query a token but the
        # last (which nothing follows)
        n = r["prompt_len"] - cached + len(r["tokens"]) - 1
        v, a = roofline_sparse.visible_and_attended(cached, n, topk)
        visible, attended = visible + v, attended + a
    return 100.0 * attended / visible if visible else None


def _kernel_seconds(tr, kinds):
    return sum(sec for key, sec in tr["by_module_kind"].items()
               if key.split("|", 1)[1] in kinds) / tr["chips"]


def read(obs, what, topk=None, kinds=()):
    if what == "select_share":
        return _select_share(obs, topk)
    tr = obs.get("trace")
    if not tr or obs["device"]["platform"] != "tpu":
        return None
    spent = _kernel_seconds(tr, kinds)
    if not spent:
        return None
    if what == "kernel_share":
        return 100.0 * spent / tr["busy_s"]
    if what == "moe_roofline":
        # the experts' widths are the cell's own configuration's:
        # obs["model"] carries its ``program`` group whole
        m, eng = obs["model"], tr["engine"]
        cfg = obs["engine_config"]
        least = 0.0
        for calls, tokens in ((eng["prefill_chunks"], m["prefill_chunk"]),
                              (eng["decode_steps"], cfg["decode_slots"])):
            flops, nbytes = roofline_sparse.moe_grouped(
                tokens, m["d_model"], m["expert_width"],
                m["experts_per_token"], m["itemsize"])
            least += calls * m["n_layers"] * roofline.min_seconds(
                flops, nbytes, obs["device"]["kind"])
        return 100.0 * least / spent
    raise ValueError(f"unknown quantity {what!r}")
