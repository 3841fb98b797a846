"""Training step times: the window's rate (all tokens over all time),
the median block, the median step, and model-FLOP utilization."""
from benchmarks import roofline, stats


def read(obs, stat):
    tr = obs.get("train")
    if not tr:
        return None
    ends, tps = tr["step_ends"], tr["tokens_per_step"]
    if stat == "window_tokens_per_s":
        return stats.window_tokens_per_s(ends, tps)
    if stat == "block_median_tokens_per_s":
        return stats.median(stats.block_tokens_per_s(
            ends, tps, tr["block_steps"]))
    if stat == "step_ms":
        return 1e3 * stats.median(stats.step_times(ends))
    if stat == "mfu":
        if obs["device"]["platform"] != "tpu":
            return None            # a rehearsal has no peak to hold it to
        rate = stats.window_tokens_per_s(ends, tps)
        per_token = roofline.train_flops_per_token(
            tr["matmul_params"], tr["n_layers"], tr["n_heads"],
            tr["head_dim"], tr["seq"])
        peak = roofline.peaks(obs["device"]["kind"])["bf16_flops"]
        return 100.0 * rate * per_token / (tr["chips"] * peak)
    raise ValueError(f"unknown training statistic {stat!r}")
