"""The request view of a serving run: tails and rates over the records
the load generator kept (``benchmarks/stats.py`` has the arithmetic)."""
from benchmarks import stats


def read(obs, stat, q=None):
    reqs, w = obs.get("requests"), obs.get("window_s")
    if not reqs or not w:
        return None
    listen = obs.get("listen_s", w)
    if stat == "ttft_slow10_ms":
        return stats.slowest_tenth_mean(stats.ttfts_ms(reqs, w, listen))
    if stat == "ttft_pct_ms":
        return stats.percentile(stats.ttfts_ms(reqs, w, listen), q)
    if stat == "tpot_pct_ms":
        return stats.percentile(stats.tpots_ms(reqs, w), q)
    if stat == "tokens_per_s":
        return stats.tokens_in_window(reqs, w) / w
    if stat == "completed_tokens_per_s":
        return stats.completed_tokens(reqs, w) / w
    if stat == "late_pct_ms":
        # how late the generator sent, against the schedule
        return stats.percentile(
            [1e3 * (r["sent"] - r["due"])
             for r in stats.due_in_window(reqs, w)], q)
    if stat == "fleet_overhead_p50_ms":
        # median client TTFT (from the send) minus the median of the
        # replica's own arrival-to-first-token time
        client = [1e3 * (r["tokens"][0] - r["sent"])
                  for r in stats.due_in_window(reqs, w)
                  if r["tokens"]]
        engine = [1e3 * s for s in obs.get("engine_ttft_s") or []]
        if not client or not engine:
            return None
        return stats.median(client) - stats.median(engine)
    raise ValueError(f"unknown request statistic {stat!r}")
