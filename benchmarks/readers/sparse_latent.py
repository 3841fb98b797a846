"""What a key selection over a latent cache adds to read: the share of
its roofline that the selecting path reaches in decode and in prefill
programs, from the engine's by-kind counters over the traced stretch and
the reduced trace's ops under the selection's scopes. None where there
is nothing to read (no trace, a rehearsal's CPU trace, a program without
these counters or scopes, as the parent)."""
from benchmarks import roofline, roofline_sparse_latent


def _scope_seconds(tr, module, scopes):
    """Seconds a chip in ops of programs whose name contains ``module``
    under one of the ``scopes`` (or deeper)."""
    return sum(
        op["seconds"] for op in tr["op_calls"].values()
        if module in op["module"] and any(
            op["scope"] == s or op["scope"].startswith(s + "/")
            for s in scopes)) / tr["chips"]


def read(obs, what, module, scopes):
    tr = obs.get("trace")
    if not tr or obs["device"]["platform"] != "tpu":
        return None
    if what not in ("decode_roofline", "prefill_roofline"):
        raise ValueError(f"unknown quantity {what!r}")
    m, eng = obs["model"], tr["engine"]
    scored = eng.get(f"indexer_keys_scored_{module}_total")
    attended = eng.get(f"keys_attended_{module}_total")
    spent = _scope_seconds(tr, module, scopes)
    if not scored or attended is None or not spent:
        return None
    flops, nbytes = roofline_sparse_latent.selected_work(
        scored, attended * m["n_layers"],
        1 if module == "decode" else m["prefill_chunk"], m, m["itemsize"])
    return 100.0 * roofline.min_seconds(
        flops, nbytes, obs["device"]["kind"]) / spent
