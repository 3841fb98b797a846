"""Numbers from the reduced profiler trace (``benchmarks/trace.py``):
idle share and the part of it inside the program's own annotations, a
kernel's share of device time and of its roofline, the share of the ops
under named scopes and of those that write the KV pool, exposed
collectives. All None without a trace."""
import math

from benchmarks import roofline, trace as T


def _kernel_seconds(tr, kinds, module=None):
    """Seconds per chip in ops of these kinds (inside programs whose
    name contains ``module``)."""
    total = 0.0
    for key, sec in tr["by_module_kind"].items():
        mod, kind = key.split("|", 1)
        if kind in kinds and (module is None or module in mod):
            total += sec
    return total / tr["chips"]


def _flash(obs, tr):
    """(min seconds, kernel seconds) per chip over every flash call."""
    least = spent = 0.0
    for op in tr["op_calls"].values():
        if op["kind"] in roofline.FLASH_MATMULS:
            flops, nbytes = roofline.flash_call(
                op["kind"], T.result_shape(op["name"])[1])
            least += op["calls"] * roofline.min_seconds(
                flops, nbytes, obs["device"]["kind"])
            spent += op["seconds"]
    return least / tr["chips"], spent / tr["chips"]


def _paged_prefill_least(obs, tr):
    """Least seconds for the prefill chunks inside the traced stretch:
    the whole window's requests give the work of an average chunk, the
    stretch's own counter gives how many chunks it ran."""
    m, w = obs["model"], obs["window_s"]
    flops = nbytes = 0.0
    for r in obs["requests"]:
        if 0.0 <= r["due"] < w and r["tokens"]:
            cached = (r["shared"] // m["kv_block_size"]) * m["kv_block_size"]
            f, b = roofline.paged_prefill(r["prompt_len"], cached,
                                          m["prefill_chunk"], m)
            flops, nbytes = flops + f, nbytes + b
    chunks = obs["engine"]["prefill_chunks"]
    if not chunks:
        return None
    share = tr["engine"]["prefill_chunks"] / chunks
    return m["n_layers"] * share * roofline.min_seconds(
        flops, nbytes, obs["device"]["kind"])


def read(obs, what, module=None, scopes=()):
    tr = obs.get("trace")
    if not tr or obs["device"]["platform"] != "tpu":
        return None            # no trace, or a rehearsal's CPU trace
    window, busy = tr["window_s"], tr["busy_s"]
    if what == "idle_share":
        return 100.0 * (1.0 - busy / window)
    if what == "idle_in_tick_share":
        # of the idle seconds, those under any annotation of the thread
        # that drives the device; None where it wrote none
        by_phase = tr.get("idle_by_phase") or {}
        idle = sum(by_phase.values())
        if not idle or set(by_phase) <= {T.OUTSIDE}:
            return None
        return 100.0 * (1.0 - tr["idle_outside_tick_s"] / idle)
    if what == "scope_share":
        # busy time under these scope paths (none inside another)
        found = [tr["by_scope"][s] for s in scopes
                 if s in tr.get("by_scope", {})]
        return 100.0 * sum(found) / busy if found else None
    if what == "exposed_collective_share":
        return 100.0 * tr["exposed_collective_s"] / window
    if what == "flash_share":
        return 100.0 * _flash(obs, tr)[1] / busy
    if what == "flash_roofline":
        least, spent = _flash(obs, tr)
        return 100.0 * least / spent if spent else None
    if what == "paged_share":
        return 100.0 * _kernel_seconds(tr, ("paged_attention",)) / busy
    if what == "kv_write_share":
        # ops that write the KV pool: under these scopes (the rows'
        # index arithmetic, a copy-on-write), or with a result of at
        # least one layer of the pool. The in-place row scatters are
        # found by that size alone: the compiler hands them the layer
        # scan's own name (``jit(_decode_fn)/while``), not the scope
        # they were written under (PERF.md section 6, PR 39)
        m = obs["model"]
        layer = m["num_kv_blocks"] * m["kv_heads"] * m["kv_block_size"] \
            * m["head_dim"]
        sec = sum(tr.get("by_scope", {}).get(s, 0.0) for s in scopes)
        for op in tr["op_calls"].values():
            dims = T.result_shape(op["name"])[1]
            scope = op.get("scope", "")
            if op["kind"] != "paged_attention" and dims \
                    and math.prod(dims) >= layer and not any(
                        scope == s or scope.startswith(s + "/")
                        for s in scopes):
                sec += op["seconds"] / tr["chips"]
        return 100.0 * sec / busy if sec else None
    spent = _kernel_seconds(tr, ("paged_attention",), module)
    if not spent:
        return None
    if what == "paged_decode_roofline":
        flops, nbytes = roofline.paged_decode(
            tr["engine"]["decode_pages_live"] * obs["model"]["n_layers"],
            obs["model"])
        return 100.0 * roofline.min_seconds(
            flops, nbytes, obs["device"]["kind"]) / spent
    if what == "paged_prefill_roofline":
        least = _paged_prefill_least(obs, tr)
        return None if least is None else 100.0 * least / spent
    raise ValueError(f"unknown trace quantity {what!r}")
