"""The paged-attention entries of a stack in which only some layers have
pages (a hybrid: recurrent layers with a row a slot beside full-attention
layers with a page table). ``device_trace``'s ``paged_*_roofline`` and
``kv_write_share`` multiply one page size by ``n_layers`` and take every
large result for a write of the pool; here the layers that have pages are
counted from ``obs["model"]["layer_pattern"]``, a prompt's cached part is
what a hit of a model with recurrent state is cut back to (the engine's
``state_snapshot_stride``), and a write of the pool is an op whose result
is the pool itself. None where there is nothing to read (no trace, a
rehearsal's CPU trace, a stack without a layer pattern, no paged kernel
in the stretch)."""
import math

from benchmarks import roofline, trace as T
from benchmarks.readers.device_trace import _kernel_seconds

#: the kinds of layer that keep a row a slot and no page
NO_PAGES = ("mamba", "delta")


def paged_layers(m) -> int:
    """How many of the stack's ``n_layers`` have pages; 0 without a
    layer pattern (every layer has, and ``device_trace`` reads it)."""
    pattern = m.get("layer_pattern")
    if not pattern:
        return 0
    return sum(pattern[l % len(pattern)] not in NO_PAGES
               for l in range(m["n_layers"]))


def _prefill_least(obs, tr, layers: int):
    """Least seconds for the paged layers' attention in the prefill
    chunks of the traced stretch: the window's requests give the work of
    an average chunk (a prompt's cached part cut back to the snapshot
    stride, strictly short of its last token), the stretch's own counter
    how many chunks it ran."""
    m, w = obs["model"], obs["window_s"]
    cut = obs["engine_config"].get("state_snapshot_stride") \
        or m["kv_block_size"]
    flops = nbytes = 0.0
    for r in obs["requests"]:
        if 0.0 <= r["due"] < w and r["tokens"]:
            cached = min(r["shared"], r["prompt_len"] - 1) // cut * cut
            f, b = roofline.paged_prefill(r["prompt_len"], cached,
                                          m["prefill_chunk"], m)
            flops, nbytes = flops + f, nbytes + b
    chunks = obs["engine"]["prefill_chunks"]
    if not chunks:
        return None
    share = tr["engine"]["prefill_chunks"] / chunks
    return layers * share * roofline.min_seconds(
        flops, nbytes, obs["device"]["kind"])


def read(obs, what, module=None, scopes=()):
    tr = obs.get("trace")
    if not tr or obs["device"]["platform"] != "tpu":
        return None
    m = obs["model"]
    layers = paged_layers(m)
    if not layers:
        return None
    if what == "kv_write_share":
        # ops under these scopes (the rows' index arithmetic, a
        # copy-on-write), and the in-place row scatters, found by their
        # result: the pool whole, every paged layer of it (the compiler
        # names those fusions after the layer scan or not at all, PERF.md
        # section 6, PR 39; the per-slot state and its snapshot rows are
        # other sizes)
        pool = layers * m["num_kv_blocks"] * m["kv_heads"] \
            * m["kv_block_size"] * m["head_dim"]
        sec = sum(tr.get("by_scope", {}).get(s, 0.0) for s in scopes)
        for op in tr["op_calls"].values():
            scope = op.get("scope", "")
            if op["kind"] != "paged_attention" \
                    and math.prod(T.result_shape(op["name"])[1] or (0,)) \
                    == pool and not any(
                        scope == s or scope.startswith(s + "/")
                        for s in scopes):
                sec += op["seconds"] / tr["chips"]
        return 100.0 * sec / tr["busy_s"] if sec else None
    spent = _kernel_seconds(tr, ("paged_attention",), module)
    if not spent:
        return None
    if what == "decode_roofline":
        flops, nbytes = roofline.paged_decode(
            tr["engine"]["decode_pages_live"] * layers, m)
        return 100.0 * roofline.min_seconds(
            flops, nbytes, obs["device"]["kind"]) / spent
    if what == "prefill_roofline":
        least = _prefill_least(obs, tr, layers)
        return None if least is None else 100.0 * least / spent
    raise ValueError(f"unknown quantity {what!r}")
