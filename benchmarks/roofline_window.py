"""Counting rules of the paged kernel over TWO KINDS of layer (full and
sliding-window attention in one stack), beside ``roofline.py``:
operations and bytes from shapes and the engine's counters by kind,
never read from the program. No JAX.

A full layer's call reads every live page of a sequence; a window
layer's reads the pages with a key inside the window of its first
query and no other, and its queries meet ``sliding_window`` keys at
most. The kinds differ in query heads too, so each is counted with its
own: a kernel call belongs to one layer, so the least time of a program
is the sum over kinds of each kind's own larger time."""
from typing import Any, Dict, Tuple

KINDS = ("full", "window")


def layers_by_kind(model: Dict[str, Any]) -> Dict[str, int]:
    """Layers of each kind among the ``n_layers`` run: the pattern
    repeats over the depth (no pattern: every layer full)."""
    pattern = list(model.get("layer_pattern") or ["full"])
    kinds = [pattern[l % len(pattern)] for l in range(model["n_layers"])]
    return {kind: kinds.count(kind) for kind in KINDS}


def heads_by_kind(model: Dict[str, Any]) -> Dict[str, int]:
    return {"full": model["n_heads"],
            "window": model.get("window_heads") or model["n_heads"]}


def page_bytes(model: Dict[str, Any]) -> int:
    """One layer's K and V of one page: 16 x 2 x 8 x 128 x 2 B =
    65,536 B at this configuration's widths."""
    return 2 * model["kv_heads"] * model["kv_block_size"] \
        * model["head_dim"] * model["itemsize"]


def key_flops(heads: int, model: Dict[str, Any]) -> float:
    """FLOPs a query token and key: every head's QK^T and PV row."""
    return 4.0 * heads * model["head_dim"]


def decode(pages: Dict[str, int], model: Dict[str, Any]
           ) -> Dict[str, Tuple[float, float]]:
    """kind -> (flops, bytes) of decode attention: ``pages[kind]`` live
    pages ONE layer of the kind reads, summed over sequences and steps
    (the engine's ``decode_pages_live_<kind>``), times the kind's
    layers; a page is read once for K and once for V by all the heads
    that share it, and the one query token meets each of its keys (a
    window layer's first page may hold keys behind the window: counted,
    an overcount of under one page in thirty-three that moves nothing
    while bytes bind)."""
    layers, heads = layers_by_kind(model), heads_by_kind(model)
    return {kind: (pages[kind] * layers[kind] * model["kv_block_size"]
                   * key_flops(heads[kind], model),
                   float(pages[kind] * layers[kind] * page_bytes(model)))
            for kind in KINDS}


def prefill(pages: Dict[str, int], keys: Dict[str, int], tokens: int,
            model: Dict[str, Any]) -> Dict[str, Tuple[float, float]]:
    """kind -> (flops, bytes) of the chunks' attention: ``keys[kind]``
    keys the chunks' queries meet in one layer of the kind (position p:
    p + 1, a window layer ``sliding_window`` at most; the engine's
    ``prefill_keys_live_<kind>``), ``pages[kind]`` pages one such layer
    reads (``prefill_pages_live_<kind>``), ``tokens`` query tokens
    whose rows go in and come out once a layer."""
    layers, heads = layers_by_kind(model), heads_by_kind(model)
    out = {}
    for kind in KINDS:
        rows = 2 * tokens * heads[kind] * model["head_dim"] \
            * model["itemsize"]
        out[kind] = (layers[kind] * keys[kind]
                     * key_flops(heads[kind], model),
                     float(layers[kind]
                           * (pages[kind] * page_bytes(model) + rows)))
    return out
