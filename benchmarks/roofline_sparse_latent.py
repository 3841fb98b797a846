"""Counting rules of a learned key selection over a latent cache, beside
``roofline.py``: operations and bytes of the SELECTED work from shapes
and the engine's counters, never read from the program, and whatever
implements it. No JAX.

An indexer of ``index_heads`` heads of ``index_dim`` scores every key a
query may see: a (query, key) pair is ``index_heads`` dots of
``index_dim``, and the key read is its one shared index key. The query
then attends the keys selected: a pair is, for each of ``n_heads``
heads, a dot of ``rank + rope`` with the latent row and a ``rank``-wide
value row added (the absorbed form of ``roofline_latent.key_cost``), and
the key read is the ``rank + rope`` numbers of its latent row. A
program reads a key once for all of its queries that use it."""
from typing import Any, Dict, Tuple


def pair_costs(widths: Dict[str, Any], itemsize: int = 2
               ) -> Dict[str, Tuple[float, float]]:
    """(flops a (query, key) pair, bytes a key) of scoring and of
    attending. 64 x 128 indexer, 64 heads over 512 + 64: 16,384 FLOP and
    256 B scored, 139,264 FLOP and 1152 B attended."""
    rank, rope = widths["kv_lora_rank"], widths["qk_rope_dim"]
    return {
        "scored": (2.0 * widths["index_heads"] * widths["index_dim"],
                   float(widths["index_dim"] * itemsize)),
        "attended": (2.0 * widths["n_heads"] * (2 * rank + rope),
                     float((rank + rope) * itemsize))}


def selected_work(scored: float, attended: float, readers: int,
                  widths: Dict[str, Any], itemsize: int = 2
                  ) -> Tuple[float, float]:
    """(flops, least bytes) of ``scored`` scored and ``attended``
    attended (query, key) pairs, summed over layers, in programs in
    which at most ``readers`` queries share one read of a key: 1 in a
    decode step (each query has a sequence of its own), the chunk in a
    prefill program."""
    cost = pair_costs(widths, itemsize)
    pairs = {"scored": scored, "attended": attended}
    return (sum(pairs[k] * cost[k][0] for k in pairs),
            sum(pairs[k] * cost[k][1] for k in pairs) / max(1, readers))
