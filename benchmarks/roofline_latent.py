"""Counting rules of the latent-attention configuration's kernels,
beside ``roofline.py``: operations and bytes from shapes and counters,
never read from the program. No JAX.

A latent cache holds, a token and layer, one row of ``rank + rope``
numbers that every head reads as its key and, in its first ``rank``, as
its value (the absorbed form: the key and value projections are folded
into the query and taken after the softmax). The row counted is the
``rank + rope`` numbers the algorithm needs: the program keeps them in a
row padded to whole lane tiles, and what it reads beyond them shows as a
lower share."""
from typing import Any, Dict, Tuple


def key_cost(widths: Dict[str, Any], itemsize: int = 2
             ) -> Tuple[float, float]:
    """(flops a key and query token, bytes a key) of absorbed latent
    attention: every one of ``n_heads`` query rows takes a ``rank +
    rope`` dot with the key and adds a ``rank``-wide value row; the key
    is read once for all of them. 128 heads at 512 + 64: 278,528 FLOP
    against 1152 B, 242 FLOP/B."""
    rank, rope = widths["kv_lora_rank"], widths["qk_rope_dim"]
    return (2.0 * widths["n_heads"] * (2 * rank + rope),
            float((rank + rope) * itemsize))


def latent_decode(pages: int, block_size: int, widths: Dict[str, Any],
                  itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of decode attention over ``pages`` live latent
    pages summed over sequences, steps and layers (a slot with no
    sequence has no page: the engine counts none for it and the kernel
    fetches none): a page is read once and every head's one query row
    meets each of its keys."""
    flops, nbytes = key_cost(widths, itemsize)
    keys = pages * block_size
    return keys * flops, keys * nbytes


def latent_prefill(prompt_len: int, cached: int, chunk: int,
                   block_size: int, widths: Dict[str, Any],
                   itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) per layer of the chunked prefill of one prompt
    whose first ``cached`` tokens came out of the prefix cache, in the
    absorbed form: the query at position p meets p + 1 keys; a chunk
    reads every page written so far once, its absorbed queries (``rank +
    rope`` a head) once and writes ``rank`` a head."""
    flops, nbytes = key_cost(widths, itemsize)
    rank, rope = widths["kv_lora_rank"], widths["qk_rope_dim"]
    pairs = prompt_len * (prompt_len + 1) // 2 - cached * (cached + 1) // 2
    total, start = 0.0, cached
    while start < prompt_len:
        n = min(chunk, prompt_len - start)
        pages = -(-(start + n) // block_size)
        total += pages * block_size * nbytes
        total += n * widths["n_heads"] * (2 * rank + rope) * itemsize
        start += n
    return pairs * flops, total


def moe_held(tokens: int, widths: Dict[str, Any], itemsize: int = 2
             ) -> Tuple[float, float]:
    """(flops, least bytes) of one layer's three grouped products for a
    call of ``tokens`` tokens, each sent to ``experts_per_token`` of
    ``n_experts`` of which ``experts_held`` are here: the assignments
    that land here are the held share of all of them (the router's
    choices stay on the device; even routing is the expected count),
    each a row through three ``d_model x expert_width`` matrices; the
    rows are read and written once (the middle rows twice), and the
    weights read are those of the held experts that get a row: with
    ``rows`` landing evenly on ``held`` experts, ``held * (1 - (1 -
    1/held) ** rows)`` of them (a chunk's 1024 rows touch all sixteen,
    a decode step's eight rows 6.5)."""
    d, w = widths["d_model"], widths["expert_width"]
    held = widths["experts_held"]
    rows = tokens * widths["experts_per_token"] * held / widths["n_experts"]
    experts = held * (1.0 - (1.0 - 1.0 / held) ** rows)
    flops = 2.0 * 3 * rows * d * w
    nbytes = experts * 3 * d * w * itemsize \
        + rows * itemsize * (2 * d + 3 * w)
    return flops, float(nbytes)
