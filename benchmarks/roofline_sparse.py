"""Counting rules of the selecting, routing configuration's kernels,
beside ``roofline.py``: operations and bytes from shapes and counters,
never read from the program. No JAX."""
from typing import Tuple


def visible_and_attended(first: int, n: int, topk: int) -> Tuple[int, int]:
    """Keys the ``n`` queries at positions ``first .. first + n - 1``
    could see (position p sees p + 1) and keys they attended (``topk``
    at most), summed."""
    last = first + n
    visible = (last * (last + 1) - first * (first + 1)) // 2
    lo, hi = min(first, topk), min(last, topk)
    attended = (hi * (hi + 1) - lo * (lo + 1)) // 2 \
        + ((last - hi) - (first - lo)) * topk
    return visible, attended


def moe_grouped(tokens: int, d_model: int, width: int, per_token: int,
                itemsize: int = 2) -> Tuple[float, float]:
    """(flops, least bytes) of one layer's three grouped products (gate,
    up, down of gated experts of ``width``) for one call of ``tokens``
    tokens, each sent to ``per_token`` experts: every assignment is a
    row through three ``d_model x width`` matrices; the rows are read
    and written once (the middle rows twice: written by gate and up,
    read by down), and the call cannot touch fewer experts than one
    token's ``per_token``, whose weights it reads once."""
    rows = tokens * per_token
    flops = 2.0 * 3 * rows * d_model * width
    weights = per_token * 3 * d_model * width * itemsize
    acts = rows * itemsize * (2 * d_model + 3 * width)
    return flops, float(weights + acts)
