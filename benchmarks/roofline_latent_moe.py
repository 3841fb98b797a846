"""Counting rule of the latent experts' grouped products, beside
``roofline.py``: operations and bytes from shapes and the engine's
counters, never read from the program, and the same whatever implements
the products. No JAX.

An ungated expert in a latent is two matrices, ``latent x width`` and
``width x latent``. An assignment (a token sent to an expert) that lands
on a held expert is a latent row through both; a call reads the weights
of the held experts that get a row."""
from typing import Any, Dict, Tuple


def expert_bytes(widths: Dict[str, Any], itemsize: int = 2) -> float:
    """Bytes of one routed expert's two matrices (5.5 MB at 1024 x 2688,
    bf16)."""
    return 2.0 * widths["moe_latent"] * widths["expert_width"] * itemsize


def experts_met(rows: float, held: int) -> float:
    """Held experts that get a row when ``rows`` assignments land evenly
    on ``held``: ``held (1 - (1 - 1/held)^rows)`` (the router's choices
    stay on the device; even routing is the expected count, as
    ``roofline_latent.moe_held`` reckons it)."""
    return held * (1.0 - (1.0 - 1.0 / held) ** rows)


def grouped(assignments: float, calls: float, widths: Dict[str, Any],
            itemsize: int = 2) -> Tuple[float, float]:
    """(flops, least bytes) of the two grouped products of ``calls``
    (call, expert layer) pairs that ``assignments`` assignments were made
    in, over ALL ``n_experts``: the held share of them lands here, each
    ``2 x 2 x latent x width`` FLOP, reading a latent row and writing
    one; a (call, layer) reads the weights of the held experts that get a
    row of its even share."""
    if not calls:
        return 0.0, 0.0
    held, r = widths["experts_held"], widths["moe_latent"]
    landed = assignments * held / widths["n_experts"]
    flops = landed * 2.0 * 2 * r * widths["expert_width"]
    nbytes = landed * 2.0 * r * itemsize \
        + calls * experts_met(landed / calls, held) \
        * expert_bytes(widths, itemsize)
    return flops, float(nbytes)
