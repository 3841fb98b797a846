"""Counting rules of the state-space configuration's recurrence, beside
``roofline.py``: operations and bytes from shapes and the engine's
counters, never read from the program. No JAX.

A "mamba" layer keeps, a sequence, a state of ``heads x head_dim x
state`` float32 numbers and the convolution's last ``conv - 1`` inputs
(``heads x head_dim + 2 x state`` wide, the compute dtype). What is
counted is the recurrence and the convolution ahead of it (the scopes
``ssm_scan`` and ``ssm_conv``), not the projections around them."""
from typing import Any, Dict, Tuple


def _sizes(widths: Dict[str, Any]) -> Tuple[int, int, int, int]:
    h, p, n = widths["ssm_heads"], widths["ssm_head_dim"], \
        widths["ssm_state"]
    return h, p, n, h * p + 2 * n          # ... and the convolution's width


def state_bytes(widths: Dict[str, Any], itemsize: int = 2) -> float:
    """Bytes of one sequence's state in one layer: the float32 state and
    the convolution's tail."""
    h, p, n, cw = _sizes(widths)
    return float(h * p * n * 4 + (widths["ssm_conv"] - 1) * cw * itemsize)


def mamba_layers(widths: Dict[str, Any], n_layers: int) -> int:
    pattern = widths["layer_pattern"]
    return sum(pattern[l % len(pattern)] == "mamba" for l in range(n_layers))


def scan_decode(rows: int, widths: Dict[str, Any], itemsize: int = 2
                ) -> Tuple[float, float]:
    """(flops, bytes) of the one-token update of ``rows`` (sequence,
    layer) pairs: a row reads and writes its state once (2 x 4 MiB and
    the tail twice at the published widths) and does 5 operations a
    state element (the decay's product, the outer product's two and its
    addition, the output's product; the output's sum rides on it)."""
    h, p, n, _ = _sizes(widths)
    return rows * 5.0 * h * p * n, rows * 2.0 * state_bytes(widths, itemsize)


def scan_prefill(tokens: int, calls: int, widths: Dict[str, Any],
                 itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of the blocked scan over ``tokens`` live (token,
    layer) pairs in ``calls`` (sequence-call, layer) pairs, the
    published blocked form's count at block ``ssm_chunk``: a token and
    head meets its block's ``ssm_chunk`` tokens (2 x chunk x head_dim),
    goes into the state and comes out of it (2 x 2 x head_dim x state);
    the group's ``C B^T`` once a token (2 x chunk x state). A token's
    inputs are read and its output written once (the convolution's
    width, the heads' steps, the channels), the state and the tail read
    and written once a call."""
    h, p, n, cw = _sizes(widths)
    q = widths["ssm_chunk"]
    flops = tokens * (h * (2.0 * q * p + 4.0 * p * n) + 2.0 * q * n)
    nbytes = tokens * (cw + h + h * p) * itemsize \
        + calls * 2.0 * state_bytes(widths, itemsize)
    return flops, float(nbytes)
