"""Counting rules of the delta-rule configuration's recurrence, beside
``roofline.py``: operations and bytes from shapes and the engine's
counters, never read from the program, and the same whatever implements
it. No JAX.

A "delta" layer keeps, a sequence, a state of ``heads x key_dim x
value_dim`` float32 numbers and the convolution's last ``conv - 1`` raw
inputs (``heads x (2 key_dim + value_dim)`` wide, the compute dtype).
What is counted is the recurrence and the convolution ahead of it (the
scopes ``delta_scan`` and ``delta_conv``), not the projections around
them."""
from typing import Any, Dict, Tuple

#: the published blocked form's block
BLOCK = 64


def _sizes(widths: Dict[str, Any]) -> Tuple[int, int, int, int]:
    h, dk, dv = widths["delta_heads"], widths["delta_key_dim"], \
        widths["delta_value_dim"]
    return h, dk, dv, h * (2 * dk + dv)    # ... and the convolution's width


def state_bytes(widths: Dict[str, Any], itemsize: int = 2) -> float:
    """Bytes of one sequence's state in one layer, and of one snapshot
    row's: the float32 state and the convolution's tail."""
    h, dk, dv, cw = _sizes(widths)
    return float(h * dk * dv * 4
                 + (widths["delta_conv"] - 1) * cw * itemsize)


def delta_layers(widths: Dict[str, Any], n_layers: int) -> int:
    pattern = widths["layer_pattern"]
    return sum(pattern[l % len(pattern)] == "delta" for l in range(n_layers))


def scan_decode(rows: int, widths: Dict[str, Any], itemsize: int = 2
                ) -> Tuple[float, float]:
    """(flops, bytes) of the one-token update of ``rows`` (sequence,
    layer) pairs: a row reads and writes its state and tail once and does
    7 operations a state element (the decay's product, ``S^T k``'s two,
    the outer product's two, ``S^T q``'s two)."""
    h, dk, dv, _ = _sizes(widths)
    return rows * 7.0 * h * dk * dv, rows * 2.0 * state_bytes(widths,
                                                              itemsize)


def scan_prefill(tokens: int, calls: int, snapshots: int,
                 widths: Dict[str, Any], itemsize: int = 2
                 ) -> Tuple[float, float]:
    """(flops, bytes) of the blocked delta rule over ``tokens`` live
    (token, layer) pairs in ``calls`` (sequence-call, layer) pairs that
    wrote ``snapshots`` (snapshot, layer) rows: the published blocked
    form's count at block ``L`` = 64, a token and head: ``Q K^T`` and ``K
    K^T`` (2 x 2 x L x key_dim), the triangular solve applied to ``K``
    and ``V`` (2 x L x (key_dim + value_dim)), ``W S_0`` and ``Q S_0`` (2
    x 2 x key_dim x value_dim), ``tril(.) V'`` (2 x L x value_dim), the
    state's update (2 x key_dim x value_dim). A token's inputs are read
    and its output written once (the convolution's width, a decay and a
    write strength a head, the heads' values), the state and the tail
    read and written once a call, and a snapshot written once."""
    h, dk, dv, cw = _sizes(widths)
    L = BLOCK
    per_head = 4.0 * L * dk + 2.0 * L * (dk + dv) + 4.0 * dk * dv \
        + 2.0 * L * dv + 2.0 * dk * dv
    nbytes = tokens * (cw + 2 * h + h * dv) * itemsize \
        + (2.0 * calls + snapshots) * state_bytes(widths, itemsize)
    return tokens * h * per_head, float(nbytes)
