"""Counting rules of a TRAINED stack of window and full attention layers
over dropless routed experts, of which the program holds a share, beside
``roofline.py``: operations and bytes from the configuration's sizes and
the call's shapes, never read from the program. No JAX.

``sizes`` is a configuration file's ``program`` group (``d_model``,
``n_heads``, ``head_dim``, ``n_kv_heads``, ``vocab_size``,
``layer_pattern``, ``sliding_window``, ``n_experts``, ``experts_held``,
``experts_per_token``, ``expert_width``) with ``n_layers``, the depth run.
"""
from typing import Any, Dict, Tuple

from benchmarks import roofline

#: the windowed flash calls by kind, counted by ``roofline.FLASH_MATMULS``
#: / ``FLASH_TENSORS`` under the accepted kernels' names
WINDOW_KINDS = {"flash_window_fwd": "flash_fwd",
                "flash_window_bwd_dkdv": "flash_bwd_dkdv",
                "flash_window_bwd_dq": "flash_bwd_dq",
                "flash_window_bwd_delta": "flash_bwd_delta"}
#: grouped products of one expert layer in a training step: gate, up and
#: down forward; dX and dW of each backward. Recomputation is not counted.
PRODUCTS_FORWARD, PRODUCTS_BACKWARD = 3, 6


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of a sequence of ``seq`` under ``query - window
    < key <= query``: the first ``window`` queries see 1, 2, .. ``window``
    keys, every later one ``window``: ``W (W + 1) / 2 + (s - W) W``. No
    window, or one as long as the sequence: the causal triangle."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layers_by_kind(sizes: Dict[str, Any]) -> Dict[str, int]:
    pattern = list(sizes.get("layer_pattern") or ["full"])
    kinds = [pattern[l % len(pattern)] for l in range(sizes["n_layers"])]
    return {kind: kinds.count(kind) for kind in ("full", "window")}


def expert_params(sizes: Dict[str, Any]) -> int:
    """One SwiGLU expert's three matrices."""
    return 3 * sizes["d_model"] * sizes["expert_width"]


def assignments_here(tokens: float, sizes: Dict[str, Any]) -> float:
    """Of ``tokens`` tokens' ``experts_per_token`` assignments each, those
    an even router lands on the held share (the router's choices stay on
    the device and no program counter reaches ``obs``: the expected
    count, which uniform token ids over seeded weights come close to)."""
    held = sizes.get("experts_held") or sizes["n_experts"]
    return tokens * sizes["experts_per_token"] * held / sizes["n_experts"]


def train_flops_per_token(sizes: Dict[str, Any], seq: int) -> float:
    """Model FLOPs a token of a training step, forward and backward: 6 a
    matmul parameter MET (attention projections, the router, of the held
    experts the ``experts_per_token x held / n_experts`` a token meets,
    the head; the embedding is a lookup), and the scores, 12 x heads x
    head_dim x the mean keys a query meets in a layer of each kind.
    Recomputation (remat, flash's S in the backward) is not counted."""
    e, hd = sizes["d_model"], sizes["n_heads"] * sizes["head_dim"]
    kv = (sizes.get("n_kv_heads") or sizes["n_heads"]) * sizes["head_dim"]
    attention = 2 * e * hd + 2 * e * kv
    router = e * sizes["n_experts"]
    met = assignments_here(1.0, sizes) * expert_params(sizes)
    layers = layers_by_kind(sizes)
    scores = sum(
        n * 12.0 * hd * window_pairs(
            seq, sizes["sliding_window"] if kind == "window" else 0) / seq
        for kind, n in layers.items())
    return 6.0 * (sizes["n_layers"] * (attention + router + met)
                  + e * sizes["vocab_size"]) + scores


def expert_products(rows: float, sizes: Dict[str, Any], itemsize: int = 2
                    ) -> Tuple[float, float]:
    """(flops, least bytes) of ONE expert layer's nine grouped products
    in a training step, ``rows`` assignments landing on the held experts:
    each product ``2 x rows x d_model x expert_width`` FLOP; the held
    experts' matrices read twice in the compute dtype (forward, dX) and
    their dW written once in float32; a row in (x) and out (y) forward,
    in (dy) and out (dx) backward."""
    e, f = sizes["d_model"], sizes["expert_width"]
    held = sizes.get("experts_held") or sizes["n_experts"]
    flops = (PRODUCTS_FORWARD + PRODUCTS_BACKWARD) * 2.0 * rows * e * f
    weights = held * expert_params(sizes)
    nbytes = weights * (2 * itemsize + 4) + 4.0 * rows * e * itemsize
    return flops, float(nbytes)


def flash_window_call(kind: str, dims: Tuple[int, ...], window: int,
                      itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one windowed flash call whose (first) result is
    ``dims`` = (b, h, s, d): each of the kernel's matmuls touches the
    ``window_pairs(s, window)`` live pairs, 2 x d FLOP a pair; the
    tensors ``roofline.FLASH_TENSORS`` counts go in or out once."""
    base = WINDOW_KINDS[kind]
    if base == "flash_bwd_delta":         # elementwise; bytes by its readers
        return 0.0, 0.0
    b, h, s, d = dims
    flops = roofline.FLASH_MATMULS[base] * 2.0 * b * h \
        * window_pairs(s, window) * d
    return flops, float(roofline.FLASH_TENSORS[base] * b * h * s * d
                        * itemsize)
