"""The yardstick's constants and counting rules: published peaks by
``device_kind``, the operations and bytes each kernel's algorithm needs
(computed from shapes, never read from the program), and the model-FLOP
count behind ``mfu``. No JAX."""
from typing import Any, Dict, Tuple

#: Google Cloud documentation, "TPU v5e" (system architecture table):
#: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. JAX reports the
#: chip as "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> Dict[str, Any]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to benchmarks/roofline.py "
                       f"with its source (have {sorted(PEAKS)})")
    return PEAKS[device_kind]


def min_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])


# -------------------------------------------------------- flash kernels
#: causal matmuls each kernel's algorithm needs: the forward computes
#: S = QK^T and PV; dkdv recomputes S and computes dP, dV, dK; dq
#: recomputes S and computes dP, dQ; delta is elementwise (rowsum O*dO).
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dkdv": 4, "flash_bwd_dq": 3,
                 "flash_bwd_delta": 0}
#: (b, h, s, d) tensors each kernel must read or write at least once
FLASH_TENSORS = {"flash_fwd": 4, "flash_bwd_dkdv": 6, "flash_bwd_dq": 5,
                 "flash_bwd_delta": 2}


def flash_call(kind: str, dims: Tuple[int, ...], itemsize: int = 2
               ) -> Tuple[float, float]:
    """(flops, bytes) of one call of a flash kernel whose (first)
    result is ``dims`` = (b, h, s, d), causal, seq_q = seq_k = s. A
    causal matmul touches half the s x s square: 2 * s*s*d / 2."""
    if kind == "flash_bwd_delta":          # result is (b, h, 1, s)
        return 0.0, 0.0                    # bytes counted by its readers
    b, h, s, d = dims
    flops = FLASH_MATMULS[kind] * b * h * s * s * d
    return float(flops), float(FLASH_TENSORS[kind] * b * h * s * d
                               * itemsize)


# --------------------------------------------------------- paged kernel
def paged_decode(pages: int, model: Dict[str, Any]) -> Tuple[float, float]:
    """(flops, bytes) of paged attention over ``pages`` KV pages summed
    over sequences, steps and layers: each page is read once for k and
    once for v by all heads that share it, and every query row does a
    QK^T and a PV row against its tokens."""
    bs, d = model["kv_block_size"], model["head_dim"]
    nbytes = pages * 2 * model["kv_heads"] * bs * d * model["itemsize"]
    flops = pages * bs * 4 * model["n_heads"] * d
    return float(flops), float(nbytes)


def paged_prefill(prompt_len: int, cached: int, chunk: int,
                  model: Dict[str, Any]) -> Tuple[float, float]:
    """(flops, bytes) per layer of chunked prefill of one prompt whose
    first ``cached`` tokens were served from the prefix cache: the query
    at position p attends p + 1 keys; a chunk reads every page written
    so far."""
    h, d, bs = model["n_heads"], model["head_dim"], model["kv_block_size"]
    rows = prompt_len * (prompt_len + 1) // 2 - cached * (cached + 1) // 2
    flops = 4.0 * h * d * rows
    nbytes, start = 0.0, cached
    while start < prompt_len:
        n = min(chunk, prompt_len - start)
        pages = -(-(start + n) // bs)
        nbytes += pages * 2 * model["kv_heads"] * bs * d * model["itemsize"]
        nbytes += 2 * n * h * d * model["itemsize"]          # q in, o out
        start += n
    return flops, nbytes


# ------------------------------------------------------------------ mfu
def train_flops_per_token(matmul_params: int, n_layers: int, n_heads: int,
                          head_dim: int, seq: int) -> float:
    """Model FLOPs a token needs in training: 6 per matmul parameter
    (the embedding table is a lookup and is not counted) plus causal
    attention, forward and backward (3 x 2 matmuls of s*s*d/2 MACs a
    head): 6 * layers * heads * head_dim * seq. Recomputation (remat,
    flash's S in the backward) is not counted."""
    return 6.0 * matmul_params + 6.0 * n_layers * n_heads * head_dim * seq
