"""Normalization ops (f32 accumulation, XLA-fusable).

These are deliberately plain jnp: XLA fuses the reductions into neighboring
elementwise work on TPU, so a Pallas kernel buys nothing here. The contract
is numerical: statistics are always computed in float32 regardless of the
activation dtype (bf16 on TPU), matching standard large-model practice.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6,
             dtype: Optional[jnp.dtype] = None) -> jnp.ndarray:
    """RMSNorm over the last axis. ``scale`` broadcast on the last axis."""
    orig_dtype = dtype or x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jnp.reciprocal(jnp.sqrt(var + eps))
    return (y * scale.astype(jnp.float32)).astype(orig_dtype)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5,
               dtype: Optional[jnp.dtype] = None) -> jnp.ndarray:
    """LayerNorm over the last axis with learned scale and bias."""
    orig_dtype = dtype or x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jnp.reciprocal(jnp.sqrt(var + eps))
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(orig_dtype)


@jax.named_scope("gated_norm")
def gated_rms_norm(x: jnp.ndarray, scale: jnp.ndarray, w_down: jnp.ndarray,
                   w_up: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm whose output passes a low-rank sigmoid gate of itself:
    ``n = rms_norm(x)``, ``n * sigmoid((n W_down) W_up)`` with ``W_down
    [d, rank]``, ``W_up [rank, d]``, no bias and nothing between the two
    products. The products run in ``x``'s dtype, the gate in float32."""
    n = rms_norm(x, scale, eps)
    low = jnp.dot(n, w_down.astype(n.dtype))
    gate = jax.nn.sigmoid(jnp.dot(low, w_up.astype(n.dtype),
                                  preferred_element_type=jnp.float32))
    return (n.astype(jnp.float32) * gate).astype(n.dtype)
