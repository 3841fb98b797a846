"""Rotary position embeddings (RoPE).

Two layouts are supported:
- ``"neox"`` (rotate-half): the first half of the head dim is paired with
  the second half. Used by GPT-NeoX/Llama-family models.
- ``"gptj"`` (rotate-every-two): even/odd interleaved pairs, the original
  GPT-J layout.

Tables are precomputed once (f32) and gathered per position so the op is a
pure elementwise fuse target for XLA.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


def rotary_table(max_len: int, rot_dim: int, base: float = 10000.0
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute (sin, cos) tables of shape (max_len, rot_dim // 2)."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, rot_dim, 2,
                                          dtype=jnp.float32) / rot_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)          # (max_len, rot_dim/2)
    return jnp.sin(freqs), jnp.cos(freqs)


def yarn_inv_freq(rot_dim: int, base: float, factor: float,
                  original_len: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's ``rot_dim // 2`` inverse frequencies (float32 numpy): per
    frequency a blend of the plain one ``f`` and the interpolated one
    ``f / factor`` by a linear ramp over the pair index, from ``low``
    (``beta_fast`` turns in ``original_len`` positions: all plain below
    it) to ``high`` (``beta_slow`` turns: all interpolated above it),
    both truncated to whole dimensions, as the public implementation
    does by default."""
    half = rot_dim // 2
    freq = 1.0 / base ** (np.arange(0, rot_dim, 2, dtype=np.float64)
                          / rot_dim)

    def correction_dim(turns: float) -> float:
        return rot_dim * math.log(original_len / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rot_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return (freq / factor * ramp + freq * (1.0 - ramp)).astype(np.float32)


def rotary_at(positions: jnp.ndarray, inv_freq: Sequence[float],
              scale: float = 1.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sin, cos) at ``positions`` ``(..., seq)`` for the frequencies
    ``inv_freq``, each ``(..., seq, 1, len(inv_freq))`` and times
    ``scale`` (YaRN's attention factor): what :func:`apply_rotary`
    gathers out of a table, computed for the positions asked and no
    other, so a step over a 64k window builds no 64k-row table."""
    ang = positions.astype(jnp.float32)[..., None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    return jnp.sin(ang) * scale, jnp.cos(ang) * scale


def apply_rotary(x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray,
                 positions: Optional[jnp.ndarray] = None,
                 layout: str = "gptj") -> jnp.ndarray:
    """Apply RoPE to ``x`` of shape (..., seq, num_heads, head_dim).

    Only the leading ``2 * sin.shape[-1]`` features of head_dim are rotated
    (GPT-J rotates ``rotary_dim=64`` of its 256-dim heads); the remainder
    passes through.

    ``positions``: optional (..., seq) int array of absolute positions
    (for packed sequences / decode steps); defaults to arange. ``sin`` /
    ``cos`` of :func:`rotary_at` (one more axis than a table has) are
    the rows at the positions already and are used as they are.
    """
    rot = 2 * sin.shape[-1]
    seq = x.shape[-3]
    if sin.ndim > 2:
        sin_p, cos_p = sin, cos
    elif positions is None:
        sin_p, cos_p = sin[:seq], cos[:seq]            # (seq, rot/2)
        # broadcast over leading batch dims and the heads axis
        sin_p = sin_p[:, None, :]
        cos_p = cos_p[:, None, :]
    else:
        sin_p = jnp.take(sin, positions, axis=0)[..., :, None, :]
        cos_p = jnp.take(cos, positions, axis=0)[..., :, None, :]

    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x32 = x_rot.astype(jnp.float32)

    if layout == "gptj":
        x1 = x32[..., 0::2]
        x2 = x32[..., 1::2]
        r1 = x1 * cos_p - x2 * sin_p
        r2 = x2 * cos_p + x1 * sin_p
        rotated = jnp.stack([r1, r2], axis=-1).reshape(x32.shape)
    elif layout == "neox":
        half = rot // 2
        x1 = x32[..., :half]
        x2 = x32[..., half:]
        r1 = x1 * cos_p - x2 * sin_p
        r2 = x2 * cos_p + x1 * sin_p
        rotated = jnp.concatenate([r1, r2], axis=-1)
    else:
        raise ValueError(f"unknown rotary layout: {layout!r}")

    rotated = rotated.astype(x.dtype)
    if x_pass.shape[-1] == 0:
        return rotated
    return jnp.concatenate([rotated, x_pass], axis=-1)
