"""Stable softmax cross-entropy for language-model heads.

Two paths:

- :func:`cross_entropy_loss` — the reference: takes materialized logits,
  computed in float32 with log-sum-exp, optional z-loss (stabilizes the
  softmax normalizer at scale, as in PaLM), and a validity mask for
  padded / shifted-label positions.

- :func:`fused_lm_head_loss` — the memory-lean production path: takes the
  final *hidden states* and the LM-head weights and computes the loss in
  sequence chunks under a ``custom_vjp``. Per chunk it projects to logits
  (float32 MXU accumulation), reduces to log-sum-exp + label logit, and
  keeps only the per-token LSE as a residual; the backward recomputes each
  chunk's logits and softmax to form dX/dW/db. The full
  ``[batch, seq, vocab]`` float32 logits tensor is never resident — peak
  loss memory drops from ``O(b·s·v)`` to ``O(b·chunk·v)``, which is what
  frees HBM for larger batches at long sequence lengths.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       mask: Optional[jnp.ndarray] = None,
                       z_loss_coeff: float = 0.0,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean token cross entropy.

    logits: (..., vocab), labels: (...) int, mask: (...) bool/float of
    valid positions. Returns (loss, n_valid_tokens) — callers doing
    data-parallel mean should psum both and divide (exact global mean).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - label_logit
    if z_loss_coeff:
        nll = nll + z_loss_coeff * jnp.square(lse)
    if mask is None:
        n = jnp.array(nll.size, jnp.float32)
        return jnp.sum(nll) / n, n
    mask = mask.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll * mask) / n, n


# ------------------------------------------------------- fused chunked CE
def _chunk_layout(x, labels, mask, chunk: int):
    """Pad seq to a chunk multiple and reshape to chunk-major scan inputs.

    x: (b, s, e) -> (nc, b, C, e); labels/mask: (b, s) -> (nc, b, C).
    Padded positions carry mask 0 so they contribute nothing.
    """
    b, s, e = x.shape
    c = min(chunk, s)
    nc = -(-s // c)
    pad = nc * c - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    xc = jnp.moveaxis(x.reshape(b, nc, c, e), 1, 0)
    yc = jnp.moveaxis(labels.reshape(b, nc, c), 1, 0)
    mc = jnp.moveaxis(mask.reshape(b, nc, c), 1, 0)
    return xc, yc, mc, pad


def _chunk_logits(xi, w, bias):
    """One chunk's logits in float32: (b, C, e) @ (e, v) + (v,)."""
    logits = jnp.einsum("bce,ev->bcv", xi, w,
                        preferred_element_type=jnp.float32)
    return logits + bias


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
@jax.named_scope("lm_head_loss")
def _fused_ce(cfg, x, w, bias, labels, mask):
    loss, n, _ = _fused_ce_fwd_impl(cfg, x, w, bias, labels, mask)
    return loss, n


def _fused_ce_fwd_impl(cfg, x, w, bias, labels, mask):
    chunk, z = cfg
    wd = w.astype(x.dtype)
    xc, yc, mc, _ = _chunk_layout(x, labels, mask, chunk)

    def body(carry, inp):
        loss_sum, n = carry
        xi, yi, mi = inp
        logits = _chunk_logits(xi, wd, bias)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yi[..., None], axis=-1)[..., 0]
        nll = lse - ll
        if z:
            nll = nll + z * jnp.square(lse)
        return (loss_sum + jnp.sum(nll * mi), n + jnp.sum(mi)), lse

    (loss_sum, n), lses = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, yc, mc))
    n = jnp.maximum(n, 1.0)
    return loss_sum / n, n, lses


@jax.named_scope("lm_head_loss")
def _fused_ce_fwd(cfg, x, w, bias, labels, mask):
    loss, n, lses = _fused_ce_fwd_impl(cfg, x, w, bias, labels, mask)
    return (loss, n), (x, w, bias, labels, mask, lses, loss, n)


@jax.named_scope("lm_head_loss")
def _fused_ce_bwd(cfg, res, cts):
    chunk, z = cfg
    x, w, bias, labels, mask, lses, loss, n = res
    g_loss, _ = cts                      # n is a count — no useful cotangent
    wd = w.astype(x.dtype)
    xc, yc, mc, pad = _chunk_layout(x, labels, mask, chunk)
    b, s, e = x.shape
    v = w.shape[-1]

    def body(carry, inp):
        dw, db = carry
        xi, yi, mi, lsei = inp
        logits = _chunk_logits(xi, wd, bias)
        p = jnp.exp(logits - lsei[..., None])
        coef = (g_loss / n) * mi                       # (b, C)
        zf = (1.0 + 2.0 * z * lsei) if z else 1.0
        one_hot = jax.nn.one_hot(yi, v, dtype=jnp.float32)
        dl = p * (coef * zf)[..., None] - coef[..., None] * one_hot
        db = db + jnp.sum(dl, axis=(0, 1))
        dlc = dl.astype(x.dtype)
        dxi = jnp.einsum("bcv,ev->bce", dlc, wd,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        dw = dw + jnp.einsum("bce,bcv->ev", xi, dlc,
                             preferred_element_type=jnp.float32)
        # d loss / d mask_i = (nll_i - loss) / n  (mask enters sum and n)
        ll = jnp.take_along_axis(logits, yi[..., None], axis=-1)[..., 0]
        nll = lsei - ll
        if z:
            nll = nll + z * jnp.square(lsei)
        dmi = g_loss * (nll - loss) / n
        return (dw, db), (dxi, dmi)

    (dw, db), (dxc, dmc) = jax.lax.scan(
        body,
        (jnp.zeros((e, v), jnp.float32), jnp.zeros((v,), jnp.float32)),
        (xc, yc, mc, lses))
    dx = jnp.moveaxis(dxc, 0, 1).reshape(b, -1, e)[:, :s]
    dm = jnp.moveaxis(dmc, 0, 1).reshape(b, -1)[:, :s]
    dlabels = np.zeros(labels.shape, jax.dtypes.float0)
    return dx, dw.astype(w.dtype), db.astype(bias.dtype), dlabels, \
        dm.astype(mask.dtype)


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_lm_head_loss(x: jnp.ndarray, head_w: jnp.ndarray,
                       labels: jnp.ndarray, *,
                       head_bias: Optional[jnp.ndarray] = None,
                       mask: Optional[jnp.ndarray] = None,
                       z_loss_coeff: float = 0.0,
                       chunk_size: int = 512,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked fused LM-head projection + cross entropy.

    x: (b, s, e) final hidden states (compute dtype); head_w: (e, v)
    master weights (cast to ``x.dtype`` for the MXU matmul, float32
    accumulation); labels: (b, s) int; mask: (b, s) valid positions.
    ``chunk_size`` tokens of each sequence are projected at a time
    (``0``/``>= s`` degenerates to one chunk — still fused, no separate
    logits tensor or float32 upcast copy). ``z_loss_coeff`` must be a
    static Python float. Returns (mean_loss, n_valid_tokens) like
    :func:`cross_entropy_loss`.
    """
    b, s, _ = x.shape
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    mask = mask.astype(jnp.float32)
    bias = head_bias if head_bias is not None \
        else jnp.zeros((head_w.shape[-1],), jnp.float32)
    chunk = chunk_size if chunk_size and chunk_size > 0 else s
    cfg = (int(chunk), float(z_loss_coeff))
    return _fused_ce(cfg, x, head_w, bias, labels, mask)
