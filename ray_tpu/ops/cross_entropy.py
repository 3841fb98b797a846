"""Stable softmax cross-entropy for language-model heads.

Two paths:

- :func:`cross_entropy_loss` — the reference: takes materialized logits,
  computed in float32 with log-sum-exp, optional z-loss (stabilizes the
  softmax normalizer at scale, as in PaLM), and a validity mask for
  padded / shifted-label positions.

- :func:`fused_lm_head_loss` — the memory-lean production path: takes the
  final *hidden states* and the LM-head weights and computes the loss in
  sequence chunks under a ``custom_vjp``. Per chunk it projects to logits
  (float32 MXU accumulation) and reduces to log-sum-exp + label logit. The
  full ``[batch, seq, vocab]`` float32 logits tensor is never resident —
  peak loss memory drops from ``O(b·s·v)`` to ``O(b·chunk·v)``, which is
  what frees HBM for larger batches at long sequence lengths.

  The two functions of the ``custom_vjp`` do different work. The primal
  (a call nobody differentiates: evaluation, a pipeline stage's forward)
  runs one matmul a chunk and no gradient work. The differentiated rule
  forms the gradients in the same pass: the loss is a scalar, so its
  gradient is known up to the incoming cotangent once a chunk's logits
  and LSE are, and each chunk runs three matmuls (logits, dX, dW) where a
  backward that recomputed the logits ran four. What it holds for the
  backward is therefore not ``x``, ``W`` and the LSEs but the gradients at
  unit cotangent — dX like ``x``, dW like ``W`` (``[e, v]``, the head's own
  size), db — and the per-token nll; the backward only scales them.

  Under a mesh whose data axes split the batch (:class:`PerChip`) both
  scans run per chip inside ``jax.shard_map`` and every sum over chips
  happens once, outside the scan (:func:`fused_lm_head_loss` says which).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


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
class PerChip(NamedTuple):
    """Where the fused loss's sums over chips happen when it runs per
    chip: ``batch`` are the mesh axes (each of size > 1) that split the
    batch rows, ``rows`` those of them that also split the head's rows
    (``fsdp`` under ``FSDP_RULES``, none under ``DDP_RULES``)."""
    mesh: Any
    batch: Tuple[str, ...]
    rows: Tuple[str, ...]


def _per_chip(cfg, scan_fn, grads: bool):
    """``scan_fn(cfg, x, w, bias, labels, mask)`` as it is (no mesh: the
    arrays are whole) or mapped over the batch axes, where it sees its
    chip's rows of ``x`` / ``labels`` / ``mask`` and its rows of ``w``.
    Returns (loss, n), both whole, and with ``grads`` (dx, dw, db, nll)
    laid out like (x, w, bias, mask)."""
    fn = functools.partial(scan_fn, cfg)
    pc = cfg[2]
    if pc is None:
        return fn
    x, y = P(pc.batch, None, None), P(pc.batch, None)
    head, bias = P(pc.rows or None, None), P(None)
    return jax.shard_map(
        fn, mesh=pc.mesh, in_specs=(x, head, bias, y, y),
        out_specs=(P(), P()) + ((x, head, bias, y) if grads else ()),
        check_vma=False)


def _whole_head(wd, pc):
    """The chip's rows of the (already cast) head gathered to the whole."""
    if pc is None or not pc.rows:
        return wd
    return jax.lax.all_gather(wd, pc.rows, axis=0, tiled=True)


def _chip_sum(a, pc):
    """A per-chip partial sum, summed over every chip that holds rows."""
    return a if pc is None else jax.lax.psum(a, pc.batch)


def _head_sum(dw, pc):
    """A chip's whole ``[e, v]`` dW summed over the chips and landed on the
    head's own sharding: the transpose of :func:`_whole_head` (a
    reduce-scatter over ``rows``), a plain sum over the other batch axes."""
    if pc is None:
        return dw
    if pc.rows:
        dw = jax.lax.psum_scatter(dw, pc.rows, scatter_dimension=0,
                                  tiled=True)
    rest = tuple(a for a in pc.batch if a not in pc.rows)
    return jax.lax.psum(dw, rest) if rest else dw


def n_chunks(seq: int, chunk: int) -> int:
    """How many chunks the fused loss scans over ``seq`` positions."""
    return -(-seq // min(chunk, seq)) if chunk and chunk > 0 else 1


def _chunk_layout(x, labels, mask, chunk: int):
    """Pad seq to a chunk multiple and reshape to chunk-major scan inputs.

    x: (b, s, e) -> (nc, b, C, e); labels/mask: (b, s) -> (nc, b, C).
    Padded positions carry mask 0 so they contribute nothing.
    """
    b, s, e = x.shape
    c = min(chunk, s)
    nc = n_chunks(s, chunk)
    pad = nc * c - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    xc = jnp.moveaxis(x.reshape(b, nc, c, e), 1, 0)
    yc = jnp.moveaxis(labels.reshape(b, nc, c), 1, 0)
    mc = jnp.moveaxis(mask.reshape(b, nc, c), 1, 0)
    return xc, yc, mc


def _chunk_logits(xi, w, bias):
    """One chunk's logits in float32: (b, C, e) @ (e, v) + (v,)."""
    logits = jnp.einsum("bce,ev->bcv", xi, w,
                        preferred_element_type=jnp.float32)
    return logits + bias


def _chunk_nll(logits, yi, z):
    """One chunk's per-token (LSE, nll) from its float32 logits."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, yi[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if z:
        nll = nll + z * jnp.square(lse)
    return lse, nll


def _loss_scan(cfg, x, w, bias, labels, mask):
    """One matmul a chunk, no gradient work, nothing kept."""
    chunk, z, pc = cfg
    wd = _whole_head(w.astype(x.dtype), pc)
    n = jnp.maximum(_chip_sum(jnp.sum(mask), pc), 1.0)

    def body(loss_sum, inp):
        xi, yi, mi = inp
        _, nll = _chunk_nll(_chunk_logits(xi, wd, bias), yi, z)
        return loss_sum + jnp.sum(nll * mi), None

    loss_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                               _chunk_layout(x, labels, mask, chunk))
    return _chip_sum(loss_sum, pc) / n, n


def _grad_scan(cfg, x, w, bias, labels, mask):
    """One scan forms the loss and, from each chunk's one set of logits,
    dX, dW and db at unit cotangent (three matmuls a chunk). Per chip the
    coefficient ``1/n`` is the global one, ``dw`` is the chip's own whole
    ``[e, v]`` float32 sum that no collective touches inside the scan, and
    the sums over chips follow the scan, dW's in float32."""
    chunk, z, pc = cfg
    wd = _whole_head(w.astype(x.dtype), pc)
    b, s, e = x.shape
    v = w.shape[-1]
    n = jnp.maximum(_chip_sum(jnp.sum(mask), pc), 1.0)

    def body(carry, inp):
        loss_sum, dw, db = carry
        xi, yi, mi = inp
        logits = _chunk_logits(xi, wd, bias)
        lse, nll = _chunk_nll(logits, yi, z)
        p = jnp.exp(logits - lse[..., None])
        coef = (1.0 / n) * mi                          # (b, C)
        zf = (1.0 + 2.0 * z * lse) if z else 1.0
        one_hot = jax.nn.one_hot(yi, v, dtype=jnp.float32)
        dl = p * (coef * zf)[..., None] - coef[..., None] * one_hot
        db = db + jnp.sum(dl, axis=(0, 1))
        # held once in the compute dtype for both products: left to fuse,
        # XLA forms dl from the float32 logits again inside each of them
        # (three exponentials an element; each product ~10% slower on a v5e)
        dlc = jax.lax.optimization_barrier(dl.astype(x.dtype))
        dxi = jnp.einsum("bcv,ev->bce", dlc, wd,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        dw = dw + jnp.einsum("bce,bcv->ev", xi, dlc,
                             preferred_element_type=jnp.float32)
        return (loss_sum + jnp.sum(nll * mi), dw, db), (dxi, nll)

    (loss_sum, dw, db), (dxc, nllc) = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros((e, v), jnp.float32),
         jnp.zeros((v,), jnp.float32)),
        _chunk_layout(x, labels, mask, chunk))
    loss = _chip_sum(loss_sum, pc) / n
    dx = jnp.moveaxis(dxc, 0, 1).reshape(b, -1, e)[:, :s]
    nll = jnp.moveaxis(nllc, 0, 1).reshape(b, -1)[:, :s]
    dw = _head_sum(dw, pc)
    if pc is not None:
        # dX waits for the reduced dW: left free, the v5e's scheduler puts
        # the reduce-scatter behind the whole backward and the chip's whole
        # [e, v] float32 dW (0.5 GiB at 4096 x 32768) stays live until then
        # (0.75 GiB of temporaries in an 8-layer step, for no time)
        dx, dw = jax.lax.optimization_barrier((dx, dw))
    return (loss, n, dx, dw.astype(w.dtype),
            _chip_sum(db, pc).astype(bias.dtype), nll)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
@jax.named_scope("lm_head_loss")
def _fused_ce(cfg, x, w, bias, labels, mask):
    """The primal (a call nobody differentiates)."""
    return _per_chip(cfg, _loss_scan, False)(x, w, bias, labels, mask)


@jax.named_scope("lm_head_loss")
def _fused_ce_fwd(cfg, x, w, bias, labels, mask):
    """The differentiated rule: the forward scan forms the gradients and
    the backward only scales them. Residuals: those gradients, each in the
    dtype (and, under a mesh, on the sharding) of the cotangent it becomes,
    and the per-token nll (for the mask's gradient)."""
    loss, n, dx, dw, db, nll = _per_chip(cfg, _grad_scan, True)(
        x, w, bias, labels, mask)
    return (loss, n), (dx, dw, db, nll, loss, n)


@jax.named_scope("lm_head_loss")
def _fused_ce_bwd(cfg, res, cts):
    dx, dw, db, nll, loss, n = res
    g_loss, _ = cts                      # n is a count — no useful cotangent
    # d loss / d mask_i = (nll_i - loss) / n  (mask enters sum and n);
    # the mask is float32 (fused_lm_head_loss casts it), as nll is
    dm = g_loss * (nll - loss) / n
    dlabels = np.zeros(nll.shape, jax.dtypes.float0)
    dx, dw, db = ((g_loss * g).astype(g.dtype) for g in (dx, dw, db))
    return dx, dw, db, dlabels, dm


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_lm_head_loss(x: jnp.ndarray, head_w: jnp.ndarray,
                       labels: jnp.ndarray, *,
                       head_bias: Optional[jnp.ndarray] = None,
                       mask: Optional[jnp.ndarray] = None,
                       z_loss_coeff: float = 0.0,
                       chunk_size: int = 512,
                       per_chip: Optional[PerChip] = None,
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

    Not differentiated, the call does no gradient work. Differentiated
    (``jax.grad`` / ``jax.vjp``), the forward pass already forms dX, dW
    and db at unit cotangent, and the residuals are those: one array like
    ``x``, one like ``head_w``, one like the bias, and ``(b, s)`` float32
    nll. Where the forward and the backward are one program they live for
    an instant; a caller that carries ``jax.vjp``'s closure from one
    program to another (the single-device MPMD pipeline's last stage)
    carries the head's size per micro-batch in flight.

    Where the sums over chips happen. With ``per_chip=None`` (no mesh, one
    device, the pipeline's last stage) the arrays are whole and any
    partitioning is GSPMD's: under a mesh that splits the batch it gives
    the scan's dW carry the head's sharding and reduces every chunk's
    partial product onto it. With a :class:`PerChip` (``lm_loss`` builds it
    when the mesh's data axes split the batch and neither the vocabulary
    nor the sequence is split) the scans run per chip under
    ``jax.shard_map``: the head is cast and all-gathered once before the
    scan, the token count and the loss are summed over chips with a
    ``psum``, dX stays on its chip, and dW (float32) and db are reduced
    onto the parameter's sharding once, after the scan. The arithmetic of
    a chunk is the same in both; only the order of the sum over chunks
    and chips differs.
    """
    b, s, _ = x.shape
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    mask = mask.astype(jnp.float32)
    bias = head_bias if head_bias is not None \
        else jnp.zeros((head_w.shape[-1],), jnp.float32)
    chunk = chunk_size if chunk_size and chunk_size > 0 else s
    cfg = (int(chunk), float(z_loss_coeff), per_chip)
    return _fused_ce(cfg, x, head_w, bias, labels, mask)
