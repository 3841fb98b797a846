"""Flash attention for TPU: Pallas forward kernel + chunked XLA backward.

Forward: a VMEM-blocked streaming-softmax kernel. Grid is
(batch, heads, q_blocks, k_blocks) with the k axis innermost so the
(m, l, acc) scratch accumulators persist across k blocks; matmuls hit the
MXU in bf16 with float32 accumulation (``preferred_element_type``); the
log-sum-exp is emitted so the backward pass can recompute P exactly.

Backward: Pallas dq/dk/dv kernels (default) — dk/dv accumulate in VMEM
across a q scan, dq across a k scan, both recomputing P from the saved
log-sum-exp (Dao et al., Algorithm 4). The softmax-Jacobian diagonal
``delta = rowsum(dO·O)`` is precomputed ONCE by a small fused Pallas
kernel and fed to both passes, so neither rematerializes the f32
``dO·O`` product. The earlier `lax.scan` XLA formulation remains
available (``backward="xla"``) as the numerical cross-check.

Block sizes: callers may pass explicit ``block_q``/``block_k``; leaving
them ``None`` picks chip-aware defaults (:func:`default_flash_blocks`,
keyed on ``parallel.mesh.chip_spec``), and
:func:`autotune_flash_blocks` times a small candidate grid once and
caches the winner per ``(chip, seq, head_dim)``.

Layout convention at this layer: (batch, num_heads, seq, head_dim).
Use :func:`ray_tpu.ops.attention.multihead_attention` for the (B, S, H, D)
model-side API with automatic dispatch.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import logging
import math
import os
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.util import compile_cache

logger = logging.getLogger(__name__)

_NEG_INF = -1e30  # large-finite instead of -inf: avoids NaN from inf-inf


@dataclasses.dataclass(frozen=True)
class _Cfg:
    causal: bool
    sm_scale: float
    block_q: int
    block_k: int
    interpret: bool
    bwd: str = "pallas"   # "pallas" | "xla"
    # > 0: a sliding-window layer, query i attends the ``window`` keys up
    # to and including its own (``i - window < key <= i``); 0: every key
    # the causal structure leaves. The windowed calls carry names of
    # their own (``flash_window_*``) and a narrower innermost grid axis.
    window: int = 0


def _name(cfg: _Cfg, stem: str) -> str:
    """``flash_fwd`` or, windowed, ``flash_window_fwd``: what reads a
    trace counts each by its own rule."""
    return f"flash_window_{stem}" if cfg.window else f"flash_{stem}"


# The windowed kernels' innermost grid axis walks only the blocks that
# can hold a live (query, key) pair: a q block's k blocks start at the one
# that holds the first row's oldest key, a k block's q blocks at the one
# that holds the first key's own row. A block the walk reaches past the
# sequence's end, ahead of the diagonal or behind the window is skipped
# (``_window_run``) and its index clamped, so nothing new is fetched.

def _k_first(cfg: _Cfg, ib, offset: int):
    """The first k block a q block's rows see under the window."""
    return jnp.maximum(ib * cfg.block_q + offset - cfg.window + 1, 0) \
        // cfg.block_k


def _q_first(cfg: _Cfg, kb, offset: int):
    """The first q block whose rows see a k block under causality."""
    return jnp.maximum(kb * cfg.block_k - offset, 0) // cfg.block_q


def _k_steps(cfg: _Cfg, nk: int) -> int:
    """k blocks a q block walks: its rows' keys span ``block_q + window
    - 1`` positions, which touch one block more than they fill."""
    return min(nk, -(-(cfg.block_q + cfg.window - 1) // cfg.block_k) + 1)


def _q_steps(cfg: _Cfg, nq: int) -> int:
    return min(nq, -(-(cfg.block_k + cfg.window - 1) // cfg.block_q) + 1)


def _window_run(cfg: _Cfg, ib, kb, n_blocks_k, offset: int):
    """Whether the (q block ``ib``, k block ``kb``) pair holds a live
    pair: not ahead of the diagonal, not wholly behind the window, inside
    the sequence."""
    bq, bk = cfg.block_q, cfg.block_k
    return (kb * bk <= ib * bq + (bq - 1) + offset) \
        & (kb * bk + (bk - 1) > ib * bq + offset - cfg.window) \
        & (kb < n_blocks_k)


def _window_mask(cfg: _Cfg, s, ib, kb, offset: int):
    """The causal and window mask inside a block."""
    bq, bk = cfg.block_q, cfg.block_k
    rows = ib * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = (cols <= rows + offset) & (cols > rows + offset - cfg.window)
    return jnp.where(keep, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, cfg: _Cfg, offset: int, nk_all: int = 0):
    """``offset = sk - sq``: causality is end-aligned (query i attends keys
    0..i+offset), matching ``attention_reference``'s ``tril(k=sk-sq)`` for
    decode-style sq < sk calls. Windowed, the innermost axis counts from
    the q block's first k block (``_k_first``) and ``nk_all`` is the
    sequence's count of k blocks."""
    ib = pl.program_id(2)          # q block index
    kb = pl.program_id(3)          # k block index (innermost)
    nk = pl.num_programs(3)
    bq, bk = cfg.block_q, cfg.block_k
    step = kb                      # the innermost axis's own count
    if cfg.window:
        kb = _k_first(cfg, ib, offset) + step

    @pl.when(step == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # Under causality, blocks strictly above the diagonal contribute nothing.
    if cfg.window:
        run = _window_run(cfg, ib, kb, nk_all, offset)
    else:
        run = (kb * bk <= ib * bq + (bq - 1) + offset) if cfg.causal \
            else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                                  # (bq, d)
        k = k_ref[0, 0]                                  # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        s = s * cfg.sm_scale
        if cfg.window:
            s = _window_mask(cfg, s, ib, kb, offset)
        elif cfg.causal:
            rows = ib * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            cols = kb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows + offset, s, _NEG_INF)

        m_prev = m_s[...]                                # (bq, 128) lanes equal
        l_prev = l_s[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)       # (bq, 1)
        m_next = jnp.maximum(m_prev, m_cur)              # (bq, 128)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, 0:1])                  # (bq, bk) f32
        l_s[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_next
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, d)
        acc_s[...] = acc_s[...] * alpha[:, 0:1] + pv

    @pl.when(step == nk - 1)
    def _final():
        l = l_s[:, 0:1]
        # Fully-masked rows (can't happen with causal self-attn) guard:
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_s[:, 0] + jnp.log(l[:, 0])).reshape(1, bq)


def _compiler_params(cfg: _Cfg, grid_rank: int):
    """Mosaic grid semantics: every axis parallel except the innermost
    reduction axis of the rank-4 grids. Interpret mode takes none."""
    if cfg.interpret:
        return None
    sem = ("parallel",) * 3 + ("arbitrary",) * (grid_rank - 3)
    return pltpu.CompilerParams(dimension_semantics=sem)


def _fwd_pallas(cfg: _Cfg, q, k, v) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(cfg.block_q, sq)
    bk = min(cfg.block_k, sk)
    cfg = dataclasses.replace(cfg, block_q=bq, block_k=bk)
    nq, nk = sq // bq, sk // bk
    grid = (b, h, nq, nk)
    offset = sk - sq

    kernel = functools.partial(_fwd_kernel, cfg=cfg, offset=offset)
    kv_block = lambda b_, h_, i, j: (b_, h_, j, 0)      # noqa: E731
    if cfg.window:
        grid = (b, h, nq, _k_steps(cfg, nk))
        kernel = functools.partial(kernel, nk_all=nk)
        kv_block = lambda b_, h_, i, j: (                # noqa: E731
            b_, h_, jnp.minimum(_k_first(cfg, i, offset) + j, nk - 1), 0)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_block),
            pl.BlockSpec((1, 1, bk, d), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b_, h_, i, j: (b_, h_, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max m
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom l
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=_compiler_params(cfg, 4),
        interpret=cfg.interpret,
        name=_name(cfg, "fwd"),
    )(q, k, v)
    return out, lse[:, :, 0, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _Cfg, q, k, v):
    o, _ = _fwd_pallas(cfg, q, k, v)
    return o


def _flash_fwd(cfg: _Cfg, q, k, v):
    o, lse = _fwd_pallas(cfg, q, k, v)
    return o, (q, k, v, o, lse)


def _delta_kernel(o_ref, do_ref, delta_ref, *, bq: int):
    """delta = rowsum(dO * O) in f32, blocked over q — the backward's
    softmax-Jacobian diagonal, shaped like the LSE so both ride the same
    block spec in the dq and dk/dv kernels."""
    o = o_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    delta_ref[0, 0] = jnp.sum(o * do, axis=-1).reshape(1, bq)


def _delta_pallas(cfg: _Cfg, o, do):
    b, h, sq, d = o.shape
    bq = min(cfg.block_q, sq)
    nq = sq // bq
    return pl.pallas_call(
        functools.partial(_delta_kernel, bq=bq),
        grid=(b, h, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, bq), lambda b_, h_, i: (b_, h_, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        compiler_params=_compiler_params(cfg, 3),
        interpret=cfg.interpret,
        name=_name(cfg, "bwd_delta"),
    )(o, do)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_s, dv_s, *, cfg: _Cfg, offset: int,
                 nq_all: int = 0, nk_all: int = 0):
    """Grid (b, h, k_blocks, q_blocks), q innermost: dk/dv accumulators
    persist in VMEM across the q scan; P is recomputed from the saved
    LSE (the flash-attention backward recipe, Dao et al. Alg. 4).
    Windowed, the innermost axis counts from the k block's first q
    block (``_q_first``)."""
    kb = pl.program_id(2)
    ib = pl.program_id(3)
    nq = pl.num_programs(3)
    bq, bk = cfg.block_q, cfg.block_k
    step = ib
    if cfg.window:
        ib = _q_first(cfg, kb, offset) + step

    @pl.when(step == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    if cfg.window:
        run = _window_run(cfg, ib, kb, nk_all, offset) & (ib < nq_all)
    else:
        run = (kb * bk <= ib * bq + (bq - 1) + offset) if cfg.causal \
            else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                                   # (bq, d)
        k = k_ref[0, 0]                                   # (bk, d)
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)             # (bq, d)
        lse = lse_ref[0, 0]                               # (1, bq)
        delta = delta_ref[0, 0]                           # (1, bq)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * cfg.sm_scale
        if cfg.window:
            s = _window_mask(cfg, s, ib, kb, offset)
        elif cfg.causal:
            rows = ib * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            cols = kb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows + offset, s, _NEG_INF)
        p = jnp.exp(s - lse[0][:, None])                  # (bq, bk)
        # dV += P^T dO
        dv_s[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)
        # dS = P * (dO V^T - delta) * scale;  dK += dS^T Q
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        ds = p * (dp - delta[0][:, None]) * cfg.sm_scale
        dk_s[...] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)

    @pl.when(step == nq - 1)
    def _final():
        dk_ref[0, 0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_s, *, cfg: _Cfg, offset: int, nk_all: int = 0):
    """Grid (b, h, q_blocks, k_blocks), k innermost: dq accumulates in
    VMEM across the k scan (windowed: from ``_k_first``, as the
    forward)."""
    ib = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)
    bq, bk = cfg.block_q, cfg.block_k
    step = kb
    if cfg.window:
        kb = _k_first(cfg, ib, offset) + step

    @pl.when(step == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    if cfg.window:
        run = _window_run(cfg, ib, kb, nk_all, offset)
    else:
        run = (kb * bk <= ib * bq + (bq - 1) + offset) if cfg.causal \
            else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * cfg.sm_scale
        if cfg.window:
            s = _window_mask(cfg, s, ib, kb, offset)
        elif cfg.causal:
            rows = ib * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            cols = kb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows + offset, s, _NEG_INF)
        p = jnp.exp(s - lse[0][:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[0][:, None]) * cfg.sm_scale
        dq_s[...] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, d)

    @pl.when(step == nk - 1)
    def _final():
        dq_ref[0, 0] = dq_s[...].astype(dq_ref.dtype)


def _bwd_pallas(cfg: _Cfg, q, k, v, o, lse, do):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(cfg.block_q, sq)
    bk = min(cfg.block_k, sk)
    cfg = dataclasses.replace(cfg, block_q=bq, block_k=bk)
    nq, nk = sq // bq, sk // bk
    offset = sk - sq
    delta = _delta_pallas(cfg, o, do)                     # (b,h,1,sq)
    lse4 = lse[:, :, None, :]                             # (b,h,1,sq)

    compiler_params = _compiler_params(cfg, 4)
    dkdv_kernel = functools.partial(_dkdv_kernel, cfg=cfg, offset=offset)
    dq_kernel = functools.partial(_dq_kernel, cfg=cfg, offset=offset)
    dkdv_grid, dq_grid = (b, h, nk, nq), (b, h, nq, nk)
    # the q block a dkdv step reads, the k block a dq step reads
    q_of = lambda j, i: i                                # noqa: E731
    k_of = lambda i, j: j                                # noqa: E731
    if cfg.window:
        dkdv_kernel = functools.partial(dkdv_kernel, nq_all=nq, nk_all=nk)
        dq_kernel = functools.partial(dq_kernel, nk_all=nk)
        dkdv_grid = (b, h, nk, _q_steps(cfg, nq))
        dq_grid = (b, h, nq, _k_steps(cfg, nk))
        q_of = lambda j, i: jnp.minimum(                 # noqa: E731
            _q_first(cfg, j, offset) + i, nq - 1)
        k_of = lambda i, j: jnp.minimum(                 # noqa: E731
            _k_first(cfg, i, offset) + j, nk - 1)
    dk, dv = pl.pallas_call(
        dkdv_kernel,
        grid=dkdv_grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h_, j, i: (b_, h_, q_of(j, i), 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h_, j, i: (b_, h_, q_of(j, i), 0)),
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b_, h_, j, i: (b_, h_, 0, q_of(j, i))),
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b_, h_, j, i: (b_, h_, 0, q_of(j, i))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=cfg.interpret,
        name=_name(cfg, "bwd_dkdv"),
    )(q, k, v, do, lse4, delta)

    dq = pl.pallas_call(
        dq_kernel,
        grid=dq_grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_, k_of(i, j), 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_, k_of(i, j), 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b_, h_, i, j: (b_, h_, 0, i)),
            pl.BlockSpec((1, 1, 1, bq), lambda b_, h_, i, j: (b_, h_, 0, i)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=compiler_params,
        interpret=cfg.interpret,
        name=_name(cfg, "bwd_dq"),
    )(q, k, v, do, lse4, delta)
    return dq, dk, dv


def _flash_bwd(cfg: _Cfg, res, do):
    q, k, v, o, lse = res
    if cfg.bwd == "pallas":
        return _bwd_pallas(cfg, q, k, v, o, lse, do)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bk = min(cfg.block_k, sk)
    nk = sk // bk
    scale = cfg.sm_scale

    q32 = q.astype(jnp.float32)
    do32 = do.astype(jnp.float32)
    # D_i = sum_d dO_i * O_i — the softmax-Jacobian diagonal term.
    delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)     # (b,h,sq)
    rows = jnp.arange(sq)[:, None] + (sk - sq)    # end-aligned causality

    k_blocks = k.astype(jnp.float32).reshape(b, h, nk, bk, d)
    v_blocks = v.astype(jnp.float32).reshape(b, h, nk, bk, d)
    k_blocks = jnp.moveaxis(k_blocks, 2, 0)                    # (nk,b,h,bk,d)
    v_blocks = jnp.moveaxis(v_blocks, 2, 0)

    def step(dq_acc, blk):
        j, kb_, vb_ = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kb_) * scale
        if cfg.causal:
            cols = j * bk + jnp.arange(bk)[None, :]
            keep = cols <= rows
            if cfg.window:
                keep &= cols > rows - cfg.window
            s = jnp.where(keep, s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])                        # (b,h,sq,bk)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, do32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do32, vb_)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, kb_)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q32)
        return dq_acc, (dk, dv)

    dq0 = jnp.zeros((b, h, sq, d), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        step, dq0, (jnp.arange(nk), k_blocks, v_blocks))
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, h, sk, d)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, h, sk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------- block-size selection
def resolve_chip(chip: Optional[str]) -> str:
    """The chip name block pickers key on: ``chip`` when given, else the
    running platform's (``chip_spec`` raises on a TPU kind it does not
    know — block sizes are never guessed for an unknown device)."""
    if chip is not None:
        return chip
    from ray_tpu.parallel.mesh import chip_spec
    return chip_spec().name


def time_candidates(what: str, candidates: Sequence,
                    timer: Callable[..., float]):
    """Time each autotune candidate and return the fastest. A candidate
    the compiler rejects (a block too large for VMEM) is skipped with a
    warning; if none runs this raises — an autotuner never reports a
    winner it did not time."""
    best, best_t, last_err = None, float("inf"), None
    for cand in candidates:
        args = cand if isinstance(cand, tuple) else (cand,)
        try:
            t = timer(*args)
        except (ValueError, RuntimeError) as e:  # lowering / Mosaic
            logger.warning("%s autotune: candidate %s rejected: %s",
                           what, cand, str(e)[:200])
            last_err = e
            continue
        if t < best_t:
            best, best_t = cand, t
    if best is None:
        raise RuntimeError(
            f"{what} autotune: none of {list(candidates)} compiled"
        ) from last_err
    return best


def default_flash_blocks(seq_q: int, seq_k: int, head_dim: int,
                         chip: Optional[str] = None) -> Tuple[int, int]:
    """Chip-aware default (block_q, block_k).

    Keyed on ``parallel.mesh.chip_spec``: wider k blocks at long sequence
    amortize the per-block softmax bookkeeping against the MXU matmuls;
    large head dims shrink both blocks to keep the f32 S/P tiles plus the
    (block, head_dim) operands inside VMEM.
    """
    chip = resolve_chip(chip)
    if chip == "cpu":
        bq, bk = 256, 256
    elif head_dim >= 256:
        bq, bk = 256, 512
    elif seq_k >= 2048:
        bq, bk = 512, 1024
    else:
        bq, bk = 512, 512
    bq, bk = min(bq, seq_q), min(bk, seq_k)
    # Blocks must tile the sequence; fall back to the largest divisor.
    while seq_q % bq:
        bq //= 2
    while seq_k % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


# Winner cache: (chip, seq, head_dim, causal) -> (block_q, block_k).
_AUTOTUNE_CACHE: dict = {}

# ---- disk persistence: serving replicas must not re-time the candidate
# grid on every process start. Winners are stored as JSON keyed by
# "chip|jax_version|seq|head_dim|causal" (the jax version is part of the
# key because a compiler upgrade can move the optimum) under the
# compile-cache root (util/compile_cache.py). Only TIMED winners
# persist — chip defaults cost nothing to recompute.
_DISK_CACHE_LOADED = False


def _autotune_cache_path() -> str:
    return os.path.join(compile_cache.cache_root(), "flash_autotune.json")


def _disk_cache_enabled() -> bool:
    return os.environ.get("RAY_TPU_FLASH_AUTOTUNE_CACHE", "1") != "0"


def _disk_key(key: tuple) -> str:
    chip, seq, head_dim, causal = key
    return f"{chip}|{jax.__version__}|{seq}|{head_dim}|{int(causal)}"


def _load_disk_cache() -> None:
    """Merge persisted winners for THIS jax version into the in-memory
    cache (once per process; misses after that re-time normally)."""
    global _DISK_CACHE_LOADED
    if _DISK_CACHE_LOADED or not _disk_cache_enabled():
        return
    _DISK_CACHE_LOADED = True
    try:
        with open(_autotune_cache_path()) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return
    ver = jax.__version__
    for k, v in data.items():
        parts = k.split("|")
        if len(parts) != 5 or parts[1] != ver:
            continue
        try:
            key = (parts[0], int(parts[2]), int(parts[3]),
                   bool(int(parts[4])))
            _AUTOTUNE_CACHE.setdefault(key, (int(v[0]), int(v[1])))
        except (TypeError, ValueError, IndexError):
            continue


def persist_cached_blocks(disk_key: str, blocks: Tuple[int, int]) -> None:
    """Write-through one timed winner under an arbitrary string key
    (read-modify-write + atomic rename; concurrent replicas may race,
    last writer wins — every intermediate state is a valid cache).
    Best-effort: a read-only filesystem must not break autotuning.
    Shared by the flash and paged autotuners — foreign key formats
    coexist in the same JSON."""
    if not _disk_cache_enabled():
        return
    path = _autotune_cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        data[disk_key] = list(blocks)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


def load_cached_blocks(disk_key: str) -> Optional[Tuple[int, int]]:
    """Look one persisted winner up by its exact string key (the
    generic side of the disk cache — the flash loader's bulk merge
    stays keyed on its own 5-part format)."""
    if not _disk_cache_enabled():
        return None
    try:
        with open(_autotune_cache_path()) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    v = data.get(disk_key)
    try:
        return (int(v[0]), int(v[1])) if v is not None else None
    except (TypeError, ValueError, IndexError):
        return None


def _persist_winner(key: tuple, blocks: Tuple[int, int]) -> None:
    persist_cached_blocks(_disk_key(key), blocks)

_AUTOTUNE_CANDIDATES = (
    (256, 256), (256, 512), (512, 512), (512, 1024),
    (1024, 512), (1024, 1024),
)


def _flash_block_timer(batch, heads, seq, head_dim, causal, dtype,
                       iters: int, include_backward: bool):
    """Build a timer(block_q, block_k) -> seconds for autotuning."""
    import time

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (batch, heads, seq, head_dim)
    q, k, v = (jax.random.normal(kk, shape, dtype) for kk in ks)

    def timer(bq: int, bk: int) -> float:
        def f(q, k, v):
            o = flash_attention(q, k, v, causal=causal,
                                block_q=bq, block_k=bk)
            return jnp.sum(o.astype(jnp.float32))
        fn = jax.jit(jax.grad(f, argnums=(0, 1, 2))) \
            if include_backward else jax.jit(f)
        r = fn(q, k, v)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(q, k, v)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / iters

    return timer


def autotune_flash_blocks(seq: int, head_dim: int, *,
                          batch: int = 1, heads: int = 8,
                          causal: bool = True,
                          dtype=jnp.bfloat16,
                          candidates=None,
                          iters: int = 5,
                          include_backward: bool = True,
                          timer=None,
                          chip: Optional[str] = None) -> Tuple[int, int]:
    """One-shot block-size autotune: time a small candidate grid and cache
    the winner per ``(chip, seq, head_dim, causal)``.

    Off-TPU (and without an injected ``timer``) this returns the
    chip-aware default without running anything. ``timer`` is injectable
    for tests: a callable ``(block_q, block_k) -> seconds``. Raises when
    no candidate compiles (:func:`time_candidates`).
    """
    chip = resolve_chip(chip)
    key = (chip, int(seq), int(head_dim), bool(causal))
    if key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]
    _load_disk_cache()   # persisted winners from earlier processes
    if key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]

    default = default_flash_blocks(seq, seq, head_dim, chip=chip)
    cands = [c for c in (candidates or _AUTOTUNE_CANDIDATES)
             if seq % min(c[0], seq) == 0 and seq % min(c[1], seq) == 0]
    if default not in cands:
        cands.insert(0, default)
    if timer is None:
        if jax.default_backend() != "tpu" or len(cands) <= 1:
            return default
        timer = _flash_block_timer(batch, heads, seq, head_dim, causal,
                                   dtype, iters, include_backward)
    clamped = list(dict.fromkeys(
        (min(bq, seq), min(bk, seq)) for bq, bk in cands))
    best = time_candidates("flash", clamped, timer)
    _AUTOTUNE_CACHE[key] = best
    _persist_winner(key, best)   # timed winner: survive process restarts
    return best


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    backward: str = "pallas",
                    window: int = 0) -> jnp.ndarray:
    """Flash attention over (batch, heads, seq, head_dim) arrays.

    ``window > 0`` (causal calls only): a sliding-window layer, a query
    attends the ``window`` keys up to and including its own position.
    The calls are then named ``flash_window_fwd`` / ``_bwd_dkdv`` /
    ``_bwd_dq`` / ``_bwd_delta`` and their innermost grid axis walks only
    the blocks the window reaches; ``window=0`` is the kernels as they
    were, name and instructions.

    Requires seq divisible by the (clamped) block sizes; ``block_q`` /
    ``block_k`` left as ``None`` (or 0) pick chip-aware defaults
    (:func:`default_flash_blocks`). ``interpret=True`` runs the Pallas
    kernels in interpreter mode (CPU tests). ``backward`` selects the VJP
    implementation: "pallas" (VMEM-blocked dq/dk/dv kernels recomputing P
    from the saved LSE) or "xla" (the lax.scan formulation, kept for
    parity checks).
    """
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if backward not in ("pallas", "xla"):
        raise ValueError(f"backward must be 'pallas' or 'xla', "
                         f"got {backward!r}")
    if window < 0 or window and not causal:
        raise ValueError(f"window {window}: a sliding window is a form "
                         f"of causal attention, 0 or more keys wide")
    if not block_q or not block_k:
        dq_, dk_ = default_flash_blocks(q.shape[2], k.shape[2], d,
                                        chip="cpu" if interpret else None)
        block_q = block_q or dq_
        block_k = block_k or dk_
    cfg = _Cfg(causal=causal, sm_scale=float(sm_scale),
               block_q=block_q, block_k=block_k, interpret=interpret,
               bwd=backward, window=int(window))
    return _flash(cfg, q, k, v)
