"""Latent (MLA) attention over a one-pool paged cache.

A latent cache holds, a token and layer, ONE row for every head: the
normed latent ``c`` (``rank`` numbers) and the rotated key ``k_rope``
(``rope`` numbers), padded to whole lane tiles (:func:`latent_row_width`
— the TPU keeps a pool's minor dimension in 128-lane tiles whatever the
program asks for, and the kernel's page copies must be whole tiles). A
head's key is ``(c · W_UK[h] | k_rope)`` and its value ``c · W_UV[h]``;
neither is ever stored.

The attention is **absorbed**: ``W_UK`` goes into the query (``q_abs =
q_nope · W_UK[h]ᵀ``, ``rank`` wide), scores are taken against the latent
rows themselves, the softmax weights sum latent rows, and ``W_UV``
expands the ``rank``-wide result once a query. A step reads each cached
row once for all heads: the form of the paged kernel
(:func:`ray_tpu.ops.paged_flash.paged_flash_attention` with ``v_width``:
one key head whose page's first ``rank`` columns are the value), for
chunks as for decode. (Expanding a block of cached rows to per-head K
and V costs fewer FLOPs a pair; as plain XLA it measured slower at 8k
and 30k of context, PERF.md, PR 37. It comes back when it is a kernel.)

``impl``: "auto" (the kernel on a TPU, else the plain path) | "kernel"
| "interpret" | "reference" (the plain XLA path), as
:func:`ray_tpu.ops.attention.paged_attention`.

With a learned key selection (``select``) the heads attend the latent
rows an indexer ranks highest and no other
(:func:`ray_tpu.ops.sparse_attention.sparse_latent_attention`: plain XLA
on every platform, a decode step gathering the selected rows; the
paged kernel's ``chosen`` operand, which the per-head form's decode
step takes on a TPU, is there for a latent pool as well and no caller
here passes it yet): the absorbed query and the expansion after the
softmax are the ones here.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _resolve
from ray_tpu.ops.paged_flash import paged_flash_attention, sublane_tile

_NEG_INF = -1e30

#: cached rows a step of the plain path folds (whole pages of them)
_KEY_BLOCK = 512


def latent_row_width(rank: int, rope: int) -> int:
    """Columns of a latent pool's row: ``rank + rope`` up to whole
    128-lane tiles (512 + 64 -> 640; the padding columns stay zero)."""
    return -(-(rank + rope) // 128) * 128


def latent_attention(q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                     w_uk: jnp.ndarray, w_uv: jnp.ndarray,
                     pool: jnp.ndarray, block_tables: jnp.ndarray,
                     q_positions: jnp.ndarray, *, layer,
                     lens: jnp.ndarray, sm_scale: float,
                     impl: str = "auto",
                     block_r: Optional[int] = None,
                     select: Optional[tuple] = None) -> jnp.ndarray:
    """Causal attention of new-token queries against a latent pool.

    ``q_nope [B, C, H, nope]``, ``q_rope [B, C, H, rope]`` (rotated) at
    absolute ``q_positions [B, C]``; ``w_uk [rank, H, nope]``, ``w_uv
    [rank, H, v]`` (the two halves of the stored ``wkv_b``); ``pool``
    the WHOLE ``[L, N, 1, bs, row]`` latent pool with ``layer`` the
    layer attended (never sliced, as every pool); ``lens [B]`` the live
    tokens after this call's writes. ``select``: ``(qi, wi, ki_pool,
    topk)``, the indexer's queries ``[B, C, Hi, Di]`` and head weights
    ``[B, C, Hi]`` of the new tokens, the whole pool of index keys and
    how many keys a query attends. Returns ``[B, C, H, v]``."""
    rank, rope = w_uk.shape[0], q_rope.shape[-1]
    bs, row = pool.shape[3:]
    dt = q_nope.dtype
    layer = jnp.asarray(layer, jnp.int32)
    unfit = None
    if rank % 128:
        unfit = f"latent rank {rank} % 128 != 0"
    elif bs % sublane_tile(pool.dtype):
        unfit = f"block_size {bs} % {sublane_tile(pool.dtype)} != 0"
    if select is None:
        choice = _resolve("latent", impl, "kernel", unfit)
    with jax.named_scope("mla_q"):
        q_abs = jnp.einsum("bchd,rhd->bchr", q_nope, w_uk.astype(dt))
    if select is not None:
        from ray_tpu.ops.sparse_attention import sparse_latent_attention
        qi, wi, ki_pool, topk = select
        o_lat = sparse_latent_attention(
            q_abs, q_rope, qi, wi, pool, ki_pool, block_tables,
            q_positions, lens, layer=layer, topk=topk, sm_scale=sm_scale)
    elif choice == "reference":
        o_lat = _blocked(q_abs, q_rope, pool, block_tables, q_positions,
                         layer, lens, sm_scale)
    else:
        # the kernel's one query row: (absorbed | rope | zeros to the
        # pool's row)
        q_lat = jnp.concatenate(
            [q_abs, q_rope,
             jnp.zeros(q_rope.shape[:-1] + (row - rank - rope,), dt)], -1)
        with jax.named_scope("mla_attn"):
            o_lat = paged_flash_attention(
                q_lat, pool, None, block_tables, q_positions, lens,
                layer=layer, sm_scale=sm_scale, block_r=block_r,
                interpret=choice == "interpret", v_width=rank)
    with jax.named_scope("mla_out"):
        return jnp.einsum("bchr,rhd->bchd", o_lat, w_uv.astype(dt))


def _blocked(q, q_rope, pool, block_tables, q_positions, layer, lens,
             sm_scale, chosen=None):
    """The plain XLA path: an online softmax over blocks of
    ``_KEY_BLOCK`` cached rows gathered by table slot; blocks past the
    longest sequence's live rows are not run. ``q`` is the absorbed
    query ``[B, C, H, rank]`` and the result the latent-wide ``[B, C,
    H, rank]``. ``chosen [B, C, W]`` (``W`` at least the live rows):
    the keys each query attends, none of them ahead of it, in place of
    every key up to its own position."""
    b, c, h, rank = q.shape
    rope = q_rope.shape[-1]
    bs = pool.shape[3]
    t = block_tables.shape[1]
    tb = max(1, min(_KEY_BLOCK // bs, t))      # table slots a block
    n_blocks = -(-t // tb)
    bt = jnp.pad(block_tables, ((0, 0), (0, n_blocks * tb - t)))
    if chosen is not None and chosen.shape[-1] < n_blocks * tb * bs:
        chosen = jnp.pad(chosen, ((0, 0), (0, 0), (
            0, n_blocks * tb * bs - chosen.shape[-1])))
    dt = q.dtype
    f32 = jnp.float32

    def fold(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(bt, i * tb, tb, axis=1)
        rows = pool[layer, ids, 0].reshape(b, tb * bs, -1)   # [B, K, row]
        lat, k_rope = rows[..., :rank], rows[..., rank:rank + rope]
        s = jnp.einsum("bchr,bkr->bhck", q, lat,
                       preferred_element_type=f32)
        s = (s + jnp.einsum("bchd,bkd->bhck", q_rope, k_rope,
                            preferred_element_type=f32)) * sm_scale
        if chosen is None:
            key_pos = i * (tb * bs) + jnp.arange(tb * bs, dtype=jnp.int32)
            mask = key_pos[None, None, :] <= q_positions[:, :, None]
        else:
            mask = jax.lax.dynamic_slice_in_dim(chosen, i * (tb * bs),
                                                tb * bs, axis=2)
        s = jnp.where(mask[:, None], s, _NEG_INF)
        m_next = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_next)
        p = jnp.exp(s - m_next[..., None])
        # a row that has met no key yet (its block lies ahead) carries
        # m = -1e30: exp(0) = 1 of masked scores must not count
        p = jnp.where(mask[:, None], p, 0.0)
        l = alpha * l + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhck,bkr->bhcr", p.astype(dt), lat,
                        preferred_element_type=f32)
        return m_next, l, acc * alpha[..., None] + pv

    with jax.named_scope("mla_attn"):
        live = jnp.clip(-(-jnp.max(lens) // (tb * bs)), 1, n_blocks)
        init = (jnp.full((b, h, c), _NEG_INF, f32),
                jnp.zeros((b, h, c), f32),
                jnp.zeros((b, h, c, rank), f32))
        _, l, acc = jax.lax.fori_loop(0, live, fold, init)
        # a row that met no key is zero whatever its window's pages
        # hold (a zero weight times a NaN there would be a NaN)
        seen = l > 0.0
        out = jnp.where(seen[..., None],
                        acc / jnp.where(seen, l, 1.0)[..., None],
                        0.0).astype(dt)
        return out.transpose(0, 2, 1, 3)                 # [B, C, H, rank]
