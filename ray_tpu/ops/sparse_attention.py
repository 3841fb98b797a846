"""Learned sparse attention over the paged cache: an indexer ranks every
visible key of a query, the ``topk`` best are selected exactly, and
attention runs over those keys alone (DeepSeek-Sparse-Attention's
"lightning indexer"; ``TransformerConfig.index_topk``).

Two forms of cache, one indexer. Over per-head K/V
(:func:`sparse_paged_attention`) a page holds three kinds of state
under one block table: K and V (``[L, N, kv_heads, bs, D]``) and the
indexer's keys ``kI`` (``[L, N, 1, bs, Di]``, one shared key head).
Over a latent (MLA) cache (:func:`sparse_latent_attention`) it holds
two: the latent rows (``[L, N, 1, bs, row]``, every head's key and
value, :mod:`ray_tpu.ops.latent_attention`) and ``kI``; the attention
over the selected rows is the absorbed one. The pools come in whole
with a layer index, as in :mod:`ray_tpu.ops.paged_flash`, so a step
program's layer scan carries and writes them in place.

The indexer and the selection are plain XLA, one form on every
platform, and so is everything over a latent cache. Over per-head K/V
the attention itself has two forms. A decode step (:func:`decode_choice`)
is XLA's gather of the selected rows, or the paged kernel
(:mod:`ray_tpu.ops.paged_flash`) reading every live page with the
selection as a mask operand, which the TPU takes where the window is
short enough for streamed pages to beat gathered rows. A chunk
(:func:`chunk_choice`) is a tiled masked online softmax in XLA
(:func:`_chunk_masked`), or the same kernel with a mask row a token,
which the TPU takes wherever the page tiles (both read every live
page; the kernel keeps its score tiles in VMEM). Off the TPU the plain
forms run; the kernel is tested in interpret mode. Work follows the
live context, not the window: the loops over key tiles stop at the
longest live sequence.

- :func:`index_scores` — ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] .
  kI[s])`` against the cached ``kI``, tiled over keys.
- :func:`topk_mask` — the exact ``k`` largest of each row as a mask
  (ties to the lower index, as a stable descending sort would): the
  k-th largest value is found two bits a pass on an order-preserving
  integer image of the scores, 16 counting passes and no sort.
  ``jax.lax.approx_max_k`` would be a different model.
- a decode step (one query a sequence) either takes ``jax.lax.top_k``
  of its row and reads K and V of the selected tokens only, ``topk``
  rows a kv head, sequence and layer whatever the context, or hands
  ``topk_mask`` of its row to the paged kernel, which reads the live
  pages once; a prefill chunk (thousands of queries, each with a
  selection of its own) masks an online-softmax pass over the live
  pages, XLA's tiled one or the paged kernel's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _paged_unfit, _resolve

_NEG_INF = -1e30

#: seconds XLA's gather takes a selected row of a per-head pool on a
#: v5e: 256 B rows, 12.6-14.0 ns at windows of 32k-128k whatever the
#: fill (tools/sparse_decode_crossover.py; PERF.md section 6, PR 59)
_GATHER_S_PER_ROW = 13.5e-9

#: bytes a second at which the paged kernel's masked decode call reads
#: full tables on a v5e: 341-365 GB/s at 16k-128k live keys a sequence,
#: pages of 16 KB (the same tool and runs)
_MASKED_BYTES_PER_S = 350e9

#: query rows of a chunk handled at a time
_ROW_BLOCK = 256

#: float32 bytes one tile of scores (query rows x keys) may take: sets
#: how many pages a loop iteration covers
_TILE_BYTES = 256 << 20


def _pages_per_tile(rows: int, table_len: int, block_size: int) -> int:
    """Pages per key tile so that ``rows x keys`` float32 fits
    ``_TILE_BYTES``: a power of two, at most the table."""
    keys = max(block_size, _TILE_BYTES // (4 * max(rows, 1)))
    pages = 1 << int(math.log2(max(1, keys // block_size)))
    return min(pages, 1 << max(0, (table_len - 1).bit_length()))


def _pad_table(block_tables, pages: int):
    """The block table padded to a whole number of tiles of ``pages``;
    the padding names block 0, whose keys lie past every query's
    position."""
    pad = -block_tables.shape[1] % pages
    return jnp.pad(block_tables, ((0, 0), (0, pad))) if pad \
        else block_tables


def _live_tiles(lens, keys_per_tile: int):
    return (jnp.max(lens).astype(jnp.int32) + keys_per_tile - 1) \
        // keys_per_tile


@jax.named_scope("indexer_scores")
def index_scores(qi, wi, ki_pool, block_tables, positions, lens, layer):
    """Indexer scores of new-token queries against the cached ``kI``.

    ``qi [B, C, Hi, Di]`` (rotated), ``wi [B, C, Hi]`` float32 (scaled),
    ``ki_pool [L, N, 1, bs, Di]``, ``positions [B, C]`` absolute,
    ``lens [B]`` live tokens. Returns ``[B, C, W]`` float32, ``W`` the
    (tile-padded) window: ``-inf`` where key ``s > positions[b, c]``."""
    b, c, hi, di = qi.shape
    bs = ki_pool.shape[3]
    pages = _pages_per_tile(b * c * hi, block_tables.shape[1], bs)
    bt = _pad_table(block_tables, pages)
    keys = pages * bs
    window = bt.shape[1] * bs

    def tile(j, out):
        ids = jax.lax.dynamic_slice_in_dim(bt, j * pages, pages, axis=1)
        kb = ki_pool[layer, ids, 0].reshape(b, keys, di)
        s = jnp.einsum("bchd,bkd->bchk", qi, kb,
                       preferred_element_type=jnp.float32)
        # on the VPU in float32: a dot would round both sides to bf16
        s = jnp.sum(jax.nn.relu(s) * wi[..., None], axis=2)
        key_pos = j * keys + jnp.arange(keys, dtype=jnp.int32)
        s = jnp.where(key_pos[None, None] <= positions[:, :, None], s,
                      -jnp.inf)
        return jax.lax.dynamic_update_slice_in_dim(out, s, j * keys,
                                                   axis=2)

    return jax.lax.fori_loop(
        0, _live_tiles(lens, keys), tile,
        jnp.full((b, c, window), -jnp.inf, jnp.float32))


@jax.named_scope("select")
def topk_mask(scores, k: int):
    """Mask of the ``k`` largest entries of each row of ``scores [...,
    W]`` (float32, ``-inf`` = not a candidate): every candidate while a
    row has at most ``k``; equal scores go to the lower index. Exact."""
    visible = scores > -jnp.inf
    scores = jnp.where(scores == 0.0, 0.0, scores)        # -0.0 == +0.0
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    # unsigned image with the floats' own order; 0 = not a candidate
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    u = jnp.where(visible, u, jnp.uint32(0))

    def count(cand):
        return jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)

    def two_bits(i, t):
        # the largest of t, t|lo, t|hi, t|hi|lo with k entries at or
        # above it: three counts off one read of u
        hi = jnp.uint32(1 << 31) >> (2 * i).astype(jnp.uint32)
        lo = hi >> 1
        return jnp.where(
            count(t | hi | lo) >= k, t | hi | lo, jnp.where(
                count(t | hi) >= k, t | hi, jnp.where(
                    count(t | lo) >= k, t | lo, t)))

    # the largest t with at least k entries >= t: the k-th largest value
    # (0 while the row has fewer than k candidates)
    t = jax.lax.fori_loop(0, 16, two_bits,
                          jnp.zeros(u.shape[:-1], jnp.uint32))[..., None]
    equal = u == t
    need = k - jnp.sum(u > t, axis=-1, dtype=jnp.int32, keepdims=True)
    tied = (t > 0) & (jnp.sum(equal, axis=-1, dtype=jnp.int32,
                              keepdims=True) > need)
    width = u.shape[-1]

    def last_equal_taken():
        # where values tie at the threshold, the first ``need`` of them
        # by index: the index of the need-th
        nth = jnp.cumsum(equal, axis=-1, dtype=jnp.int32) == need
        cut = jnp.argmax(nth & equal, axis=-1, keepdims=True)
        return jnp.where(tied, cut.astype(jnp.int32), width)

    cut = jax.lax.cond(jnp.any(tied), last_equal_taken,
                       lambda: jnp.full(tied.shape, width, jnp.int32))
    index = jax.lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)
    return ((u > t) | (equal & (index <= cut))) & visible


def masked_read_wins(topk: int, kv_heads: int, row_bytes: int,
                     window: int, pools: int = 2) -> bool:
    """Whether a decode step over a ``window`` of keys is cheaper as one
    masked read of every page than as a gather of the selected rows.
    The gather fetches ``topk`` rows a kv head out of each of ``pools``
    pools at a cost a row that no context changes; the masked read
    streams the window, ``row_bytes`` a key and kv head, at the rate the
    kernel reaches. From shapes alone (the heads and pools multiply both
    sides, and are there so that each side reads as its seconds): rows
    of 256 B under top-2048 cross near 38k keys, by the WINDOW, which a
    full table reaches; at the fills a cell sees the read is shorter."""
    rows = pools * kv_heads
    gather_s = topk * rows * _GATHER_S_PER_ROW
    masked_s = window * rows * row_bytes / _MASKED_BYTES_PER_S
    return masked_s < gather_s


def decode_choice(impl: str, topk: int, k_pool, table_len: int,
                  record: bool = False) -> str:
    """What a decode step's selected attention over per-head K/V runs
    as under ``impl``: the paged kernel with the selection as a mask
    ("kernel" | "interpret") or the gather of the selected rows
    ("reference": off the TPU, where the page does not tile, and where
    :func:`masked_read_wins` says the window is too long)."""
    kvh, bs, d = k_pool.shape[-3:]
    unfit = _paged_unfit(k_pool, k_pool)       # a head's q is a row wide
    if not unfit and not masked_read_wins(
            topk, kvh, d * k_pool.dtype.itemsize, table_len * bs):
        unfit = f"gathering {topk} rows beats reading {table_len * bs}"
    return _resolve("sparse_decode", impl, "kernel", unfit, record)


def chunk_choice(impl: str, k_pool, record: bool = False) -> str:
    """What a chunk's selected attention over per-head K/V runs as under
    ``impl``: the paged kernel with the selections as a mask ("kernel" |
    "interpret") or :func:`_chunk_masked` ("reference": off the TPU, or
    where the page does not tile). Both read every live page, so no
    length decides between them."""
    return _resolve("sparse_chunk", impl, "kernel",
                    _paged_unfit(k_pool, k_pool), record)


def _grouped(q, kv_heads: int):
    b, c, h, d = q.shape
    return q.reshape(b, c, kv_heads, h // kv_heads, d)


@jax.named_scope("sparse_attn")
def _decode_selected(q, k_pool, v_pool, block_tables, scores, layer,
                     topk: int, sm_scale: float):
    """One query a sequence: ``top_k`` of its score row, then K and V of
    the selected tokens alone, gathered row by row out of the pool."""
    b, _, h, d = q.shape
    kvh, bs = k_pool.shape[2:4]
    with jax.named_scope("select"):
        val, idx = jax.lax.top_k(scores[:, 0],
                                 min(topk, scores.shape[-1]))
    chosen = val > -jnp.inf
    bid = jnp.take_along_axis(block_tables, idx // bs, axis=1)[..., None]
    slot = (idx % bs)[..., None]
    head = jnp.arange(kvh, dtype=jnp.int32)
    ks = k_pool[layer, bid, head, slot]                  # [B, k, KVH, D]
    vs = v_pool[layer, bid, head, slot]
    qg = _grouped(q, kvh)[:, 0]                          # [B, KVH, r, D]
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, ks,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(chosen[:, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", p.astype(vs.dtype), vs,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, d).astype(q.dtype)


@jax.named_scope("sparse_attn")
def _chunk_masked(q, k_pool, v_pool, block_tables, chosen, lens, layer,
                  sm_scale: float):
    """A chunk of queries, each with a selection of its own (``chosen
    [B, C, W]``): online softmax over tiles of the live pages, a key
    counted only where its query selected it."""
    b, c, h, d = q.shape
    kvh, bs = k_pool.shape[2:4]
    rep = h // kvh
    pages = _pages_per_tile(b * c * h, block_tables.shape[1], bs)
    bt = _pad_table(block_tables, pages)
    keys = pages * bs
    if chosen.shape[-1] < bt.shape[1] * bs:
        chosen = jnp.pad(chosen, ((0, 0), (0, 0),
                                  (0, bt.shape[1] * bs - chosen.shape[-1])))
    qg = _grouped(q, kvh)

    def tile(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(bt, j * pages, pages, axis=1)

        def page_rows(pool):                  # [B, KVH, keys, D]
            return pool[layer, ids].transpose(0, 2, 1, 3, 4) \
                .reshape(b, kvh, keys, d)
        kb, vb = page_rows(k_pool), page_rows(v_pool)
        sel = jax.lax.dynamic_slice_in_dim(chosen, j * keys, keys,
                                           axis=2)[:, None, None]
        s = jnp.einsum("bcgrd,bgkd->bgrck", qg, kb,
                       preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(sel, s, _NEG_INF)
        m_next = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_next)
        # a row none of whose keys is selected so far has m = -1e30 and
        # would count exp(0) a key
        p = jnp.where(sel, jnp.exp(s - m_next[..., None]), 0.0)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrck,bgkd->bgrcd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return m_next, l, acc

    shape = (b, kvh, rep, c)
    m, l, acc = jax.lax.fori_loop(
        0, _live_tiles(lens, keys), tile,
        (jnp.full(shape, _NEG_INF, jnp.float32),
         jnp.zeros(shape, jnp.float32),
         jnp.zeros(shape + (d,), jnp.float32)))
    o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, c, h, d).astype(q.dtype)


@jax.named_scope("sparse_attn")
def _through_kernel(q, k_pool, v_pool, block_tables, positions, lens,
                    layer, sm_scale: float, block_r, interpret: bool,
                    chosen):
    """The queries' attention over their live pages through the paged
    kernel, a key counted only where ``chosen [B, C, W]`` says its query
    selected it."""
    from ray_tpu.ops.paged_flash import paged_flash_attention
    return paged_flash_attention(
        q, k_pool, v_pool, block_tables, positions, lens, layer=layer,
        sm_scale=sm_scale, block_r=block_r, interpret=interpret,
        chosen=chosen)


def sparse_paged_attention(q, qi, wi, k_pool, v_pool, ki_pool,
                           block_tables, positions, lens, *, layer,
                           topk: int, sm_scale=None, impl: str = "auto",
                           block_r=None):
    """Attention of new-token queries over the ``topk`` cached keys the
    indexer ranks highest for each (all of them while a query sees at
    most ``topk``), the new tokens' own K, V and ``kI`` having been
    written first. ``q [B, C, H, D]``, ``qi [B, C, Hi, Di]``, ``wi [B,
    C, Hi]``; pools whole, ``layer`` an int32 scalar; ``positions [B,
    C]``, ``lens [B]`` as for :func:`ray_tpu.ops.paged_attention`. Rows
    at positions past ``lens`` (a chunk's padding) come back zero or
    attend live keys: the caller's to discard. ``impl`` / ``block_r``:
    as for :func:`ray_tpu.ops.paged_attention`, for the calls of the
    paged kernel (:func:`decode_choice`, :func:`chunk_choice`)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    c = q.shape[1]
    if c == 1:
        scores = index_scores(qi, wi, ki_pool, block_tables, positions,
                              lens, layer)
        choice = decode_choice(impl, topk, k_pool, block_tables.shape[1],
                               record=True)
        if choice != "reference":
            return _through_kernel(q, k_pool, v_pool, block_tables,
                                   positions, lens, layer, sm_scale,
                                   block_r, choice == "interpret",
                                   topk_mask(scores, topk))
        # the scores' window is the table padded to whole tiles
        bt = _pad_table(block_tables,
                        scores.shape[-1] // k_pool.shape[3])
        return _decode_selected(q, k_pool, v_pool, bt, scores, layer,
                                topk, sm_scale)

    choice = chunk_choice(impl, k_pool, record=True)

    def chunk(q, qi, wi, positions, lens):
        scores = index_scores(qi, wi, ki_pool, block_tables, positions,
                              lens, layer)
        chosen = topk_mask(scores, topk)
        if choice == "reference":
            return _chunk_masked(q, k_pool, v_pool, block_tables, chosen,
                                 lens, layer, sm_scale)
        return _through_kernel(q, k_pool, v_pool, block_tables, positions,
                               lens, layer, sm_scale, block_r,
                               choice == "interpret", chosen)

    return _in_row_blocks(chunk, (q, qi, wi), positions, lens)


def _in_row_blocks(chunk, per_row, positions, lens):
    """``chunk(*per_row, positions, lens)`` over a chunk's queries in
    blocks of ``_ROW_BLOCK`` rows, as many as hold a live row: a
    question of 64 tokens behind a cached document fills one block of a
    2048-row chunk, and a block's keys end at its own last row. The
    result has the shape of ``per_row[0]``; rows of blocks not run are
    zero."""
    c, rows = positions.shape[1], _ROW_BLOCK
    if c <= rows or c % rows:
        return chunk(*per_row, positions, lens)

    def block(i, out):
        def rows_of(x):
            return jax.lax.dynamic_slice_in_dim(x, i * rows, rows, axis=1)
        pos = rows_of(positions)
        o = chunk(*(rows_of(x) for x in per_row), pos,
                  jnp.minimum(lens, pos[:, -1] + 1))
        return jax.lax.dynamic_update_slice_in_dim(out, o, i * rows,
                                                   axis=1)

    live = jnp.max(lens - positions[:, 0]).astype(jnp.int32)
    return jax.lax.fori_loop(0, (live + rows - 1) // rows, block,
                             jnp.zeros_like(per_row[0]))


@jax.named_scope("sparse_latent_attn")
def _decode_selected_latent(q_abs, q_rope, pool, block_tables, idx, chosen,
                            layer, sm_scale: float):
    """One query a sequence over the latent rows ``idx [B, k]`` names
    (``chosen``: which of them count), gathered row by row out of the
    pool: every head's absorbed query against the ``k`` rows, the
    softmax weights summing their first ``rank`` columns."""
    rank, rope = q_abs.shape[-1], q_rope.shape[-1]
    bs = pool.shape[3]
    bid = jnp.take_along_axis(block_tables, idx // bs, axis=1)
    rows = pool[layer, bid, 0, idx % bs]                   # [B, k, row]
    lat, k_rope = rows[..., :rank], rows[..., rank:rank + rope]
    s = jnp.einsum("bhr,bkr->bhk", q_abs[:, 0], lat,
                   preferred_element_type=jnp.float32)
    s = (s + jnp.einsum("bhd,bkd->bhk", q_rope[:, 0], k_rope,
                        preferred_element_type=jnp.float32)) * sm_scale
    p = jax.nn.softmax(jnp.where(chosen[:, None], s, _NEG_INF), axis=-1)
    o = jnp.einsum("bhk,bkr->bhr", p.astype(lat.dtype), lat,
                   preferred_element_type=jnp.float32)
    return o[:, None].astype(q_abs.dtype)


def sparse_latent_attention(q_abs, q_rope, qi, wi, pool, ki_pool,
                            block_tables, positions, lens, *, layer,
                            topk: int, sm_scale: float):
    """Absorbed latent attention of new-token queries over the ``topk``
    cached rows the indexer ranks highest for each (all of them while a
    query sees at most ``topk``), the new tokens' own latent row and
    ``kI`` having been written first. ``q_abs [B, C, H, rank]`` (the
    query with ``W_UK`` folded in), ``q_rope [B, C, H, rope]``, ``qi
    [B, C, Hi, Di]``, ``wi [B, C, Hi]``; ``pool [L, N, 1, bs, row]`` and
    ``ki_pool [L, N, 1, bs, Di]`` whole, ``layer`` an int32 scalar.
    Returns the latent-wide ``[B, C, H, rank]``, for ``W_UV`` to
    expand. A decode step takes ``jax.lax.top_k`` of its row and
    gathers the selected rows, ``topk`` a sequence and layer whatever
    the context; a chunk's queries each have a selection of their own,
    which masks the plain latent pass over the live pages
    (:func:`ray_tpu.ops.latent_attention._blocked`)."""
    from ray_tpu.ops.latent_attention import _blocked
    if q_abs.shape[1] == 1:
        scores = index_scores(qi, wi, ki_pool, block_tables, positions,
                              lens, layer)
        with jax.named_scope("select"):
            val, idx = jax.lax.top_k(scores[:, 0],
                                     min(topk, scores.shape[-1]))
        # the scores' window is the table padded to whole tiles
        bt = _pad_table(block_tables, scores.shape[-1] // pool.shape[3])
        return _decode_selected_latent(q_abs, q_rope, pool, bt, idx,
                                       val > -jnp.inf, layer, sm_scale)

    def chunk(q_abs, q_rope, qi, wi, positions, lens):
        scores = index_scores(qi, wi, ki_pool, block_tables, positions,
                              lens, layer)
        chosen = topk_mask(scores, topk)
        with jax.named_scope("sparse_latent_attn"):
            return _blocked(q_abs, q_rope, pool, block_tables, positions,
                            layer, lens, sm_scale, chosen=chosen)

    return _in_row_blocks(chunk, (q_abs, q_rope, qi, wi), positions, lens)
