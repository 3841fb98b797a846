"""Pallas paged-attention kernel — the length-aware serving fast path.

The serving hot loop attends a handful of new-token queries per
sequence against a paged KV cache (``[n_layers, num_blocks, kv_heads,
block_size, head_dim]`` pool + per-sequence block tables). The pool is
handed over WHOLE with a layer index, never sliced: a step program's
layer scan carries it and writes it in place, and a slice per layer
would copy that layer in and out of every step. The pure-XLA reference
(:func:`ray_tpu.ops.attention.paged_attention`) gathers the WHOLE
table window every step — work is O(B · T · block_size) regardless of
how many tokens a sequence actually holds. This kernel makes decode
work proportional to **live tokens**, a **group of P pages a grid
step**:

- grid ``(batch, kv_head_groups, q_row_blocks, ceil(T / P))`` with the
  page-group axis innermost so the online-softmax accumulators
  (m, l, acc in f32 VMEM scratch) persist across a sequence's pages;
- the pools stay in HBM (``memory_space=pl.ANY``) and the kernel
  fetches pages itself. The block table, per-sequence ``lens`` and the
  layer index ride **scalar prefetch**
  (:class:`pltpu.PrefetchScalarGridSpec`); a step reads them to start
  one async copy a live page, HBM page ``(layer, bt[b, t·P + j], this
  step's kv heads)`` to slot ``j`` of a ``[2, P, heads, block_size,
  head_dim]`` VMEM buffer, K and V each, all 2·P at once. The two
  halves alternate: step ``t`` starts group ``t + 1``'s copies, waits
  for its own (started by step ``t − 1``; group 0's by step 0 itself,
  the one exposed fetch a sequence) and folds them. Only q, the row
  positions and the output are BlockSpec operands: the BlockSpec
  pipeline spends ~0.08 µs an operand a step on its index map and its
  changed-or-not test whether or not a copy follows, so with a
  BlockSpec a page a dead step costs as much as the table slots it
  covers, grouped or not (measured on a v5e; PERF.md, PR 36);
- the body reads the P pages of one kv head as ONE ``(P·block_size,
  head_dim)`` K tile and one V tile (a page is a whole number of
  sublane tiles, so the join moves nothing) and runs one ``q·Kᵀ``, one
  online-softmax update and one ``p·V`` a head a step: the step's
  fixed cost is paid once for P pages, and the products are P·block_size
  key columns wide instead of one page's;
- groups past ``last = (pages − 1) // P`` are **skipped**: no copy is
  started for them and ``pl.when`` skips the body, so a dead step
  costs what the three BlockSpec operands' bookkeeping costs
  (~0.05 µs). ``pages`` is a row block's own: **its bound is its
  highest live position** (``top``, the largest ``0 <= position <
  lens[b]`` among its rows: pages ``0 .. top // block_size``), which
  with one row block, a decode or a verify call, is the length's
  ``ceil(lens[b] / block_size)``. A chunk's row block of padding alone
  (most of a short question's chunk) has no live row and an early
  block of a document's chunk stops at its own diagonal. A sequence
  with ``lens[b] <= 0`` holds nothing (the engine's decode slot with
  no sequence in it). Either way ``pages = 0`` and ``last = −1``:
  every step of the sweep is a dead one, no page of the table is read
  and the rows come back zero. In a partly live last group
  the pages past the last live one are not fetched either: their key
  columns are masked out (``key position < pages · block_size``)
  beside the causal mask, and their V rows are zeroed (a zero weight
  times whatever the buffer held would otherwise be free to be NaN);
- P is the largest of ``_PAGE_GROUPS`` whose buffers fit
  ``_VMEM_BUDGET`` and that the table has a use for
  (:func:`paged_pages_per_step`): it follows the page's bytes (kv heads
  a step × block_size × head_dim × itemsize), the row block and the
  table's length, nothing else. T need not divide by P;
- GQA is handled by **indexing kv heads in-kernel**: queries are
  regrouped host-side to ``[B, kv_heads, C·group, D]`` rows (a
  transpose of the tiny q tensor, not of the cache); a grid step loads
  the pages of ``heads_per_step`` kv heads and walks them with a static
  loop — the cache is never repeated or copied;
- a **chunk call's step is bound by its page copies**, not by its
  arithmetic: a step starts 2·P of them whatever their size, about
  50 ns each on a v5e (64 copies of one kv head's 4 KB: 3.4 µs, where
  folding them into 512 query rows is 1.8; PERF.md section 6, PR 61),
  and a copy of four heads' rows of a page costs what one head's does.
  So the dense form's chunk call on a full-attention layer serves up
  to four kv heads from one fetch of a group
  (:func:`_chunk_heads_per_step`: up to ``_MAX_ROWS_PER_CHUNK_STEP``
  query rows a step, while the step still holds the P pages it held
  with one): a quarter of the copies and of the sweeps, the same
  arithmetic a head in the same order, every row bit for bit.

Every operand tiles the way Mosaic requires. A page is a
``(block_size, head_dim)`` tile per kv head because ``kv_heads`` sits
AHEAD of ``block_size`` in the cache, so a head is picked on an untiled
leading dim. Row positions travel as a ``[B, rows, 1]`` column (no
lane-to-sublane relayout in the kernel), and rows are padded to the
sublane tile of the query dtype (8 rows for 32-bit, 16 for bf16).

Rows are padded to ``block_r`` (chip-aware default via
:func:`default_paged_block_r`; :func:`autotune_paged_block_r` times a
candidate grid once and persists the winner through the SAME on-disk
table as ``autotune_flash_blocks``). Padded rows carry position −1 —
fully masked, dropped on unpack. The rows a caller pads with (a chunk's
tail, positions at and past ``lens``) see the keys up to their block's
bound or none, and are the caller's to discard.

A **latent cache** (``v_width``; :mod:`ray_tpu.ops.latent_attention`)
is the same kernel with one key head and no V pool: a page ``[1,
block_size, row]`` is fetched once and read as the key tile and, in its
first ``v_width`` columns, as the value tile. It takes the scalars the
dense form takes; at 128 rows a token most of a short question's chunk
is row blocks of padding, which the bound above leaves without a page.

A **learned key selection** (``chosen``;
:mod:`ray_tpu.ops.sparse_attention`) is the same kernel with one more
BlockSpec operand, the mask of the keys each query may count, a block
of P pages' keys a grid step: it joins the causal mask in the body, the
pages read are the ones read without it, and a dead step names the
block it already holds (its index stops at the sequence's last group),
so no copy follows. A call without it has no such operand.

``interpret=True`` runs the same kernel, copies and semaphores
included, on CPU (tier-1 parity tests); on TPU it compiles with
parallel/arbitrary dimension semantics like the flash kernels (what a
step carries to the next — accumulators, the buffer's other half —
stays inside one sweep of the innermost axis).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (
    load_cached_blocks, persist_cached_blocks, resolve_chip,
    time_candidates)

_NEG_INF = -1e30

#: query rows (heads_per_step × block_r) one grid step may carry: bounds
#: the f32 (m, l, acc) scratch to ~1 MiB at head_dim 256
_MAX_ROWS_PER_STEP = 512

#: query rows a grid step of a chunk call may carry on the dense form
#: (_chunk_heads_per_step): four kv heads' row blocks of 512, whose
#: f32 (m, l, acc) scratch is 3 MiB at head_dim 128
_MAX_ROWS_PER_CHUNK_STEP = 2048

#: pages a grid step may fold, largest first (paged_pages_per_step)
_PAGE_GROUPS = (32, 16, 8, 4, 2, 1)

#: VMEM a grid step may plan for (_step_vmem_bytes): three quarters of
#: the 16 MiB of scoped VMEM a Mosaic kernel has by default on a v5e,
#: the rest left to the compiler's own temporaries. The kernel asks for
#: no ``vmem_limit_bytes``.
_VMEM_BUDGET = 12 << 20


def sublane_tile(dtype) -> int:
    """Rows of one (sublane, 128-lane) tile for ``dtype``: 8 for 32-bit,
    16 for bf16 — the granularity query rows and KV pages must meet for
    the compiled kernel."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def paged_work_pages(lens, block_size: int):
    """Pages a length-aware kernel reads per sequence:
    ``ceil(lens / block_size)``, and 0 for ``lens <= 0`` (a decode slot
    that holds no sequence: the kernel reads no page for it). Works on
    numpy and jax arrays — the engine's FLOP accounting shares this
    definition with the kernel."""
    return ((lens + block_size - 1) // block_size).clip(min=0) \
        if hasattr(lens, "clip") else max(-(-lens // block_size), 0)


def _paged_kernel(*refs, bs: int, hb: int, pp: int, slots: int,
                  sm_scale: float, v_width: Optional[int] = None,
                  window: int = 0, row_blocks: int = 1,
                  selects: bool = False):
    """One (batch b, kv head group g, row block r, page group t) step:
    fold pages ``t·pp .. t·pp + pp − 1`` of sequence b into the row
    block's online softmax, one kv head of the group at a time.
    ``k_hbm`` / ``v_hbm`` are the whole pools, left where they are;
    ``k_buf`` / ``v_buf`` ``[2, pp, hb, bs, d]`` hold two groups, the
    one this step folds and the one the next step will; ``sem[slot,
    0|1]`` counts a buffer's K and V copies. Scalar refs (bt, lens,
    layer) are in SMEM ahead of the body.

    ``row_blocks`` (the call's, static): with more than one, a row
    block's pages end at its own highest live position (``top``, the
    largest ``pos < lens[b]`` among its rows, −1 with none) and not at
    the sequence's length, whatever the form: a block of a chunk's
    padding alone holds no page (a dead sweep, like a sequence with
    ``lens <= 0``), an early block of a chunk stops at its own
    diagonal. ``top`` is reckoned from ``pos_ref`` at a sweep's first
    step into one more scratch word, ``top_s`` (SMEM), which the
    sweep's other steps read. With one row block the last live row
    sits at ``lens − 1`` and the bound is the length itself: a decode
    or a verify call has neither the reduction nor the word.

    ``v_width`` (a latent cache): there is no V pool and no V buffer;
    a page's value is the first ``v_width`` columns of its key tile,
    read from the one copy of it.

    ``window`` (a sliding-window layer): a row sees the ``window`` keys
    up to its own position and none behind them. One more scalar rides
    ahead of the body, ``first_ref [B, row blocks]``, the lowest
    position among a row block's rows: a page group all of whose keys
    lie behind that row's window lies behind every row's, and is a dead
    step like one past the block's bound (no copy started, body
    skipped).

    ``selects`` (a learned key selection): one more BlockSpec operand
    behind the position column, ``chosen_ref`` ``(1, 1 | block_r, pp ·
    bs)``: which of this step's keys each row may count (one row for
    all of a decode call's, a row a token in a chunk's). It joins the
    masks above; a key not chosen weighs nothing, and a row none of
    whose keys is chosen comes back zero."""
    refs = iter(refs)
    bt_ref, lens_ref, layer_ref = next(refs), next(refs), next(refs)
    first_ref = next(refs) if window else None
    q_ref, pos_ref = next(refs), next(refs)
    chosen_ref = next(refs) if selects else None
    k_hbm = next(refs)
    v_hbm = next(refs) if v_width is None else None
    o_ref, m_s, l_s, acc_s, k_buf = (next(refs) for _ in range(5))
    # a latent page's value rows are its key rows: what a dead page's
    # rows are zeroed in
    v_buf = next(refs) if v_width is None else k_buf
    sem = next(refs)
    b, g, t = pl.program_id(0), pl.program_id(1), pl.program_id(3)
    nt = pl.num_programs(3)
    # Length-aware skipping: groups past the last live one fetch
    # nothing and fold nothing. A sequence holds at most the table, and
    # none of it where lens <= 0: last = -1, every step a dead one.
    pages = jnp.clip(pl.cdiv(lens_ref[b], bs), 0, slots)
    if row_blocks > 1:
        top_s = next(refs)

        # written ahead of _init's first fetch, which reads ``pages``
        # in the same step; rewritten at every sweep's start, so no
        # sweep reads another's word
        @pl.when(t == 0)
        def _top():
            pos = pos_ref[0]                   # (block_r, 1)
            live = (pos >= 0) & (pos < lens_ref[b])
            top_s[0] = jnp.max(jnp.where(live, pos, -1))

        pages = jnp.minimum(pages, (top_s[0] + bs) // bs)
    last = (pages - 1) // pp
    # the first group with a key inside the window of the row block's
    # first row; a block of padding alone (position "never") has none
    first = 0 if not window else jnp.maximum(
        first_ref[b, pl.program_id(2)] - (window - 1), 0) // (pp * bs)

    def copies(group, slot, j):
        """The K and the V copy of page ``j`` of ``group``, HBM page
        ``(layer, block, this step's kv heads)`` to ``buf[slot, j]``."""
        src = (layer_ref[0], bt_ref[b, group * pp + j], pl.ds(g * hb, hb))
        k_copy = pltpu.make_async_copy(k_hbm.at[src], k_buf.at[slot, j],
                                       sem.at[slot, 0])
        if v_width is not None:
            return (k_copy,)
        return (k_copy,
                pltpu.make_async_copy(v_hbm.at[src], v_buf.at[slot, j],
                                      sem.at[slot, 1]))

    def live_in(group):
        """Pages of ``group`` the sequence holds."""
        return jnp.clip(pages - group * pp, 0, pp)

    def fetch(group, slot):
        """Start the copies of ``group``'s live pages, all at once. A
        page past the last live one is not fetched; its key columns are
        masked out below, and its V rows are zeroed so that what the
        buffer held before (anything, at a call's start) cannot turn a
        zero weight into a NaN. Loops, not ``pp`` unrolled branches: the
        kernel is traced and lowered in every process that loads a step
        program, and that time is set-up."""
        def start(j, carry):
            for c in copies(group, slot, j):
                c.start()
            return carry

        def zero(j, carry):
            v_buf[slot, j] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
            return carry

        n = live_in(group)
        jax.lax.fori_loop(0, n, start, 0)
        jax.lax.fori_loop(n, pp, zero, 0)

    @pl.when(t == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        if window:
            pl.when(first <= last)(lambda: fetch(first, first % 2))
        else:
            pl.when(pages > 0)(lambda: fetch(0, 0))

    @pl.when((t <= last) if not window else (t >= first) & (t <= last))
    def _compute():
        slot = t % 2

        @pl.when(t < last)
        def _prefetch():                       # under this step's matmuls
            fetch(t + 1, 1 - slot)

        def wait(j, carry):
            for c in copies(t, slot, j):
                c.wait()
            return carry

        jax.lax.fori_loop(0, live_in(t), wait, 0)

        # a row sees keys up to its own position (causal) and none of
        # the pages past the last live one
        key_max = jnp.minimum(pos_ref[0], pages * bs - 1)  # (block_r, 1)
        if selects:
            picked = chosen_ref[0].astype(jnp.int32) != 0
        for i in range(hb):                    # static: kv heads here
            q = q_ref[0, i]                    # (block_r, d)
            # the group's pages of one kv head, one under the other: a
            # page is whole sublane tiles, the join moves nothing
            k = k_buf[slot, :, i].reshape(pp * bs, -1)
            v = v_buf[slot, :, i].reshape(pp * bs, -1) \
                if v_width is None else k[:, :v_width]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            key_pos = t * (pp * bs) + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            seen = key_pos <= key_max
            if window:
                seen &= key_pos > pos_ref[0] - window
            if selects:
                seen &= picked
            s = jnp.where(seen, s, _NEG_INF)

            m_prev = m_s[i]                    # (block_r, 128) lanes equal
            l_prev = l_s[i]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next[:, 0:1])
            if selects:
                # a row none of whose keys is chosen so far has m =
                # -1e30 and would count exp(0) a key
                p = jnp.where(seen, p, 0.0)
            l_s[i] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            m_s[i] = m_next
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_s[i] = acc_s[i] * alpha[:, 0:1] + pv

    @pl.when(t == nt - 1)
    def _final():
        for i in range(hb):
            l = l_s[i][:, 0:1]
            # rows that scored no key (padding at position −1, every
            # row of a sequence with no live page): emit zeros
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, i] = (acc_s[i] / l).astype(o_ref.dtype)


def _heads_per_step(kv_heads: int, block_r: int,
                    max_rows: Optional[int] = None) -> int:
    """Largest divisor of ``kv_heads`` whose rows fit one grid step
    (``max_rows``, ``_MAX_ROWS_PER_STEP`` unless given): decode (a few
    rows per head) takes every head of a page in one step, a prefill
    chunk (hundreds of rows per head) one or two."""
    hb = max(1, min(kv_heads,
                    (max_rows or _MAX_ROWS_PER_STEP) // block_r))
    while kv_heads % hb:
        hb -= 1
    return hb


def _chunk_heads_per_step(kv_heads: int, block_r: int, pages_of) -> int:
    """kv heads a grid step of a chunk call serves from one fetch of a
    page group, on the dense form of a full-attention layer: the most
    (a divisor of ``kv_heads``, up to ``_MAX_ROWS_PER_CHUNK_STEP`` query
    rows) at which the step still holds the pages it holds with
    :func:`_heads_per_step`'s (``pages_of(heads)``: the group is what
    the online softmax folds at once, so the same group is the same
    bits). A step's copies cost by their number, not their size: more
    heads a step are fewer copies a head."""
    narrow = _heads_per_step(kv_heads, block_r)
    widest = _heads_per_step(kv_heads, block_r, _MAX_ROWS_PER_CHUNK_STEP)
    pages = pages_of(narrow)
    for hb in range(widest, narrow, -1):
        if kv_heads % hb == 0 and pages_of(hb) == pages:
            return hb
    return narrow


def _row_block(rows: int, head_dim: int, dtype, block_r: Optional[int],
               chip: Optional[str]) -> int:
    """The row block a call runs with: the caller's (or the chip-aware
    default), no more than the rows there are, whole sublane tiles."""
    tile = sublane_tile(dtype)
    if not block_r:
        block_r = default_paged_block_r(rows, head_dim, chip=chip)
    return _round_up(min(block_r, _round_up(rows, tile)), tile)


def _step_vmem_bytes(pp: int, hb: int, bs: int, d: int, itemsize: int,
                     block_r: int, pools: int = 2,
                     chosen_bytes: int = 0) -> int:
    """VMEM one grid step holds with ``pp`` pages a group: the K and V
    pages (two groups each: this step's and the next's; ``pools`` 1
    where a latent page is both), one head's
    joined K and V tile, the f32 score tile and its exponentials
    (beside them a selection's block, ``chosen_bytes`` a key,
    double-buffered), and
    what does not grow with ``pp``: q and out blocks (double-buffered)
    and the (m, l, acc) scratch."""
    pages = pools * 2 * pp * hb * bs * d * itemsize
    joined = pools * pp * bs * d * itemsize
    scores = (2 * block_r * 4 + 2 * chosen_bytes) * _round_up(pp * bs, 128)
    fixed = 2 * 2 * hb * block_r * d * itemsize \
        + hb * block_r * (2 * 128 + d) * 4
    return pages + joined + scores + fixed


def paged_pages_per_step(rows: int, kv_heads: int, block_size: int,
                         head_dim: int, dtype, table_len: int, *,
                         block_r: Optional[int] = None,
                         chip: Optional[str] = None,
                         pools: int = 2) -> int:
    """P, the pages of a sequence one grid step of
    :func:`paged_flash_attention` folds for a call of ``rows`` query
    rows a kv head (C · heads per kv head) over tables of ``table_len``
    slots: the largest of ``_PAGE_GROUPS`` whose step fits
    ``_VMEM_BUDGET``, and no larger than the table has a use for. It
    follows what the call can observe — the page's bytes, the row
    block, the table — so MHA at head_dim 256 (a 128 KB page of sixteen
    heads) gets a smaller group than GQA at 128 (32 KB). The engine's
    grid-step counters ask the same function the kernel does.
    ``pools=1``: a latent cache, one pool whose page is key and value
    (``head_dim`` is then the latent row's width)."""
    block_r = _row_block(rows, head_dim, dtype, block_r, chip)
    return _pages_per_step(_heads_per_step(kv_heads, block_r), block_size,
                           head_dim, dtype, block_r, table_len, pools)


def _pages_per_step(hb: int, bs: int, d: int, dtype, block_r: int,
                    table_len: int, pools: int = 2,
                    chosen_bytes: int = 0) -> int:
    itemsize = jnp.dtype(dtype).itemsize
    for pp in _PAGE_GROUPS:
        if pp // 2 < table_len and _step_vmem_bytes(
                pp, hb, bs, d, itemsize, block_r, pools,
                chosen_bytes) <= _VMEM_BUDGET:
            return pp
    return 1


def paged_grid_steps(pages, table_len: int, pages_per_step: int):
    """``(steps, live)`` of one call's innermost grid axis over a batch
    whose sequences hold ``pages`` pages (:func:`paged_work_pages`, a
    numpy array): ``len(pages) · ceil(T / P)`` steps taken, ``Σ
    ceil(pages / P)`` of them with a live page to fold (the rest are
    skipped bodies that still cost a step)."""
    pages = pages.clip(max=table_len)
    groups = -(-table_len // pages_per_step)
    return len(pages) * groups, int((-(-pages // pages_per_step)).sum())


def paged_row_blocks(rows: int, live_rows: int, head_dim: int, dtype, *,
                     block_r: Optional[int] = None,
                     chip: Optional[str] = None):
    """``(blocks, live)`` of one call's row-block axis, a sequence and a
    kv head group: the blocks its ``rows`` query rows a kv head make
    (C · heads per kv head, at the row block the kernel would take),
    and those with a live row, the first ``live_rows`` (a chunk's rows
    are its tokens in order, each token's heads together, the padding
    behind them): the others hold no page and fold none. What the
    engine books a chunk, as :func:`paged_grid_steps` is a decode
    step's."""
    block_r = _row_block(rows, head_dim, dtype, block_r, chip)
    return -(-rows // block_r), -(-live_rows // block_r)


def layered_pool(k_cache: jnp.ndarray, v_cache: jnp.ndarray, layer):
    """``(k_pool, v_pool, layer)`` with the pools 5-D
    ``[L, N, KVH, bs, D]`` and ``layer`` a ``(1,)`` int32 array: a
    4-D one-layer pool (``layer=None``) becomes layer 0 of a one-layer
    pool — a reshape, not a copy."""
    if (layer is None) != (k_cache.ndim == 4):
        raise ValueError(
            f"a {k_cache.ndim}-D kv pool with layer={layer!r}: pass the "
            f"whole [L, N, KVH, bs, D] pool with its layer index, or one "
            f"layer's [N, KVH, bs, D] pool without")
    if layer is None:
        k_cache, layer = k_cache[None], 0
        v_cache = None if v_cache is None else v_cache[None]
    return k_cache, v_cache, jnp.asarray(layer, jnp.int32).reshape(1)


def paged_flash_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                          v_cache: jnp.ndarray,
                          block_tables: jnp.ndarray,
                          q_positions: jnp.ndarray,
                          lens: jnp.ndarray, *,
                          layer=None,
                          sm_scale: Optional[float] = None,
                          block_r: Optional[int] = None,
                          interpret: bool = False,
                          v_width: Optional[int] = None,
                          window: int = 0,
                          chosen: Optional[jnp.ndarray] = None
                          ) -> jnp.ndarray:
    """Paged attention of new-token queries against the block pool.

    Same contract as the XLA reference
    (:func:`ray_tpu.ops.attention.paged_attention`): ``q`` is
    ``[B, C, H, D]`` at absolute ``q_positions [B, C]``,
    ``block_tables [B, T]``. The caches are the WHOLE pool
    ``[L, N, KVH, bs, D]`` with ``layer`` (an int32 scalar, traced in a
    layer scan) naming the layer to attend — the kernel DMAs pages
    ``(layer, block)`` straight out of that buffer, so the caller never
    slices (= copies) a layer out of a pool it carries and updates in
    place. ``layer=None`` takes one layer's ``[N, KVH, bs, D]`` pool.
    ``lens [B]`` is the number of LIVE cached positions per sequence
    (after this step's writes); table slots past ``ceil(lens/bs)`` are
    skipped entirely. Rows whose position ≥ ``lens[b]`` (padded prefill
    tail) attend the live keys up to their row block's bound, none in a
    block of such rows alone (zeros) — their outputs are finite and the
    caller's to discard, as with the reference path. A sequence with
    ``lens[b] <= 0`` holds nothing: its rows come back zero and no page
    of its table is read (what is in those pages, a NaN included,
    reaches nothing).

    ``v_width`` with ``v_cache=None``: a latent cache, one pool
    ``[L, N, 1, bs, D]`` whose row is a token's key for every head and,
    in its first ``v_width`` columns, its value; the result is
    ``[B, C, H, v_width]``. A page is fetched once and read as both.

    ``window > 0`` (a sliding-window layer): a query attends the
    ``window`` keys up to and including its own position, ``key >
    query - window``; page groups wholly behind a row block's window are
    dead steps. The masks depend on ``query - key`` alone, so a caller
    may hand over a table that starts at any page of the sequence with
    ``q_positions`` and ``lens`` counted from that page's first
    position: a window layer's table need hold no page behind the
    window.

    ``chosen [B, C, W]`` (a learned key selection,
    :mod:`ray_tpu.ops.sparse_attention`): which keys each query may
    count, over the table's window (``W`` keys from position 0; keys
    past ``W`` are not chosen, columns past the table are dropped). It
    joins the causal mask: a key not chosen weighs nothing, a row none
    of whose keys is chosen comes back zero, and the pages read are
    the ones a call without it reads (every live page up to the row
    block's bound). It travels as one more BlockSpec operand, a block
    of ``pages per step x block_size`` keys a grid step: one int32 row
    a sequence where ``C == 1`` (a decode call: the heads of a group
    share their token's selection), an int8 row a token otherwise, the
    query rows then ordered head-major (row ``r`` of a kv head is head
    ``r // C'``, token ``r % C'``, ``C'`` = C in whole row blocks) so
    that a row block's tokens are a run of ``chosen``'s rows. Without
    it the call has no such operand and is the kernel it was.
    """
    if window and v_width is not None:
        raise ValueError("a latent cache has no sliding window")
    if window and chosen is not None:
        raise ValueError("a selection over a sliding window: no layer "
                         "has both")
    if (v_width is None) != (v_cache is not None):
        raise ValueError("pass a V pool, or v_width for a latent pool "
                         "whose page is key and value, not both")
    k_cache, v_cache, layer = layered_pool(k_cache, v_cache, layer)
    b, c, h, d = q.shape
    dv = d if v_width is None else v_width
    g, bs = k_cache.shape[2:4]
    t = block_tables.shape[1]
    if h % g:
        raise ValueError(f"n_heads {h} not divisible by kv_heads {g}")
    rep = h // g
    rows = c * rep
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    chip = "cpu" if interpret else None
    # a chunk's selection: head-major rows, the tokens in whole row
    # blocks, so that a block's rows are a run of ``chosen``'s
    by_head = chosen is not None and c > 1
    if by_head:
        block_r = _row_block(c, d, q.dtype, block_r, chip)
        c_pad = _round_up(c, block_r)
        rows_pad = rep * c_pad
    else:
        block_r = _row_block(rows, d, q.dtype, block_r, chip)
        c_pad, rows_pad = c, _round_up(rows, block_r)
    nr = rows_pad // block_r
    chosen_bytes = 0 if chosen is None else (block_r if by_head else 4)

    def pages_of(heads):
        return _pages_per_step(heads, bs, d, k_cache.dtype, block_r, t,
                               2 if v_width is None else 1, chosen_bytes)
    # a chunk of the dense form on a full-attention layer: more kv heads
    # a fetch (a latent pool has one key head to serve). Every other
    # call (one row block, a window, a selection) has the grid it had.
    hb = _chunk_heads_per_step(g, block_r, pages_of) \
        if nr > 1 and not window and chosen is None \
        else _heads_per_step(g, block_r)
    pp = pages_of(hb)

    # Group-major query rows: row r of kv head g is (c = r // rep,
    # head = g*rep + r % rep). Only q (tiny) is reshaped — never the
    # cache.
    pos_rows = q_positions.astype(jnp.int32)
    if by_head:
        qg = jnp.pad(q, ((0, 0), (0, c_pad - c), (0, 0), (0, 0))) \
            .reshape(b, c_pad, g, rep, d).transpose(0, 2, 3, 1, 4) \
            .reshape(b, g, rows_pad, d)
        pos_rows = jnp.tile(jnp.pad(pos_rows, ((0, 0), (0, c_pad - c)),
                                    constant_values=-1), (1, rep))
    else:
        qg = q.reshape(b, c, g, rep, d).transpose(0, 2, 1, 3, 4) \
            .reshape(b, g, rows, d)
        pos_rows = jnp.repeat(pos_rows, rep, axis=1)
        if rows_pad != rows:
            qg = jnp.pad(qg,
                         ((0, 0), (0, 0), (0, rows_pad - rows), (0, 0)))
            pos_rows = jnp.pad(pos_rows, ((0, 0), (0, rows_pad - rows)),
                               constant_values=-1)
    if window:
        # the lowest position of each row block, its padding (-1) aside;
        # a block of padding alone starts behind every page
        never = jnp.iinfo(jnp.int32).max
        scalars_window = (jnp.min(jnp.where(
            pos_rows >= 0, pos_rows, never).reshape(b, nr, block_r),
            axis=2),)
    pos_rows = pos_rows[:, :, None]            # [B, rows_pad, 1] column

    def q_map(b_, g_, r_, t_, *scalars):
        return (b_, g_, r_, 0)

    def pos_map(b_, g_, r_, t_, *scalars):
        return (b_, r_, 0)

    pools = (k_cache,) if v_cache is None else (k_cache, v_cache)
    scalars = (block_tables.astype(jnp.int32), lens.astype(jnp.int32), layer)
    if window:
        scalars += scalars_window
    groups = pl.cdiv(t, pp)
    selection, selection_specs = (), []
    if chosen is not None:
        # the grid's keys wide, the tokens in whole row blocks
        keys = groups * pp * bs
        chosen = chosen[:, :, :keys].astype(jnp.int8 if by_head
                                            else jnp.int32)
        selection = (jnp.pad(chosen, (
            (0, 0), (0, c_pad - c), (0, keys - chosen.shape[2]))),)

        def chosen_map(b_, g_, r_, t_, bt_ref, lens_ref, *scalars):
            # a step past the sequence's last page folds nothing: it
            # names the block it has, and no copy follows
            last = jnp.maximum(pl.cdiv(lens_ref[b_], bs) - 1, 0) // pp
            return (b_, r_ % (c_pad // block_r) if by_head else 0,
                    jnp.minimum(t_, last))
        selection_specs = [pl.BlockSpec(
            (1, block_r if by_head else 1, pp * bs), chosen_map)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, g // hb, nr, groups),
        in_specs=[
            pl.BlockSpec((1, hb, block_r, d), q_map),
            pl.BlockSpec((1, block_r, 1), pos_map),
        ] + selection_specs + [
            pl.BlockSpec(memory_space=pl.ANY)  # the pools, left in HBM
            for _ in pools],
        out_specs=pl.BlockSpec((1, hb, block_r, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((hb, block_r, 128), jnp.float32),  # running max m
            pltpu.VMEM((hb, block_r, 128), jnp.float32),  # running denom l
            pltpu.VMEM((hb, block_r, dv), jnp.float32),   # out accumulator
        ] + [pltpu.VMEM((2, pp, hb, bs, d), pool.dtype)   # two groups'
             for pool in pools] + [                       # pages, a pool
            pltpu.SemaphoreType.DMA((2, 2)),
        ] + [pltpu.SMEM((1,), jnp.int32)] * (nr > 1),     # top_s
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, hb=hb, pp=pp, slots=t,
                          sm_scale=float(sm_scale), v_width=v_width,
                          window=int(window), row_blocks=nr,
                          selects=chosen is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, rows_pad, dv), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="paged_attention" if v_width is None else "mla_attn",
    )(*scalars, qg, pos_rows, *selection, *pools)
    if by_head:
        return out.reshape(b, g, rep, c_pad, dv)[:, :, :, :c] \
            .transpose(0, 3, 1, 2, 4).reshape(b, c, h, dv)
    out = out[:, :, :rows, :].reshape(b, g, c, rep, dv) \
        .transpose(0, 2, 1, 3, 4).reshape(b, c, h, dv)
    return out


# --------------------------------------------------- block-size selection
def _round_up(n: int, m: int) -> int:
    return max(m, -(-n // m) * m)


def default_paged_block_r(rows: int, head_dim: int,
                          chip: Optional[str] = None) -> int:
    """Chip-aware default query-row block for the paged kernel.

    Rows = C·(heads per kv head) — tiny for batched decode (one token
    per sequence), up to a few hundred for chunked prefill. Small on
    CPU interpret (grid overhead dominates), wider on TPU so the
    row-block matmuls fill MXU tiles; large head dims halve the block
    to keep the f32 (rows, bs) score tile + accumulators in VMEM.
    """
    chip = resolve_chip(chip)
    cap = 128 if chip == "cpu" else (128 if head_dim >= 256 else 256)
    return min(_round_up(rows, 8), cap)


# Winner cache: (chip, block_size, table_len, rows, head_dim) -> block_r.
_PAGED_AUTOTUNE_CACHE: dict = {}

_PAGED_CANDIDATES = (8, 16, 32, 64, 128, 256, 512)


def _paged_disk_key(key: tuple) -> str:
    chip, bs, t, rows, head_dim = key
    return f"paged|{chip}|{jax.__version__}|{bs}|{t}|{rows}|{head_dim}"


def autotune_paged_block_r(block_size: int, table_len: int, rows: int,
                           head_dim: int, *,
                           batch: int = 8,
                           dtype=jnp.bfloat16,
                           candidates=None,
                           iters: int = 5,
                           timer=None,
                           chip: Optional[str] = None) -> int:
    """One-shot row-block autotune for the paged kernel: time a small
    candidate grid once and cache the winner per
    ``(chip, block_size, table_len, rows, head_dim)``; timed winners
    persist through the SAME on-disk JSON as the flash autotuner
    (``flash_autotune.json`` under the compile-cache root, keys
    prefixed ``paged|``), so serving replicas never re-time on process
    start.

    Off-TPU (without an injected ``timer``) returns the chip-aware
    default without running anything. ``timer`` is injectable for
    tests: a callable ``(block_r) -> seconds``. A candidate that does
    not compile (VMEM) is skipped with a warning; when none compiles
    this raises — an untimed default is never returned as a winner.
    """
    chip = resolve_chip(chip)
    key = (chip, int(block_size), int(table_len), int(rows),
           int(head_dim))
    if key in _PAGED_AUTOTUNE_CACHE:
        return _PAGED_AUTOTUNE_CACHE[key]
    persisted = load_cached_blocks(_paged_disk_key(key))
    if persisted is not None:
        _PAGED_AUTOTUNE_CACHE[key] = int(persisted[0])
        return _PAGED_AUTOTUNE_CACHE[key]

    default = default_paged_block_r(rows, head_dim, chip=chip)
    cands = sorted({min(c, _round_up(rows, 8))
                    for c in (candidates or _PAGED_CANDIDATES)})
    if default not in cands:
        cands.insert(0, default)
    if timer is None:
        if jax.default_backend() != "tpu" or len(cands) <= 1:
            return default
        timer = _paged_block_timer(batch, block_size, table_len, rows,
                                   head_dim, dtype, iters)
    best = time_candidates("paged", cands, timer)
    _PAGED_AUTOTUNE_CACHE[key] = best
    persist_cached_blocks(_paged_disk_key(key), (best, best))
    return best


def _paged_block_timer(batch, block_size, table_len, rows, head_dim,
                       dtype, iters: int):
    """Build a timer(block_r) -> seconds over a synthetic full-length
    paged batch (the worst-case decode shape)."""
    import time

    n_blocks = 1 + batch * table_len
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    kc = jax.random.normal(ks[0], (n_blocks, 1, block_size, head_dim),
                           dtype)
    vc = jax.random.normal(ks[1], (n_blocks, 1, block_size, head_dim),
                           dtype)
    q = jax.random.normal(ks[2], (batch, rows, 1, head_dim), dtype)
    bt = jnp.arange(1, n_blocks, dtype=jnp.int32).reshape(
        batch, table_len)
    lens = jnp.full((batch,), table_len * block_size, jnp.int32)
    pos = jnp.full((batch, rows), table_len * block_size - 1, jnp.int32)

    def timer(block_r: int) -> float:
        fn = jax.jit(functools.partial(
            paged_flash_attention, block_r=block_r))
        r = fn(q, kc, vc, bt, pos, lens)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(q, kc, vc, bt, pos, lens)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / iters

    return timer
