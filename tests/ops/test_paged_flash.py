"""Pallas paged-attention kernel parity suite (interpret mode on CPU).

The kernel is the serving decode fast path: every case here pins its
contract against the XLA gather reference at fp32-softmax tolerance —
GQA grouping, uneven last blocks, chunked-prefill row shapes, the
engine's block-0 trash slot, ``lens <= 0`` slots that hold no sequence
(zeros, and no page read) — plus the
length-skipping semantics themselves (content of dead blocks must be
unreachable) and the autotune/persisted-cache machinery it shares with
the flash kernel."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import (attention_reference, autotune_paged_block_r,
                         default_paged_block_r, paged_attention,
                         paged_work_pages)
import ray_tpu.ops.paged_flash as pf
from ray_tpu.ops.paged_flash import (paged_flash_attention,
                                     paged_grid_steps,
                                     paged_pages_per_step)

pytestmark = pytest.mark.serve_llm

TOL = dict(rtol=2e-5, atol=2e-5)


@pytest.fixture
def group_of(monkeypatch):
    """``group_of(P)`` makes the kernel fold P pages a grid step at
    whatever shape it is given (its own choice follows a VMEM budget
    that these tiny shapes never reach, and the table: the smallest
    group that holds all of it); ``group_of(None)`` leaves the choice
    alone."""
    def set_(pp):
        if pp:
            monkeypatch.setattr(pf, "_PAGE_GROUPS", (pp,))
    return set_


def _paged_case(seed, B, S, H, KVH, D, bs, T, shuffle=True):
    """Random sequences scattered into a paged pool (block 0 reserved
    as the engine's trash slot, filled with junk to prove it is only
    read when a sequence's table actually points at it)."""
    rng = np.random.default_rng(seed)
    k_seq = rng.normal(size=(B, T * bs, KVH, D)).astype(np.float32)
    v_seq = rng.normal(size=(B, T * bs, KVH, D)).astype(np.float32)
    n_blocks = 1 + B * T
    # pool layout [N, KVH, bs, D]: kv_heads ahead of block_size
    kc = rng.normal(size=(n_blocks, KVH, bs, D)).astype(np.float32)
    vc = rng.normal(size=(n_blocks, KVH, bs, D)).astype(np.float32)
    order = rng.permutation(np.arange(1, n_blocks)) if shuffle \
        else np.arange(1, n_blocks)
    bt = order.astype(np.int32).reshape(B, T)
    for b in range(B):
        for t in range(T):
            kc[bt[b, t]] = k_seq[b, t * bs:(t + 1) * bs].swapaxes(0, 1)
            vc[bt[b, t]] = v_seq[b, t * bs:(t + 1) * bs].swapaxes(0, 1)
    return k_seq, v_seq, kc, vc, bt


def _both(q, kc, vc, bt, pos, lens):
    ref = paged_attention(q, kc, vc, bt, pos, impl="reference")
    ker = paged_attention(q, kc, vc, bt, pos,
                          lens=jnp.asarray(np.asarray(lens, np.int32)),
                          impl="interpret")
    return np.asarray(ref), np.asarray(ker)


@pytest.mark.parametrize("pp", [None, 1, 2, 4])
@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2)])
def test_decode_parity_mixed_uneven_lens(H, KVH, pp, group_of):
    """Batched single-token decode over mixed lengths, none of them
    block-aligned — the kernel must match the reference on every live
    row while touching only live pages. 2, 6 and 3 live pages of a
    6-slot table: whole groups and a group and a page at P = 2, a
    partly live first group and a table that P does not divide at 4."""
    group_of(pp)
    B, D, bs, T = 3, 16, 4, 6
    _, _, kc, vc, bt = _paged_case(0, B, 24, H, KVH, D, bs, T)
    rng = np.random.default_rng(1)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    lens = np.array([5, 23, 9], np.int32)      # uneven last blocks
    pos = (lens - 1)[:, None]
    ref, ker = _both(q, kc, vc, bt, jnp.asarray(pos), lens)
    np.testing.assert_allclose(ker, ref, **TOL)


def test_chunked_prefill_parity_and_shape_duality():
    """The SAME kernel serves (B, 1) decode and (B, C) chunked prefill:
    a C-row chunk's valid rows must match both the reference and C
    independent single-row calls at the same positions."""
    B, C, H, KVH, D, bs, T = 2, 5, 4, 2, 8, 4, 4
    _, _, kc, vc, bt = _paged_case(2, B, 16, H, KVH, D, bs, T)
    rng = np.random.default_rng(3)
    q = rng.normal(size=(B, C, H, D)).astype(np.float32)
    lens = np.array([11, 14], np.int32)
    pos = np.stack([np.arange(C, dtype=np.int32) + (l - C)
                    for l in lens])
    ref, ker = _both(q, kc, vc, bt, jnp.asarray(pos), lens)
    np.testing.assert_allclose(ker, ref, **TOL)
    # shape duality: each chunk row == a one-token decode call
    for c in range(C):
        _, one = _both(q[:, c:c + 1], kc, vc, bt,
                       jnp.asarray(pos[:, c:c + 1]), pos[:, c] + 1)
        np.testing.assert_allclose(one[:, 0], ker[:, c], **TOL)


@pytest.mark.parametrize("pp", [None, 2, 4])
def test_length_skipping_ignores_dead_blocks(pp, group_of):
    """The headline semantics: junk written into table slots past
    ``ceil(lens/bs)`` must be bit-invisible — work is proportional to
    live tokens, not the serving window. 3 and 4 live pages of 8: at
    P = 2 and 4 the dead slots are the tail of a partly live group
    (masked page by page) and whole dead groups (skipped)."""
    group_of(pp)
    B, H, KVH, D, bs, T = 2, 4, 4, 8, 4, 8
    _, _, kc, vc, bt = _paged_case(4, B, 32, H, KVH, D, bs, T)
    rng = np.random.default_rng(5)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    lens = np.array([9, 13], np.int32)
    pos = (lens - 1)[:, None]
    _, ker = _both(q, kc, vc, bt, jnp.asarray(pos), lens)
    kc2, vc2 = kc.copy(), vc.copy()
    for b in range(B):
        dead = -(-int(lens[b]) // bs)
        kc2[bt[b, dead:]] = 1e3
        vc2[bt[b, dead:]] = -1e3
    _, ker2 = _both(q, kc2, vc2, bt, jnp.asarray(pos), lens)
    np.testing.assert_array_equal(ker, ker2)


def _poisoned(pool):
    """``pool`` (blocks on axis 0) with the trash block holding NaN and
    inf: whatever reads it shows."""
    pool = np.array(pool)
    pool[0] = np.nan
    pool[0, ..., ::2] = np.inf
    return pool


def _latent_both(lens, bt, seed=0):
    """The latent form (one pool whose page is key and value) on a
    decode batch with a poisoned trash block: (reference, kernel)."""
    from ray_tpu.ops.latent_attention import (latent_attention,
                                              latent_row_width)
    H, dn, dr, dv, rank, bs = 4, 16, 8, 16, 128, 8
    B = len(lens)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pool = np.array(jax.random.normal(
        ks[0], (1 + B * bt.shape[1], 1, bs, latent_row_width(rank, dr))))
    pool[..., rank + dr:] = 0.0
    lens = jnp.asarray(lens, jnp.int32)
    return [np.asarray(latent_attention(
        jax.random.normal(ks[1], (B, 1, H, dn)),
        jax.random.normal(ks[2], (B, 1, H, dr)),
        jax.random.normal(ks[3], (rank, H, dn)) * 0.1,
        jax.random.normal(ks[4], (rank, H, dv)) * 0.1,
        jnp.asarray(_poisoned(pool))[None], jnp.asarray(bt),
        (lens - 1)[:, None], layer=0, lens=lens,
        sm_scale=(dn + dr) ** -0.5, impl=impl))
        for impl in ("reference", "interpret")]


@pytest.mark.parametrize("form", ["dense", "latent"])
def test_lens_zero_idle_slot_is_finite_and_matches_reference(form):
    """The engine's decode slots that hold no sequence: ``lens <= 0``,
    the row below position 0, block table all-zeros (the trash block).
    Kernel and reference return zeros for them, and the kernel reads no
    page of theirs: the trash block holds NaN and inf here, and a live
    sequence between them is what it is without the poison."""
    bs, T = (4, 3) if form == "dense" else (8, 3)
    lens = np.array([0, bs + 1, -1], np.int32)
    bt = np.zeros((3, T), np.int32)
    bt[1] = 1 + np.arange(T)                   # the live one's own pages
    if form == "latent":
        ref, ker = _latent_both(lens, bt)
    else:
        H, KVH, D = 4, 2, 8
        rng = np.random.default_rng(6)
        kc = rng.normal(size=(1 + 3 * T, KVH, bs, D)).astype(np.float32)
        vc = rng.normal(size=(1 + 3 * T, KVH, bs, D)).astype(np.float32)
        q = rng.normal(size=(3, 1, H, D)).astype(np.float32)
        pos = jnp.asarray(lens - 1)[:, None]
        clean = _both(q, kc, vc, bt, pos, lens)
        ref, ker = _both(q, _poisoned(kc), _poisoned(vc), bt, pos, lens)
        for got, want in zip((ref, ker), clean):
            np.testing.assert_array_equal(got[1], want[1])
    for out in (ref, ker):
        assert np.all(np.isfinite(out))
        assert not out[[0, 2]].any() and out[1].any()
    np.testing.assert_allclose(ker, ref, **TOL)


@pytest.mark.parametrize("pp", [None, 2])
@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2)])
def test_lens_minus_one_zero_and_one_side_by_side(H, KVH, pp, group_of):
    """What a decode batch holds of short rows: the slot with no
    sequence (the engine stages length -1: ``lens`` 0), a caller's
    ``lens`` of -1, and a sequence's first token (``lens`` 1, position
    0: a live row, one key). The first two are zeros and read nothing,
    the third attends its one key, whichever slot each sits in."""
    group_of(pp)
    bs, D, T = 4, 8, 5
    lens = np.array([1, -1, 0, 1, 0, -1], np.int32)
    B = len(lens)
    _, v_seq, kc, vc, bt = _paged_case(30, B, T * bs, H, KVH, D, bs, T)
    bt[lens <= 0] = 0
    rng = np.random.default_rng(31)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    ref, ker = _both(q, _poisoned(kc), _poisoned(vc), bt,
                     jnp.asarray(lens - 1)[:, None], lens)
    np.testing.assert_allclose(ker, ref, **TOL)
    assert not ker[lens <= 0].any() and not ref[lens <= 0].any()
    # one key: the softmax is 1 and the output that key's value
    for b in np.flatnonzero(lens == 1):
        np.testing.assert_allclose(
            ker[b, 0], np.repeat(v_seq[b, 0], H // KVH, axis=0), **TOL)


def test_block_size_not_dividing_sequence():
    """lens and positions falling mid-block everywhere (block_size 5,
    live lengths 7/11/3): masking inside the last live page must be
    exact."""
    B, H, KVH, D, bs, T = 3, 2, 2, 8, 5, 4
    _, _, kc, vc, bt = _paged_case(7, B, 20, H, KVH, D, bs, T)
    rng = np.random.default_rng(8)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    lens = np.array([7, 11, 3], np.int32)
    pos = (lens - 1)[:, None]
    ref, ker = _both(q, kc, vc, bt, jnp.asarray(pos), lens)
    np.testing.assert_allclose(ker, ref, **TOL)


def test_matches_dense_attention_over_ordered_sequence():
    """End-to-end sanity vs plain dense attention: a paged read of an
    ordered sequence == attention_reference over its first ``lens``
    positions."""
    B, H, KVH, D, bs, T = 2, 4, 4, 8, 4, 3
    k_seq, v_seq, kc, vc, bt = _paged_case(9, B, 12, H, KVH, D, bs, T)
    rng = np.random.default_rng(10)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    lens = np.array([10, 10], np.int32)
    pos = (lens - 1)[:, None]
    _, ker = _both(q, kc, vc, bt, jnp.asarray(pos), lens)
    ref = attention_reference(
        jnp.asarray(q), jnp.asarray(k_seq[:, :10]),
        jnp.asarray(v_seq[:, :10]), causal=False)
    np.testing.assert_allclose(ker, np.asarray(ref), **TOL)


def test_jit_stable_across_lens_values():
    """lens is a traced operand: different live lengths must reuse ONE
    compiled program (the engine jits decode exactly once)."""
    import functools
    B, H, KVH, D, bs, T = 2, 4, 2, 8, 4, 4
    _, _, kc, vc, bt = _paged_case(11, B, 16, H, KVH, D, bs, T)
    q = np.zeros((B, 1, H, D), np.float32)
    f = jax.jit(functools.partial(paged_attention, impl="interpret"))
    for ln in ([4, 9], [16, 1], [2, 2]):
        lens = np.asarray(ln, np.int32)
        f(q, kc, vc, bt, jnp.asarray((lens - 1).clip(0)[:, None]),
          lens=lens)
    assert f._cache_size() == 1


def test_lens_none_derives_bound_from_positions():
    B, H, KVH, D, bs, T = 2, 2, 2, 8, 4, 4
    _, _, kc, vc, bt = _paged_case(12, B, 16, H, KVH, D, bs, T)
    rng = np.random.default_rng(13)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    pos = np.array([[6], [13]], np.int32)
    ref = paged_attention(q, kc, vc, bt, jnp.asarray(pos),
                          impl="reference")
    ker = paged_attention(q, kc, vc, bt, jnp.asarray(pos),
                          impl="interpret")    # lens derived: pos + 1
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), **TOL)


def test_explicit_block_r_and_row_padding():
    """block_r smaller than the row count exercises the row-block grid
    axis; block_r larger exercises padded rows (position −1, masked to
    zero and dropped on unpack)."""
    B, C, H, KVH, D, bs, T = 1, 3, 8, 2, 8, 4, 3
    _, _, kc, vc, bt = _paged_case(14, B, 12, H, KVH, D, bs, T)
    rng = np.random.default_rng(15)
    q = rng.normal(size=(B, C, H, D)).astype(np.float32)
    lens = np.array([11], np.int32)
    pos = np.arange(C, dtype=np.int32)[None, :] + (11 - C)
    ref = paged_attention(q, kc, vc, bt, jnp.asarray(pos),
                          impl="reference")
    for br in (8, 64):   # rows = C * rep = 12 -> split and padded
        ker = paged_flash_attention(
            jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
            jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(lens),
            block_r=br, interpret=True)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   **TOL)


def test_paged_work_pages_accounting():
    """A sequence's pages, and none for one that holds nothing."""
    lens = np.array([-1, 0, 1, 4, 5, 16], np.int32)
    pages = paged_work_pages(lens, 4)
    np.testing.assert_array_equal(pages, [0, 0, 1, 1, 2, 4])
    np.testing.assert_array_equal(paged_work_pages(jnp.asarray(lens), 4),
                                  pages)
    assert paged_work_pages(0, 4) == 0
    assert paged_work_pages(-1, 4) == 0
    assert paged_work_pages(-9, 4) == 0
    assert paged_work_pages(9, 4) == 3


def test_gqa_reference_has_no_materialized_repeat():
    """The satellite regression: the reference path's GQA read must not
    materialize an h/kvh-times-larger cache copy. jaxpr-level check —
    no broadcast of a gathered [*, H, D] tensor — plus value parity
    with an explicit jnp.repeat formulation."""
    import math
    B, C, H, KVH, D, bs, T = 2, 2, 8, 2, 8, 4, 3
    _, _, kc, vc, bt = _paged_case(16, B, 12, H, KVH, D, bs, T)
    rng = np.random.default_rng(17)
    q = rng.normal(size=(B, C, H, D)).astype(np.float32)
    pos = np.array([[8, 9], [8, 9]], np.int32)

    ref = paged_attention(q, kc, vc, bt, jnp.asarray(pos),
                          impl="reference")
    k = jnp.take(jnp.asarray(kc), jnp.asarray(bt), axis=0) \
        .transpose(0, 1, 3, 2, 4).reshape(B, T * bs, KVH, D)
    v = jnp.take(jnp.asarray(vc), jnp.asarray(bt), axis=0) \
        .transpose(0, 1, 3, 2, 4).reshape(B, T * bs, KVH, D)
    kr = jnp.repeat(k, H // KVH, axis=2)
    vr = jnp.repeat(v, H // KVH, axis=2)
    key_pos = np.arange(T * bs)
    mask = key_pos[None, None, :] <= pos[:, :, None]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * (1.0 / math.sqrt(D))
    s = jnp.where(jnp.asarray(mask)[:, None], s, -1e30)
    old = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vr)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(old),
                               rtol=1e-5, atol=1e-5)
    # the grouped-einsum path never materializes a [B, K, H, D] cache
    jaxpr = str(jax.make_jaxpr(
        lambda *a: paged_attention(*a, impl="reference"))(
            q, kc, vc, bt, pos))
    assert f"({B}, {T * bs}, {H}, {D})" not in jaxpr


@pytest.mark.parametrize("block_r", [256, 512])
def test_wide_row_blocks_parity_chunked_prefill(block_r):
    """Row blocks past the old 128 cap, prefill-like row counts: 288
    rows (C·rep = 72·4) split across two 256-row blocks or pad into one
    512-row block — either way bitwise-masked parity with the XLA
    reference on every valid row."""
    B, C, H, KVH, D, bs, T = 1, 72, 8, 2, 8, 4, 4
    _, _, kc, vc, bt = _paged_case(18, B, 16, H, KVH, D, bs, T)
    rng = np.random.default_rng(19)
    q = rng.normal(size=(B, C, H, D)).astype(np.float32)
    lens = np.array([15], np.int32)
    pos = np.arange(C, dtype=np.int32)[None, :] % 15
    ref = paged_attention(q, kc, vc, bt, jnp.asarray(pos),
                          impl="reference")
    ker = paged_flash_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(lens),
        block_r=block_r, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), **TOL)


# ------------------------------------------------- a group of P pages
@pytest.mark.parametrize("pp", [2, 4, 8])
@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2)])
def test_group_edges_with_idle_slots_between_live_ones(H, KVH, pp,
                                                       group_of):
    """Live pages equal to k·P, k·P + 1 and fewer than P, in a table
    that P does not divide (2·P + 3 slots), with ``lens = 0`` idle slots
    (every table row the trash block, the row below position 0) between
    the live ones, as the engine's decode batch has them: each against
    the reference, the idle ones zero, and junk in every dead slot
    unreachable."""
    group_of(pp)
    bs, D = 4, 8
    T = 2 * pp + 3
    lens = np.array([2 * pp * bs, 0, 2 * pp * bs + 1, 0,
                     max(1, (pp - 1) * bs - 1), bs], np.int32)
    B = len(lens)
    _, _, kc, vc, bt = _paged_case(20, B, T * bs, H, KVH, D, bs, T)
    bt[lens == 0] = 0
    rng = np.random.default_rng(21)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    pos = (lens - 1)[:, None]
    ref, ker = _both(q, kc, vc, bt, jnp.asarray(pos), lens)
    assert np.all(np.isfinite(ker))
    np.testing.assert_allclose(ker, ref, **TOL)
    assert not ker[lens == 0].any() and not ref[lens == 0].any()
    assert ker[lens > 0].any(axis=(1, 2, 3)).all()
    for b in range(B):
        dead = max(1, -(-int(lens[b]) // bs))
        if lens[b]:
            kc[bt[b, dead:]] = 1e3
            vc[bt[b, dead:]] = -1e3
    _, ker2 = _both(q, kc, vc, bt, jnp.asarray(pos), lens)
    np.testing.assert_array_equal(ker, ker2)


@pytest.mark.parametrize("pp", [2, 4])
@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2)])
def test_padded_chunk_tail_in_a_partly_live_group(H, KVH, pp, group_of):
    """A prefill chunk whose last rows are padding: the engine gives
    them the positions after the prompt's end, ≥ ``lens``, inside the
    table. The last group's pages past the last live one are not
    fetched, so without the per-page mask those rows would score
    whatever the buffer held there. Valid rows match the reference;
    EVERY row, padded ones too, matches the one-page-a-step kernel,
    where a live group has no such page."""
    bs, D, T, C = 4, 8, 7, 8
    start, n = 6, 5                     # lens 11: 3 live pages of 7
    _, _, kc, vc, bt = _paged_case(22, 1, T * bs, H, KVH, D, bs, T)
    rng = np.random.default_rng(23)
    q = rng.normal(size=(1, C, H, D)).astype(np.float32)
    pos = jnp.asarray(start + np.arange(C, dtype=np.int32)[None, :])
    lens = np.array([start + n], np.int32)
    group_of(pp)
    ref, ker = _both(q, kc, vc, bt, pos, lens)
    np.testing.assert_allclose(ker[:, :n], ref[:, :n], **TOL)
    group_of(1)
    _, one = _both(q, kc, vc, bt, pos, lens)
    np.testing.assert_allclose(ker, one, **TOL)


#: the cells' four calls (benchmarks/configs/*.json, traffic/*.json):
#: rows a kv head, kv heads, head_dim, block_r, table slots, the P
#: each gets
CELL_SHAPES = {
    "chat_decode": (1, 16, 256, 8, 128, 16),
    "chat_prefill": (256, 16, 256, 128, 128, 32),
    "docqa_decode": (4, 8, 128, 16, 256, 32),
    "docqa_prefill": (1024, 8, 128, 512, 256, 32),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_pages_per_step_follows_the_vmem_budget(cell):
    """P comes from the call's shapes and the budget alone: at the
    cells' shapes (bf16 pages of 16 tokens) the chosen group's buffers
    fit ``_VMEM_BUDGET`` and the next larger group, where there is one,
    does not; an MHA page four times as large gets a smaller group,
    and a table shorter than the group a smaller one too."""
    rows, kvh, d, block_r, table, want = CELL_SHAPES[cell]
    pp = paged_pages_per_step(rows, kvh, 16, d, jnp.bfloat16, table,
                              block_r=block_r, chip="v5e")
    assert pp == want
    br = pf._row_block(rows, d, jnp.bfloat16, block_r, "v5e")
    hb = pf._heads_per_step(kvh, br)
    assert pf._step_vmem_bytes(pp, hb, 16, d, 2, br) <= pf._VMEM_BUDGET
    assert pf._VMEM_BUDGET <= 16 << 20      # the default scoped limit
    for bigger in [g for g in pf._PAGE_GROUPS if g > pp]:
        assert pf._step_vmem_bytes(bigger, hb, 16, d, 2, br) \
            > pf._VMEM_BUDGET
    # a page of 64 MHA heads x 256: 512 KB, four of them at a time
    assert paged_pages_per_step(1, 64, 16, 256, jnp.bfloat16, table,
                                block_r=8, chip="v5e") < pp
    # a short table gets the smallest group that holds all of it
    assert [paged_pages_per_step(rows, kvh, 16, d, jnp.bfloat16, t,
                                 block_r=block_r, chip="v5e")
            for t in (1, 3, 4, 5, 16, 17)] == [1, 4, 4, 8, 16, min(32, pp)]


def test_paged_grid_steps_accounting():
    """What the engine books a decode step: ``batch · ceil(T / P)``
    steps, ``Σ ceil(pages / P)`` of them live (none of a slot's that
    holds no sequence)."""
    pages = paged_work_pages(
        np.array([0, 1, 64, 65, 128, 2048, 5000], np.int64), 16)
    np.testing.assert_array_equal(pages, [0, 1, 4, 5, 8, 128, 313])
    for pp in (1, 4, 8, 16):
        steps, live = paged_grid_steps(pages, 128, pp)
        assert steps == len(pages) * -(-128 // pp)
        assert live == sum(-(-min(int(p), 128) // pp) for p in pages)
    assert paged_grid_steps(pages, 100, 8) == (7 * 13, 0 + 1 + 1 + 1 + 1
                                               + 13 + 13)
    # a decode batch of slots that hold nobody: every step a dead one
    assert paged_grid_steps(paged_work_pages(np.array([-1, 0, -1]), 16),
                            128, 8) == (3 * 16, 0)


# ------------------------------------------------ autotune / disk cache
def test_default_paged_block_r_shapes():
    assert default_paged_block_r(2, 32, chip="cpu") == 8
    assert default_paged_block_r(100, 32, chip="cpu") == 104
    assert default_paged_block_r(1000, 32, chip="cpu") == 128
    assert default_paged_block_r(1000, 128, chip="v4") == 256
    assert default_paged_block_r(1000, 256, chip="v4") == 128


def test_autotune_paged_block_r_times_and_persists(tmp_path,
                                                   monkeypatch):
    """Injected timer picks the fastest candidate; the winner lands in
    the SAME on-disk JSON as the flash autotuner (``paged|`` keys) and
    a fresh process (cleared in-memory cache) reloads it without
    re-timing."""
    import json
    import ray_tpu.ops.paged_flash as pf

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(pf, "_PAGED_AUTOTUNE_CACHE", {})
    calls = []

    def timer(br):
        calls.append(br)
        return abs(br - 32) + 1.0     # 32 wins

    win = autotune_paged_block_r(16, 8, 256, 64, timer=timer,
                                 chip="v5e")
    assert win == 32 and calls
    path = tmp_path / "flash_autotune.json"
    data = json.loads(path.read_text())
    paged_keys = [k for k in data if k.startswith("paged|v5e|")]
    assert paged_keys and data[paged_keys[0]][0] == 32
    # fresh process: in-memory cache empty, disk hit, timer NOT called
    monkeypatch.setattr(pf, "_PAGED_AUTOTUNE_CACHE", {})
    calls.clear()
    assert autotune_paged_block_r(16, 8, 256, 64, timer=timer,
                                  chip="v5e") == 32
    assert not calls


def test_autotune_large_prefill_window_picks_past_128(tmp_path,
                                                      monkeypatch):
    """A ≥4k-row chunked-prefill window can win at block_r > 128: with
    a timer that rewards wider blocks the tuner must consider the 256
    and 512 candidates (not clamp at the old decode cap) and persist
    the >128 winner under its paged| disk key."""
    import json
    import ray_tpu.ops.paged_flash as pf

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(pf, "_PAGED_AUTOTUNE_CACHE", {})
    timed = []

    def timer(br):
        timed.append(br)
        return 1.0 / br              # wider is strictly faster

    win = autotune_paged_block_r(16, 256, 4096, 128, timer=timer,
                                 chip="v5e")
    assert win == 512 and {256, 512} <= set(timed)
    data = json.loads((tmp_path / "flash_autotune.json").read_text())
    key = [k for k in data if k.startswith("paged|v5e|")]
    assert key and data[key[0]][0] == 512
    # reload path honours the wide winner too
    monkeypatch.setattr(pf, "_PAGED_AUTOTUNE_CACHE", {})
    assert autotune_paged_block_r(16, 256, 4096, 128,
                                  timer=lambda br: 1.0,
                                  chip="v5e") == 512


def test_autotune_off_tpu_returns_default_without_running(monkeypatch):
    import ray_tpu.ops.paged_flash as pf
    monkeypatch.setattr(pf, "_PAGED_AUTOTUNE_CACHE", {})
    monkeypatch.setenv("RAY_TPU_FLASH_AUTOTUNE_CACHE", "0")
    assert autotune_paged_block_r(16, 16, 8, 32, chip="cpu") == \
        default_paged_block_r(8, 32, chip="cpu")


def test_flash_disk_cache_ignores_foreign_paged_keys(tmp_path,
                                                     monkeypatch):
    """The flash loader's bulk merge must skip paged| entries (and vice
    versa the paged lookup is exact-key, so flash keys never collide)."""
    import importlib
    import json
    fa = importlib.import_module("ray_tpu.ops.flash_attention")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    path = tmp_path / "flash_autotune.json"
    path.write_text(json.dumps({
        f"paged|cpu|{jax.__version__}|16|8|256|64": [32, 32],
        f"cpu|{jax.__version__}|128|64|1": [256, 512],
    }))
    monkeypatch.setattr(fa, "_DISK_CACHE_LOADED", False)
    monkeypatch.setattr(fa, "_AUTOTUNE_CACHE", {})
    fa._load_disk_cache()
    assert fa._AUTOTUNE_CACHE == {("cpu", 128, 64, True): (256, 512)}


@pytest.mark.parametrize("pp", [None, 2])
@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2)])
@pytest.mark.parametrize("layer", [0, 2])
def test_whole_pool_with_layer_index_equals_that_layers_slice(
        layer, H, KVH, pp, group_of):
    """The wrapper takes the whole ``[L, N, KVH, bs, D]`` pool and a
    layer index (traced, as a layer scan hands it over) and reads the
    pages ``(layer, block)``: bit for bit the call on that layer's own
    4-D slice, for the first and the last layer, kernel and reference —
    with every page of the 3-slot table in one group, and with two
    groups of two pages."""
    group_of(pp)
    L, B, D, bs, T, C = 3, 2, 8, 4, 3, 2
    rng = np.random.default_rng(11)
    kp = jnp.asarray(rng.normal(size=(L, 1 + B * T, KVH, bs, D))
                     .astype(np.float32))
    vp = jnp.asarray(rng.normal(size=kp.shape).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(B, C, H, D)).astype(np.float32))
    bt = jnp.asarray(rng.permutation(np.arange(1, 1 + B * T))
                     .astype(np.int32).reshape(B, T))
    pos = jnp.asarray(np.array([[5, 6], [9, 10]], np.int32))
    lens = jnp.asarray(np.array([7, 11], np.int32))

    whole = jax.jit(lambda ly: paged_flash_attention(
        q, kp, vp, bt, pos, lens, layer=ly, interpret=True))(
            jnp.int32(layer))
    sliced = paged_flash_attention(q, kp[layer], vp[layer], bt, pos, lens,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(sliced))
    for impl in ("interpret", "reference"):
        whole = jax.jit(lambda ly: paged_attention(
            q, kp, vp, bt, pos, layer=ly, lens=lens, impl=impl))(
                jnp.int32(layer))
        sliced = paged_attention(q, kp[layer], vp[layer], bt, pos,
                                 lens=lens, impl=impl)
        np.testing.assert_array_equal(np.asarray(whole),
                                      np.asarray(sliced))
    # the other layers are different data: the index is not ignored
    other = paged_attention(q, kp[1], vp[1], bt, pos, lens=lens,
                            impl="reference")
    assert not np.array_equal(np.asarray(whole), np.asarray(other))


def test_pool_rank_and_layer_must_agree():
    kp = jnp.zeros((2, 3, 1, 4, 8))
    q, bt = jnp.zeros((1, 1, 1, 8)), jnp.ones((1, 2), jnp.int32)
    pos = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="layer"):
        paged_attention(q, kp, kp, bt, pos, impl="reference")
    with pytest.raises(ValueError, match="layer"):
        paged_attention(q, kp[0], kp[0], bt, pos, layer=0,
                        impl="reference")


# ------------------------------------------------- a sliding window
def _windowed_reference(q, k_seq, v_seq, pos, window):
    """``attention_reference`` over the ordered sequence with the mask
    of a window layer: key <= query, key > query - window. GQA by
    repeating the (tiny) ordered K/V."""
    rep = q.shape[2] // k_seq.shape[2]
    keys = np.arange(k_seq.shape[1])[None, None, None, :]
    at = np.asarray(pos)[:, None, :, None]
    mask = (keys <= at) & (keys > at - window)
    return np.asarray(attention_reference(
        jnp.asarray(q), jnp.repeat(jnp.asarray(k_seq), rep, axis=2),
        jnp.repeat(jnp.asarray(v_seq), rep, axis=2),
        mask=jnp.asarray(mask)))


@pytest.mark.parametrize("pp", [None, 2, 4])
@pytest.mark.parametrize("H,KVH", [(6, 2), (8, 2)])
@pytest.mark.parametrize("impl", ["interpret", "reference"])
def test_window_decode_against_the_ordered_sequence(impl, H, KVH, pp,
                                                    group_of):
    """Decode with a window of 10 over sequences of 5, 23 and 38 keys
    (inside the window, a window and a bit, several groups behind it):
    3 and 4 query rows a kv head, one of them no whole sublane tile."""
    group_of(pp)
    B, D, bs, T, W = 3, 16, 4, 10, 10
    k_seq, v_seq, kc, vc, bt = _paged_case(10, B, 40, H, KVH, D, bs, T)
    q = np.random.default_rng(11).normal(size=(B, 1, H, D)) \
        .astype(np.float32)
    lens = np.array([5, 23, 38], np.int32)
    pos = (lens - 1)[:, None]
    got = paged_attention(q, kc, vc, bt, jnp.asarray(pos),
                          lens=jnp.asarray(lens), impl=impl, window=W)
    np.testing.assert_allclose(
        np.asarray(got), _windowed_reference(q, k_seq, v_seq, pos, W),
        **TOL)
    # the window does something: without it the long ones differ
    wide = paged_attention(q, kc, vc, bt, jnp.asarray(pos),
                           lens=jnp.asarray(lens), impl=impl)
    assert np.abs(np.asarray(wide) - np.asarray(got))[1:].max() > 1e-3


@pytest.mark.parametrize("pp", [None, 2])
@pytest.mark.parametrize("block_r", [None, 8])
def test_a_chunk_that_straddles_the_window(block_r, pp, group_of):
    """A chunk of 12 queries at positions 17..28 with a window of 10:
    its first rows see keys the last ones do not and the other way
    round, over row blocks whose first group differs (block_r 8: three
    blocks of 24 rows a kv head), with a padded tail."""
    group_of(pp)
    B, C, H, KVH, D, bs, T, W = 2, 12, 4, 2, 8, 4, 8, 10
    k_seq, v_seq, kc, vc, bt = _paged_case(12, B, 32, H, KVH, D, bs, T)
    q = np.random.default_rng(13).normal(size=(B, C, H, D)) \
        .astype(np.float32)
    lens = np.array([29, 26], np.int32)        # the second: 3 rows padding
    pos = np.stack([np.arange(C, dtype=np.int32) + 17] * B)
    want = _windowed_reference(q, k_seq, v_seq, pos, W)
    for impl in ("interpret", "reference"):
        got = np.asarray(paged_attention(
            q, kc, vc, bt, jnp.asarray(pos), lens=jnp.asarray(lens),
            impl=impl, window=W, block_r=block_r))
        np.testing.assert_allclose(got[0], want[0], **TOL)
        np.testing.assert_allclose(got[1, :9], want[1, :9], **TOL)


@pytest.mark.parametrize("impl", ["interpret", "reference"])
def test_a_short_table_shifted_behind_the_window_equals_the_whole(impl):
    """The masks see ``query - key`` alone: a table that starts at the
    page of the first key the first query sees, with positions and
    lengths counted from that page's first position, gives what the
    whole table gives, decode and chunk alike, and reads no page
    behind it (filled with NaN here)."""
    B, H, KVH, D, bs, T, W = 2, 8, 2, 8, 4, 12, 10
    k_seq, v_seq, kc, vc, bt = _paged_case(14, B, 48, H, KVH, D, bs, T)
    rng = np.random.default_rng(15)
    for C, start in ((1, 41), (6, 30)):
        q = rng.normal(size=(B, C, H, D)).astype(np.float32)
        pos = np.stack([np.arange(C, dtype=np.int32) + start] * B)
        lens = np.full((B,), start + C, np.int32)
        whole = np.asarray(paged_attention(
            q, kc, vc, bt, jnp.asarray(pos), lens=jnp.asarray(lens),
            impl=impl, window=W))
        first = max(0, start - W + 1) // bs          # a page's index
        width = (start + C - 1) // bs - first + 1
        short = np.zeros((B, width + 1), np.int32)   # a trash slot behind
        short[:, :width] = bt[:, first:first + width]
        behind = np.unique(bt[:, :first])
        kn, vn = kc.copy(), vc.copy()
        kn[behind] = np.nan
        vn[behind] = np.nan
        got = np.asarray(paged_attention(
            q, kn, vn, jnp.asarray(short), jnp.asarray(pos - first * bs),
            lens=jnp.asarray(lens - first * bs), impl=impl, window=W))
        np.testing.assert_allclose(got, whole, **TOL)
        np.testing.assert_allclose(
            whole, _windowed_reference(q, k_seq, v_seq, pos, W), **TOL)


def test_window_refuses_a_latent_pool():
    with pytest.raises(ValueError, match="no sliding window"):
        paged_flash_attention(
            jnp.zeros((1, 1, 2, 8)), jnp.zeros((1, 2, 1, 4, 8)), None,
            jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.int32),
            jnp.ones((1,), jnp.int32), layer=0, v_width=4, window=4,
            interpret=True)


@pytest.mark.parametrize("window", [0, 40])
@pytest.mark.parametrize("seed", [21, 61])
def test_the_scores_are_float32_and_bf16_scores_are_told(seed, window,
                                                         monkeypatch):
    """bf16 q, K and V with scores of order 16 (a sharp softmax), decode
    over 37, 90 and 128 keys: the kernel's output lies within 0.4% (rms,
    of the output's rms) of ``attention_reference`` in float32 on the
    same bf16 numbers, which is the rounding of its bf16 output (0.12 -
    0.15%), and the same kernel with its scores rounded to bf16 ahead
    of the softmax does not (1.2 - 1.3%): a kernel that drops the
    scores' precision is told here, which the benchmark's ``logits``
    cannot (PERF.md section 6, PR 45: 1.9% against 1.7 sound)."""
    B, H, KVH, D, bs, T, limit = 3, 8, 2, 64, 16, 8, 4e-3
    k_seq, v_seq, kc, vc, bt = _paged_case(seed, B, T * bs, H, KVH, D,
                                           bs, T)
    q = 16 * np.random.default_rng(seed + 1).normal(size=(B, 1, H, D))
    b16 = lambda a: jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
    f32 = lambda a: np.asarray(b16(a).astype(jnp.float32))
    lens = np.array([37, 90, 128], np.int32)
    pos = (lens - 1)[:, None]
    want = _windowed_reference(f32(q), f32(k_seq), f32(v_seq), pos,
                               window or T * bs)

    def off():
        got = paged_attention(b16(q), b16(kc), b16(vc), bt,
                              jnp.asarray(pos), lens=jnp.asarray(lens),
                              impl="interpret", window=window)
        assert got.dtype == jnp.bfloat16
        err = np.asarray(got.astype(jnp.float32)) - want
        return np.sqrt(np.mean(err ** 2) / np.mean(want ** 2))

    assert off() < limit / 2.5
    real = jax.lax.dot_general

    def bf16_scores(a, b, dims, **kw):
        out = real(a, b, dims, **kw)
        if dims == (((1,), (1,)), ((), ())):       # q k^T, the scores
            out = out.astype(jnp.bfloat16).astype(jnp.float32)
        return out

    monkeypatch.setattr(jax.lax, "dot_general", bf16_scores)
    assert off() > limit * 2.5


# --------------------------- a row block's bound: its highest live row
#: chunks of 16 token rows in four row blocks of four tokens, pages of
#: four tokens, two pages a grid step; (start, live tokens) a sequence
CHUNKS = {
    "padding_blocks_behind_a_prefix": [(13, 3)],   # three blocks of none
    "first_chunk_at_position_0": [(0, 16)],        # each to its diagonal
    "last_block_partly_live": [(5, 14)],           # two tokens of padding
    "a_batch_of_them": [(13, 3), (0, 16), (5, 14), (9, 1)],
}


def _chunk_form(form, chunks, seed):
    """``(run, pools, bt)`` of one chunk call of ``form`` ("dense" |
    "window" | "latent") over ``chunks``: ``run(impl, *pools)`` is the
    call's ``[B, 16, ...]`` result under ``impl``, ``pools`` the arrays
    whose axis 0 counts pages (trash page 0 first), ``bt`` the table."""
    C, bs, T = 16, 4, 8
    B = len(chunks)
    start, n = np.array(chunks, np.int32).T
    pos = jnp.asarray(start[:, None] + np.arange(C, dtype=np.int32))
    lens = jnp.asarray(start + n)
    rng = np.random.default_rng(seed)
    if form == "latent":
        from ray_tpu.ops.latent_attention import (latent_attention,
                                                  latent_row_width)
        H, dn, dr, dv, rank = 4, 16, 8, 16, 128
        pool = rng.normal(size=(1 + B * T, 1, bs,
                                latent_row_width(rank, dr)))
        pool[..., rank + dr:] = 0.0
        bt = 1 + np.arange(B * T, dtype=np.int32).reshape(B, T)
        q = [jnp.asarray(rng.normal(size=s) * w, jnp.float32)
             for s, w in (((B, C, H, dn), 1), ((B, C, H, dr), 1),
                          ((rank, H, dn), .1), ((rank, H, dv), .1))]

        def run(impl, pool):
            return np.asarray(latent_attention(
                *q, jnp.asarray(pool, jnp.float32)[None], jnp.asarray(bt),
                pos, layer=0, lens=lens, sm_scale=(dn + dr) ** -0.5,
                impl=impl, block_r=4 * H))
        return run, (pool.astype(np.float32),), bt
    H, KVH, D = 4, 2, 8
    _, _, kc, vc, bt = _paged_case(seed, B, T * bs, H, KVH, D, bs, T)
    q = rng.normal(size=(B, C, H, D)).astype(np.float32)

    def run(impl, kc, vc):
        return np.asarray(paged_attention(
            q, kc, vc, bt, pos, lens=lens, impl=impl,
            block_r=4 * H // KVH, window=6 if form == "window" else 0))
    return run, (kc, vc), bt


@pytest.mark.parametrize("case", sorted(CHUNKS))
@pytest.mark.parametrize("form", ["dense", "window", "latent"])
def test_a_row_block_folds_no_page_past_its_highest_live_row(
        form, case, group_of):
    """A chunk call with more than one row block: every live row is the
    reference's, every padded row is finite (zero in a block of padding
    alone), and for each row block in turn NaN planted in every page
    past ITS bound (the page of its highest live position the last;
    none where it has no live row) and in the trash page reaches no
    row of that block: it fetched none of them."""
    group_of(2)
    chunks = CHUNKS[case]
    run, pools, bt = _chunk_form(form, chunks, seed=31)
    want, got = run("reference", *pools), run("interpret", *pools)
    assert np.all(np.isfinite(got))
    for b, (start, n) in enumerate(chunks):
        np.testing.assert_allclose(got[b, :n], want[b, :n], **TOL)
    for r in range(4):
        tokens = slice(4 * r, 4 * r + 4)
        poisoned = [_poisoned(pool) for pool in pools]
        for b, (start, n) in enumerate(chunks):
            top = start + min(n, 4 * r + 4) - 1 if n > 4 * r else -1
            for pool in poisoned:
                pool[bt[b, (top + 4) // 4:]] = np.nan
            if top < 0:
                assert not got[b, tokens].any()
        np.testing.assert_array_equal(
            run("interpret", *poisoned)[:, tokens], got[:, tokens])


def _kernel_of(form, batch, chunk, block_r):
    """The ``pallas_call`` equation of one call of ``form`` at 8 heads
    on 2 kv heads (a latent pool: on one) over a table of 8 pages."""
    D, bs, T = 128, 16, 8
    s = jax.ShapeDtypeStruct
    pool = s((1 + batch * T, 1 if form == "latent" else 2, bs, D),
             jnp.float32)
    kw = {"latent": dict(v_width=64), "window": dict(window=24),
          "dense": {}}[form]
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, bt, pos, lens: paged_flash_attention(
            q, k, v, bt, pos, lens, block_r=block_r, interpret=True, **kw)
    )(s((batch, chunk, 8, D), jnp.float32), pool,
      None if form == "latent" else pool, s((batch, T), jnp.int32),
      s((batch, chunk), jnp.int32), s((batch,), jnp.int32))
    call, = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    return call


def _equations(jaxpr, found):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) \
                    else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _equations(inner, found)
    return found


def _int_reductions(call):
    return [e for e in _equations(call.params["jaxpr"], [])
            if e.primitive.name == "reduce_max"
            and e.outvars[0].aval.dtype == jnp.int32]


#: equations of the kernel's body at the commit before the bound
#: (28b846f, counted with _equations at _kernel_of's shapes); the latent
#: form's had seven more, its test of a fourth scalar with the live rows
ONE_ROW_BLOCK = {"dense": 219, "window": 261, "latent": 175 - 7}


@pytest.mark.parametrize("call", ["decode", "verify"])
@pytest.mark.parametrize("form", sorted(ONE_ROW_BLOCK))
def test_a_one_row_block_call_traces_the_kernel_it_traced(form, call):
    """A decode step (a row a head) and a verify step (k + 1 = 5 rows a
    head) have one row block: the bound is the length itself, and the
    kernel is the one the parent traced, equation for equation, with no
    reduction over the position column, no scratch word for it and the
    operands it had (three scalars, a window's fourth; q, positions,
    the pools)."""
    eqn = _kernel_of(form, 4, {"decode": 1, "verify": 5}[call], None)
    grid = eqn.params["grid_mapping"]
    assert grid.grid[2] == 1
    assert len(_equations(eqn.params["jaxpr"], [])) == ONE_ROW_BLOCK[form]
    assert not _int_reductions(eqn)
    assert grid.num_scratch_operands == (5 if form == "latent" else 6)
    assert grid.num_index_operands == (4 if form == "window" else 3)
    assert len(eqn.invars) == grid.num_index_operands \
        + (3 if form == "latent" else 4)


@pytest.mark.parametrize("form", sorted(ONE_ROW_BLOCK))
def test_a_chunk_call_takes_the_bound_in_without_a_new_operand(form):
    """64 tokens in four row blocks (a latent pool's one key head:
    eight): the kernel reduces the position
    column once, keeps the result in one more scratch word, and the
    call's operands are the one-row-block call's."""
    eqn, one = _kernel_of(form, 1, 64, 64), _kernel_of(form, 4, 1, None)
    grid = eqn.params["grid_mapping"]
    assert grid.grid[2] == (8 if form == "latent" else 4)
    assert len(_int_reductions(eqn)) == 1
    assert grid.num_scratch_operands \
        == one.params["grid_mapping"].num_scratch_operands + 1
    assert grid.num_index_operands \
        == one.params["grid_mapping"].num_index_operands
    assert len(eqn.invars) == len(one.invars)


@pytest.mark.parametrize("start,chunk,n,rep,block_r", [
    (21760, 2048, 64, 6, 512),      # repoqa's question: 1 of 24
    (0, 2048, 2048, 6, 512),        # a document's chunk: all 24
    (4096, 2048, 1500, 6, 512),     # a document's ragged end
    (300, 256, 48, 4, 512),         # docqa's question: 1 of 2
    (0, 256, 256, 4, 512),
    (0, 256, 100, 1, 128),          # chat: 1 of 2
    (128, 256, 129, 1, 128),        # one token into the second block
    (19000, 2048, 64, 128, 512),    # openPangu's question: 16 of 512
    (7, 24, 3, 4, 8),               # two tokens a block, the second half
    (7, 24, 1, 4, 16),
    (0, 5, 5, 3, 8),                # 15 rows in two blocks, one padded
    (40, 16, 9, 2, None),           # the default block: one of them
])
def test_paged_row_blocks_is_the_kernels_own_count(start, chunk, n, rep,
                                                   block_r):
    """What the engine books a chunk equals a count, row block by row
    block, of those with a row the kernel calls live (``0 <= position <
    lens``) in the rows the call builds: tokens in order, ``rep`` heads'
    rows each, padded with position −1 to whole blocks."""
    blocks, live = pf.paged_row_blocks(chunk * rep, n * rep, 128,
                                       jnp.bfloat16, block_r=block_r,
                                       chip="v5e")
    br = pf._row_block(chunk * rep, 128, jnp.bfloat16, block_r, "v5e")
    pos = np.repeat(start + np.arange(chunk), rep)
    pos = np.pad(pos, (0, -len(pos) % br), constant_values=-1)
    is_live = ((pos >= 0) & (pos < start + n)).reshape(-1, br).any(axis=1)
    assert (blocks, live) == (len(is_live), int(is_live.sum()))
    assert is_live[:live].all()


# ------------------ a chunk call's step serves more kv heads a fetch
@pytest.fixture
def rows_a_step(monkeypatch):
    """``rows_a_step(narrow, chunk)`` sets the query rows a grid step
    may carry (``_MAX_ROWS_PER_STEP``) and those of a chunk call's on
    the dense form (``_MAX_ROWS_PER_CHUNK_STEP``): at these tiny row
    blocks the real 512 / 2048 hold every kv head either way."""
    def set_(narrow, chunk):
        monkeypatch.setattr(pf, "_MAX_ROWS_PER_STEP", narrow)
        monkeypatch.setattr(pf, "_MAX_ROWS_PER_CHUNK_STEP", chunk)
    return set_


def _grid_of(*args, **kw):
    """The grid of the one ``pallas_call`` of ``paged_flash_attention(q,
    k, v, bt, pos, lens[, chosen], **kw)``; arrays or their shapes."""
    jaxpr = jax.make_jaxpr(lambda *a: paged_flash_attention(
        *a[:6], chosen=a[6] if len(a) > 6 else None, interpret=True,
        **kw))(*args)
    call, = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    return call.params["grid_mapping"].grid


#: CHUNKS and chunks deep in a context, (start, live tokens) a sequence: a row block
#: sweeps up to eight page groups of 8 keys, all but the last one or two
#: wholly under every row's position (96% of a document chunk's tiles in
#: the window cell: PERF.md section 6, PR 61)
ALL_CHUNKS = {
    **CHUNKS,
    "deep_in_a_long_context": [(44, 16)],
    "a_ragged_end_deep_in_a_context": [(37, 11)],
    "two_sequences_of_unlike_length": [(44, 16), (3, 9)],
}


@pytest.mark.parametrize("H,KVH,wide", [(4, 4, 4), (8, 2, 2), (12, 6, 3)])
@pytest.mark.parametrize("case", sorted(ALL_CHUNKS))
def test_more_kv_heads_a_step_is_bit_for_bit_one_head_a_step(
        case, H, KVH, wide, group_of, rows_a_step):
    """The dense form's chunk call with up to four kv heads' row blocks
    a grid step (four of 4, two of 2, three of 6: a divisor) against
    the same call held to one head a step, the grid it had: a quarter,
    half, a third of the sweeps over the kv-head axis, and every row of
    the result, live or padding, has the same bits: a head's arithmetic
    is its own, in the same order, over the same page groups. Every
    live row is the float32 reference's."""
    group_of(2)
    chunks = ALL_CHUNKS[case]
    C, D, bs, T, block_r = 16, 8, 4, 16, 8
    B = len(chunks)
    start, n = np.array(chunks, np.int32).T
    pos = jnp.asarray(start[:, None] + np.arange(C, dtype=np.int32))
    lens = jnp.asarray(start + n)
    _, _, kc, vc, bt = _paged_case(51, B, T * bs, H, KVH, D, bs, T)
    q = np.random.default_rng(52).normal(
        size=(B, C, H, D)).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
            jnp.asarray(bt), pos, lens)
    got = {}
    for name, chunk_rows in (("one", block_r), ("more", 4 * block_r)):
        rows_a_step(block_r, chunk_rows)
        grid = _grid_of(*args, block_r=block_r)
        assert grid[1] == (KVH if name == "one" else KVH // wide)
        assert grid[2] == C * (H // KVH) // block_r > 1
        got[name] = np.asarray(paged_flash_attention(
            *args, block_r=block_r, interpret=True))
    np.testing.assert_array_equal(got["more"], got["one"])
    want = np.asarray(paged_attention(q, kc, vc, bt, pos, impl="reference"))
    for b in range(B):
        np.testing.assert_allclose(got["more"][b, :n[b]], want[b, :n[b]],
                                   **TOL)


#: kv-head steps of the grid at 16 heads on 8 kv heads where a step may
#: carry 128 rows and a chunk's (128, 512): a chunk is 64 tokens in row
#: blocks of 64 rows (two heads a step, eight of the dense form's)
KV_HEAD_STEPS = {"decode": (1, 1), "verify": (1, 1), "latent": (1, 1),
                 "window": (4, 4), "selects": (4, 4), "dense": (4, 1)}


@pytest.mark.parametrize("form", sorted(KV_HEAD_STEPS))
def test_more_heads_a_step_are_the_dense_chunk_calls_alone(form,
                                                           rows_a_step):
    """By the call's static arguments: a one-row-block call (decode,
    verify), a window layer's chunk, a latent pool's (one key head) and
    a selecting chunk keep ``_heads_per_step``'s heads a step, whatever
    a chunk's step may carry; the dense form's chunk call takes all
    eight."""
    D, bs, T, H, KVH = 128, 16, 8, 16, 8
    s = jax.ShapeDtypeStruct
    chunk = {"decode": 1, "verify": 5}.get(form, 64)
    batch = 4 if chunk < 64 else 1
    kw = {"window": dict(window=24), "latent": dict(v_width=64)}.get(
        form, {})
    pool = s((1 + batch * T, 1 if form == "latent" else KVH, bs, D),
             jnp.float32)
    args = [s((batch, chunk, H, D), jnp.float32), pool,
            None if form == "latent" else pool, s((batch, T), jnp.int32),
            s((batch, chunk), jnp.int32), s((batch,), jnp.int32)]
    if form == "selects":
        args.append(s((batch, chunk, T * bs), jnp.bool_))
    steps = []
    for chunk_rows in (128, 512):
        rows_a_step(128, chunk_rows)
        steps.append(_grid_of(
            *args, block_r=None if chunk < 64 else 64, **kw)[1])
    assert tuple(steps) == KV_HEAD_STEPS[form]


@pytest.mark.parametrize("cell,kv_heads,block_r,head_dim,table,heads", [
    ("repoqa", 8, 512, 128, 4096, 4),       # 48 heads on 8
    ("docqa", 8, 512, 128, 256, 4),
    ("sessions64", 8, 512, 128, 512, 4),
    ("shared_docs12", 30, 512, 128, 512, 3),    # MHA at 30: a divisor
    ("reason48", 2, 512, 128, 512, 2),          # two kv heads in all
    # MHA at head_dim 256: four heads a step already, and eight would
    # halve the page group (16 KB a head a page): the step it had
    ("chat", 16, 128, 256, 128, 4),
])
def test_a_chunk_steps_heads_at_the_cells_shapes(cell, kv_heads, block_r,
                                                 head_dim, table, heads):
    """Heads a step of each cell's chunk call on its full-attention
    layers: up to four, a divisor of the kv heads, and never at the
    price of a smaller page group (the group is what the softmax folds
    at once: the same group is the same bits)."""
    def pages_of(hb):
        return pf._pages_per_step(hb, 16, head_dim, jnp.bfloat16, block_r,
                                  table)
    narrow = pf._heads_per_step(kv_heads, block_r)
    got = pf._chunk_heads_per_step(kv_heads, block_r, pages_of)
    assert got == heads and kv_heads % got == 0
    assert got * block_r <= pf._MAX_ROWS_PER_CHUNK_STEP
    assert pages_of(got) == pages_of(narrow) == 32


# ------------------------------------------------- a selection as a mask
#: B sequences of (first position, query tokens, live tokens) each; the
#: tables hold 8 pages of 4. ``k``: keys a query keeps; ``ties``: scores
#: drawn from four values, so that the threshold cuts a run of equals.
SELECTIONS = {
    # one query a sequence, 8 heads on a kv head: lengths past and under
    # k (a row that sees fewer keys than k keeps them all), a slot with
    # no sequence (lens -1: position -1), a length of one
    "decode_rep8": dict(H=32, KVH=4, k=6, ties=False, pp=4, block_r=None,
                        seqs=[(30, 1, 1), (3, 1, 1), (-1, 1, 0),
                              (0, 1, 1), (17, 1, 1)]),
    "decode_ties": dict(H=8, KVH=2, k=5, ties=True, pp=2, block_r=None,
                        seqs=[(21, 1, 1), (-1, 1, 0), (9, 1, 1)]),
    # the last group partly live (18 keys: pages 0-4 of groups of 4)
    "decode_part_group": dict(H=4, KVH=4, k=7, ties=False, pp=4,
                              block_r=None, seqs=[(17, 1, 1), (31, 1, 1)]),
    # a row block of queries, each with a selection of its own: two row
    # blocks a head, a ragged end (the second block of sequence 1 is
    # padding alone), tied scores
    "chunk": dict(H=8, KVH=2, k=6, ties=False, pp=2, block_r=8,
                  seqs=[(12, 16, 16), (5, 16, 7)]),
    "chunk_ties": dict(H=4, KVH=4, k=4, ties=True, pp=4, block_r=8,
                       seqs=[(0, 16, 16), (16, 16, 3)]),
    # five tokens (a verify call's): padded to one whole row block
    "chunk_padded": dict(H=8, KVH=2, k=3, ties=False, pp=None, block_r=None,
                         seqs=[(20, 5, 5), (2, 5, 5)]),
}


def _selected_softmax(q, k_seq, v_seq, chosen):
    """Plain float32 softmax of each query over the keys ``chosen [B,
    C, K]`` names; zero where it names none."""
    b, c, h, d = q.shape
    rep = h // k_seq.shape[2]
    k, v = (np.repeat(a, rep, axis=2) for a in (k_seq, v_seq))
    s = np.einsum("bchd,bkhd->bhck", q, k) / np.sqrt(d)
    s = np.where(chosen[:, None], s, -np.inf)
    top = np.max(s, axis=-1, keepdims=True)
    p = np.where(chosen[:, None], np.exp(s - np.where(
        np.isfinite(top), top, 0.0)), 0.0)
    p = p / np.where(p.sum(-1, keepdims=True) == 0, 1.0,
                     p.sum(-1, keepdims=True))
    return np.einsum("bhck,bkhd->bchd", p, v)


@pytest.mark.parametrize("case", sorted(SELECTIONS))
def test_a_selection_masks_the_kernels_read(case, group_of):
    """``chosen`` as an operand: every live row is the plain float32
    softmax over the keys its query selected (``topk_mask``: exact, ties
    to the lower index) and, for one query a sequence, what
    ``_decode_selected``'s gather of those rows gives; a row with no
    key chosen (a slot with ``lens <= 0``, a chunk's padding) is zero,
    and NaN planted in every page past a sequence's live ones and in
    the trash page reaches nothing."""
    from ray_tpu.ops.sparse_attention import (_chunk_masked,
                                              _decode_selected, topk_mask)
    spec = SELECTIONS[case]
    group_of(spec["pp"])
    H, KVH, k = spec["H"], spec["KVH"], spec["k"]
    D, bs, T = 8, 4, 8
    first, C, n = np.array(spec["seqs"], np.int32).T
    B, C = len(first), int(C[0])
    k_seq, v_seq, kc, vc, bt = _paged_case(71, B, T * bs, H, KVH, D, bs, T)
    rng = np.random.default_rng(72)
    q = rng.normal(size=(B, C, H, D)).astype(np.float32)
    pos = np.where(first[:, None] >= 0,
                   first[:, None] + np.arange(C, dtype=np.int32), -1)
    lens = np.where(first >= 0, first + n, first)
    scores = rng.integers(0, 4, size=(B, C, T * bs)).astype(np.float32) \
        if spec["ties"] else \
        rng.normal(size=(B, C, T * bs)).astype(np.float32)
    scores = np.where(np.arange(T * bs) <= pos[..., None], scores, -np.inf)
    chosen = np.asarray(topk_mask(jnp.asarray(scores), k))
    live = np.arange(C) < n[:, None]                       # [B, C]
    assert (chosen.sum(-1)[live] == np.minimum(pos + 1, k)[live]).all()
    if spec["ties"]:                 # the cut falls inside a run of equals
        kth = np.sort(scores, -1)[..., -k]
        assert ((scores == kth[..., None]).sum(-1)[live] > 1).any()

    def run(kc, vc):
        return np.asarray(paged_flash_attention(
            jnp.asarray(q), jnp.asarray(kc)[None], jnp.asarray(vc)[None],
            jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(lens), layer=0,
            block_r=spec["block_r"], interpret=True,
            chosen=jnp.asarray(chosen)))

    got = run(kc, vc)
    want = _selected_softmax(q, k_seq, v_seq, chosen)
    np.testing.assert_allclose(got[live], want[live], **TOL)
    assert not got[lens <= 0].any()
    if C == 1:
        other = np.asarray(_decode_selected(
            jnp.asarray(q), jnp.asarray(kc)[None], jnp.asarray(vc)[None],
            jnp.asarray(bt), jnp.asarray(scores), 0, k, D ** -0.5))
    else:
        other = np.asarray(_chunk_masked(
            jnp.asarray(q), jnp.asarray(kc)[None], jnp.asarray(vc)[None],
            jnp.asarray(bt), jnp.asarray(chosen), jnp.asarray(lens), 0,
            D ** -0.5))
    np.testing.assert_allclose(got[live], other[live], **TOL)
    kp, vp = _poisoned(kc), _poisoned(vc)
    for b in range(B):
        for pool in (kp, vp):
            pool[bt[b, max(-(-int(lens[b]) // bs), 0):]] = np.nan
    np.testing.assert_array_equal(run(kp, vp)[live], got[live])


@pytest.mark.parametrize("chunk", [1, 8])
def test_a_selection_masks_a_latent_pools_read(chunk, group_of):
    """The operand on the latent form (one pool whose page is key and
    value, 4 heads on its one key head): the kernel with ``chosen``
    equals the plain latent pass under the same mask
    (``latent_attention._blocked``), for one query a sequence and for a
    row block of queries, a slot with no sequence among them."""
    from ray_tpu.ops.latent_attention import _blocked, latent_row_width
    from ray_tpu.ops.sparse_attention import topk_mask
    group_of(2)
    H, rope, rank, bs, T, k = 4, 8, 128, 4, 8, 5
    first = np.array([20, -1, 3], np.int32) if chunk == 1 \
        else np.array([12, 0, 24], np.int32)
    B = len(first)
    rng = np.random.default_rng(91)
    row = latent_row_width(rank, rope)
    pool = rng.normal(size=(1 + B * T, 1, bs, row)).astype(np.float32)
    pool[..., rank + rope:] = 0.0
    bt = 1 + rng.permutation(B * T).astype(np.int32).reshape(B, T)
    pos = np.where(first[:, None] >= 0,
                   first[:, None] + np.arange(chunk, dtype=np.int32), -1)
    lens = np.where(first >= 0, first + chunk, first)
    q_abs = rng.normal(size=(B, chunk, H, rank)).astype(np.float32)
    q_rope = rng.normal(size=(B, chunk, H, rope)).astype(np.float32)
    scores = rng.normal(size=(B, chunk, T * bs)).astype(np.float32)
    scores = np.where(np.arange(T * bs) <= pos[..., None], scores, -np.inf)
    chosen = topk_mask(jnp.asarray(scores), k)
    scale = (16 + rope) ** -0.5
    want = np.asarray(_blocked(
        jnp.asarray(q_abs), jnp.asarray(q_rope), jnp.asarray(pool)[None],
        jnp.asarray(bt), jnp.asarray(pos), 0, jnp.asarray(lens), scale,
        chosen=chosen))
    q_lat = np.concatenate(
        [q_abs, q_rope, np.zeros((B, chunk, H, row - rank - rope),
                                 np.float32)], -1)
    got = np.asarray(paged_flash_attention(
        jnp.asarray(q_lat), jnp.asarray(_poisoned(pool))[None], None,
        jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(lens), layer=0,
        sm_scale=scale, block_r=8, interpret=True, v_width=rank,
        chosen=chosen))
    live = lens > 0
    np.testing.assert_allclose(got[live], want[live], **TOL)
    assert not got[~live].any()
