"""The latent (MLA) attention op: the paged kernel over a one-pool
latent cache (``paged_flash_attention`` with ``v_width``: one key head
whose page's first columns are the value), in Pallas interpret mode,
against the plain XLA path and against attention written out with every
head's key and value expanded; ragged lengths, idle slots, pages shared
between sequences, a chunk's padded rows."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.latent_attention import latent_attention, latent_row_width

H, DN, DR, DV, RANK, BS = 4, 16, 8, 16, 128, 8
ROW = latent_row_width(RANK, DR)
SCALE = (DN + DR) ** -0.5


def _inputs(batch, chunk, table, blocks, seed=0, layers=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pool = jax.random.normal(ks[0], (layers, blocks, 1, BS, ROW))
    pool = pool.at[..., RANK + DR:].set(0.0)       # the row's padding
    return dict(
        q_nope=jax.random.normal(ks[1], (batch, chunk, H, DN)),
        q_rope=jax.random.normal(ks[2], (batch, chunk, H, DR)),
        w_uk=jax.random.normal(ks[3], (RANK, H, DN)) * 0.1,
        w_uv=jax.random.normal(ks[4], (RANK, H, DV)) * 0.1,
        pool=pool)


def _written_out(a, bt, pos, layer):
    """Every head's keys and values expanded from the gathered rows,
    one masked softmax: [B, C, H, DV] in float32."""
    b, t = bt.shape
    rows = a["pool"][layer, bt, 0].reshape(b, t * BS, ROW)
    lat, k_rope = rows[..., :RANK], rows[..., RANK:RANK + DR]
    k = jnp.einsum("bkr,rhd->bkhd", lat, a["w_uk"])
    v = jnp.einsum("bkr,rhd->bkhd", lat, a["w_uv"])
    s = (jnp.einsum("bchd,bkhd->bhck", a["q_nope"], k)
         + jnp.einsum("bchd,bkd->bhck", a["q_rope"], k_rope)) * SCALE
    mask = jnp.arange(t * BS)[None, None] <= pos[:, :, None]
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), -1)
    return jnp.einsum("bhck,bkhd->bchd", p, v)


def _run(a, bt, pos, lens, layer, **kw):
    return latent_attention(
        a["q_nope"], a["q_rope"], a["w_uk"], a["w_uv"], a["pool"], bt, pos,
        layer=layer, lens=lens, sm_scale=SCALE, **kw)


@pytest.mark.parametrize("impl,key_block", [
    ("interpret", 512), ("reference", 16), ("reference", 512)])
def test_decode_over_ragged_lengths_idle_slots_and_shared_pages(
        impl, key_block, monkeypatch):
    """Four decode slots over a table of 12 pages: lengths 1, 37 (a
    ragged last page), the whole table, and an idle slot (length 0, its
    row below position 0, its table all trash page 0: zeros); slots 1
    and 2 share their first four pages, as two questions of one
    document do. A key block of 16 rows makes the plain path walk six
    blocks."""
    monkeypatch.setattr("ray_tpu.ops.latent_attention._KEY_BLOCK", key_block)
    table = 12
    a = _inputs(4, 1, table, 40)
    bt = np.zeros((4, table), np.int32)
    bt[0, :1] = [5]
    bt[1] = np.arange(6, 6 + table)
    bt[2] = np.arange(20, 20 + table)
    bt[2, :4] = bt[1, :4]
    lens = jnp.asarray([1, 37, table * BS, 0], jnp.int32)
    pos = (lens - 1)[:, None]
    got = _run(a, jnp.asarray(bt), pos, lens, 1, impl=impl)
    want = _written_out(a, jnp.asarray(bt), pos, 1)
    assert got.shape == (4, 1, H, DV)
    np.testing.assert_allclose(got[:3], want[:3], atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[3]).any()


@pytest.mark.parametrize("block_r,live", [(None, 24), (8, 24), (8, 3),
                                          (16, 1)])
def test_a_chunk_with_padded_rows_in_row_blocks(block_r, live):
    """A 24-token chunk behind 40 cached tokens, ``live`` of its rows
    real: the kernel in blocks of ``block_r`` rows (4 heads a token, so
    8 rows are two tokens) folds no page for a block past the live rows
    and returns what the plain path does for the live ones."""
    table, chunk, start = 16, 24, 40
    a = _inputs(1, chunk, table, 20, seed=1)
    bt = jnp.arange(1, 1 + table, dtype=jnp.int32)[None]
    pos = (start + jnp.arange(chunk, dtype=jnp.int32))[None]
    lens = jnp.asarray([start + live], jnp.int32)
    got = _run(a, bt, pos, lens, 0, impl="interpret", block_r=block_r)
    want = _run(a, bt, pos, lens, 0, impl="reference")
    np.testing.assert_allclose(got[:, :live], want[:, :live], atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(want[:, :live],
                               _written_out(a, bt, pos, 0)[:, :live],
                               atol=2e-5, rtol=2e-5)
    if block_r and live * H <= chunk * H - block_r:
        # a row block past the live rows ran no page: zeros out
        assert not np.asarray(got[:, -block_r // H:]).any()


def test_the_kernel_reads_the_layer_it_is_told():
    a = _inputs(2, 1, 4, 10, seed=2, layers=3)
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    lens = jnp.asarray([30, 17], jnp.int32)
    pos = (lens - 1)[:, None]
    outs = [_run(a, bt, pos, lens, jnp.int32(layer), impl="interpret")
            for layer in range(3)]
    for layer, got in enumerate(outs):
        np.testing.assert_allclose(got, _written_out(a, bt, pos, layer),
                                   atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(outs[0] - outs[1]).max()) > 1e-2


def test_impl_and_rows_are_checked():
    a = _inputs(1, 1, 2, 4)
    bt = jnp.asarray([[1, 2]], jnp.int32)
    args = (a, bt, jnp.asarray([[3]]), jnp.asarray([4]), 0)
    with pytest.raises(ValueError, match="needs a TPU"):
        _run(*args, impl="kernel")
    assert latent_row_width(512, 64) == 640
    assert latent_row_width(32, 16) == 128
    from ray_tpu.ops.paged_flash import paged_flash_attention
    with pytest.raises(ValueError, match="not both"):
        paged_flash_attention(a["q_nope"], a["pool"], a["pool"], bt,
                              jnp.asarray([[3]]), jnp.asarray([4]),
                              layer=0, v_width=RANK)
