"""The selecting, routing served forms (``index_topk``, ``qk_norm``,
``experts_per_token``) against the plain float32 reference
(``benchmarks/reference/keye.py``) on seeded weights, at a small size:
chunked prefill then paged decode of a context several times ``topk``
against the reference's full forward pass; the dropless layer with every
token forced onto the same experts; the exact top-k mask with ties; and
``topk >= window`` against the dense llama path, bit for bit."""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import keye
from ray_tpu.models import (TransformerConfig, decode_step, init_kv_cache,
                            init_params, prefill)
from ray_tpu.models.moe import topk_moe_mlp
from ray_tpu.ops import sparse_attention as SA

TINY = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, head_dim=16,
            n_kv_heads=2, d_ff=64, max_seq_len=256, rotary_dim=16,
            rope_base=1e4, block_style="llama", dtype=jnp.float32,
            paged_impl="reference", remat_policy="none")
KEYE = dict(TINY, n_experts=8, experts_per_token=2, expert_width=32,
            qk_norm=True, index_topk=32, index_heads=4, index_dim=8)
HP = dict(num_attention_heads=4, num_key_value_heads=2, rms_norm_eps=1e-6,
          rope_theta=1e4, indexer_num_heads=4, indexer_head_dim=8,
          indexer_layer_norm_eps=1e-6, topk=32, num_experts_per_tok=2,
          norm_topk_prob=True)
BS, TABLE = 16, 16


def _hp(**over):
    return tuple(sorted({**HP, **over}.items()))


def _through_cache(cfg, params, ids, prompt_len, chunk):
    """Logits of every position: the prompt in chunks of ``chunk``, then
    one decode step a token, through a paged cache of one sequence."""
    cache = init_kv_cache(cfg, 1 + TABLE, BS)
    bt = jnp.arange(1, 1 + TABLE, dtype=jnp.int32)[None]
    jp = jax.jit(functools.partial(prefill, cfg))
    jd = jax.jit(functools.partial(decode_step, cfg))
    got = []
    for start in range(0, prompt_len, chunk):
        n = min(chunk, prompt_len - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = ids[start:start + n]
        logits, cache = jp(params, jnp.asarray(toks), cache, bt,
                           jnp.full((1,), start, jnp.int32),
                           jnp.full((1,), n, jnp.int32))
        got.append(logits[0, :n])
    for pos in range(prompt_len, len(ids)):
        logits, cache = jd(params, jnp.asarray(ids[pos:pos + 1]), cache, bt,
                           jnp.full((1,), pos, jnp.int32))
        got.append(logits)
    return jnp.concatenate(got)


@pytest.mark.parametrize("tile_bytes,chunk,row_block,impl", [
    (256 << 20, 64, 256, "reference"),   # one tile holds the window
    (1 << 16, 64, 256, "reference"),     # the key loops run several tiles
    (1 << 12, 48, 256, "reference"),     # one page a tile, a chunk no
                                         # power of 2
    (1 << 16, 64, 16, "reference"),      # a chunk in four blocks of rows;
                                         # the last chunk (12 live rows)
                                         # runs one of them
    # the decode step and the chunks through the paged kernel, the
    # selection its mask operand (what the chip runs at Keye's window);
    # the last in four blocks of rows
    (256 << 20, 64, 256, "interpret"),
    (1 << 12, 48, 256, "interpret"),
    (1 << 16, 64, 16, "interpret"),
])
def test_prefill_then_decode_match_the_reference(monkeypatch, tile_bytes,
                                                 chunk, row_block, impl):
    """150 tokens of context under topk 32: the selection is at work in
    both the masked chunk path and the decode path, gathered
    (``reference``) or read through the paged kernel (``interpret``)."""
    monkeypatch.setattr(SA, "_TILE_BYTES", tile_bytes)
    monkeypatch.setattr(SA, "_ROW_BLOCK", row_block)
    from ray_tpu.ops.attention import dispatch_log

    def calls(op):
        return sum(e["count"] for e in dispatch_log()
                   if (e["op"], e["impl"]) == (op, impl))
    cfg = TransformerConfig(**dict(KEYE, paged_impl=impl))
    params = init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, 128, 150).astype(np.int32)
    before = calls("sparse_decode"), calls("sparse_chunk")
    got = _through_cache(cfg, params, ids, 140, chunk)
    # each program traced once: a layer scan
    assert (calls("sparse_decode"), calls("sparse_chunk")) \
        == (before[0] + 1, before[1] + 1)
    want = keye.forward(params, jnp.asarray(ids)[None], _hp())[0]
    assert float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))) \
        < 1e-5
    # and it is the selection that is compared: attending every key
    # reads far off
    dense = keye.forward(params, jnp.asarray(ids)[None], _hp(topk=1 << 20))[0]
    assert float(jnp.max(jnp.abs(got - dense)) / jnp.max(jnp.abs(dense))) \
        > 1e-2


def test_every_token_on_the_same_experts_loses_none():
    """Routing forced onto experts 3 and 5 for all 96 tokens (a constant
    feature the router reads): a capacity would drop most of them; the
    dropless layer equals the reference for every token."""
    cfg = TransformerConfig(**KEYE)
    lp = {k: v[0] for k, v in init_params(
        cfg, jax.random.PRNGKey(1))["layers"].items()}
    router = np.zeros((64, 8), np.float32)
    router[-1, 3], router[-1, 5] = 40.0, 39.0
    lp["w_router"] = jnp.asarray(router)
    h = np.random.default_rng(2).standard_normal((2, 48, 64)).astype(
        np.float32)
    h[..., -1] = 1.0
    got = topk_moe_mlp(cfg, lp, jnp.asarray(h))
    with jax.default_matmul_precision("highest"):
        want = keye._experts(jnp.asarray(h), lp["w_router"], 0,
                             *(lp[k][None] for k in
                               ("we_gate", "we_up", "we_down")), HP)
    assert float(jnp.min(jnp.max(jnp.abs(want), -1))) > 0    # none is zero
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6)
    # the stacked form (whole [L, E, ...] leaves and a layer index, as
    # the layer scan passes them) is the same layer
    stacked = {**lp, **{k: jnp.stack([jnp.zeros_like(lp[k]), lp[k]])
                        for k in ("we_gate", "we_up", "we_down")}}
    np.testing.assert_array_equal(
        topk_moe_mlp(cfg, stacked, jnp.asarray(h), jnp.int32(1)), got)


@pytest.mark.parametrize("k", [1, 5, 16, 64])
@pytest.mark.parametrize("kind", ["random", "ties", "few"])
def test_topk_mask_is_the_stable_sorts_first_k(k, kind):
    rng = np.random.default_rng(k)
    x = rng.standard_normal((6, 48)).astype(np.float32)
    if kind == "ties":           # few distinct values, zeros of both signs
        x = np.round(x).astype(np.float32)
        x[0, :8] = [0.0, -0.0] * 4
    x[:, 40:] = -np.inf          # not candidates
    if kind == "few":
        x[:, 3:] = -np.inf       # fewer candidates than k
    got = np.asarray(SA.topk_mask(jnp.asarray(x), k))
    order = np.argsort(-x, axis=-1, kind="stable")
    want = np.zeros_like(got)
    np.put_along_axis(want, order[:, :k], True, axis=-1)
    want &= x > -np.inf
    np.testing.assert_array_equal(got, want)


def test_topk_at_least_the_window_is_the_dense_path_bit_for_bit():
    """With ``index_topk >= window`` the selection is the identity and
    the program runs the dense paged attention unchanged: logits equal
    the same llama model's without an indexer, bit for bit."""
    dense = TransformerConfig(**TINY)
    sparse = dataclasses.replace(dense, index_topk=TABLE * BS,
                                 index_heads=4, index_dim=8)
    params = init_params(sparse, jax.random.PRNGKey(3))
    plain = dict(params, layers={
        k: v for k, v in params["layers"].items()
        if k in init_params(dense, jax.random.PRNGKey(3))["layers"]})
    ids = np.random.default_rng(3).integers(0, 128, 90).astype(np.int32)
    a = _through_cache(sparse, params, ids, 80, 32)
    b = _through_cache(dense, plain, ids, 80, 32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a topk the context never reaches, under a wider window, runs the
    # selecting path and selects everything: equal to rounding
    c = _through_cache(dataclasses.replace(sparse, index_topk=128),
                       params, ids, 80, 32)
    np.testing.assert_allclose(c, b, rtol=1e-4, atol=1e-5)


def test_counts_and_the_paths_that_refuse():
    cfg = TransformerConfig(**KEYE)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert cfg.num_params == sum(x.size for x in jax.tree.leaves(params))
    per_layer_idle = (8 - 2) * 3 * 64 * 32
    assert cfg.num_active_params == cfg.num_params - 2 * per_layer_idle
    cache = init_kv_cache(cfg, 4, BS)
    assert cache["ki"].shape == (2, 4, 1, BS, 8)
    from ray_tpu.models.transformer import run_layers
    # (PR 62) experts_per_token trains; Keye's QK-norm and key selection
    # do not, and are what the refusal names
    with pytest.raises(NotImplementedError, match="qk_norm, index_topk"):
        run_layers(cfg, params["layers"], jnp.zeros((1, 8, 64)))
    switch = TransformerConfig(**dict(TINY, n_experts=4))
    with pytest.raises(NotImplementedError, match="dropless"):
        prefill(switch, init_params(switch, jax.random.PRNGKey(0)),
                jnp.zeros((1, 8), jnp.int32), init_kv_cache(switch, 4, BS),
                jnp.zeros((1, TABLE), jnp.int32), jnp.zeros((1,), jnp.int32),
                jnp.full((1,), 8, jnp.int32))


@pytest.mark.parametrize("name,kv_heads,row_bytes,window,pools,masked", [
    # Keye: 4 kv heads x 128 bf16, K and V, a 32,768 window
    ("keye", 4, 256, 32768, 2, True),
    # A.X-K2: one latent row of 640 bf16 a token, one pool, 65,536
    ("a.x-k2", 1, 1280, 65536, 1, False),
    # a per-head model at a 131,072 window: a read four times Keye's
    ("per_head_128k", 4, 256, 131072, 2, False),
    ("per_head_24k", 8, 256, 24576, 2, True),
    ("per_head_48k", 8, 256, 49152, 2, False),
])
def test_the_decode_form_follows_from_shapes(monkeypatch, name, kv_heads,
                                             row_bytes, window, pools,
                                             masked):
    """``masked_read_wins`` by hand: 2048 rows a kv head and pool at the
    gather's cost a row against the window's bytes at the kernel's rate;
    and ``decode_choice`` on a TPU takes the kernel exactly where the
    masked read wins and the page tiles."""
    rows = pools * kv_heads
    gather_s = 2048 * rows * SA._GATHER_S_PER_ROW
    masked_s = window * rows * row_bytes / SA._MASKED_BYTES_PER_S
    assert (masked_s < gather_s) == masked
    assert SA.masked_read_wins(2048, kv_heads, row_bytes, window,
                               pools) == masked
    if pools == 1:
        return                          # a latent cache never asks
    import ray_tpu.ops.attention as A
    pool = jax.ShapeDtypeStruct((6, 64, kv_heads, 16, 128), jnp.bfloat16)
    assert SA.decode_choice("auto", 2048, pool, window // 16) \
        == "reference"                  # off the TPU: the gather
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    assert SA.decode_choice("auto", 2048, pool, window // 16) \
        == ("kernel" if masked else "reference")
    # a page that does not tile rules the kernel out whatever the window
    odd = jax.ShapeDtypeStruct((6, 64, kv_heads, 8, 128), jnp.bfloat16)
    assert SA.decode_choice("auto", 2048, odd, window // 8) \
        == "reference"
