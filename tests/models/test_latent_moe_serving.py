"""The latent-attention served forms (``kv_lora_rank`` with its query
bottleneck, ``sandwich_norm``, ``n_dense_layers``, the sigmoid router,
the shared expert, a held share of the experts) against the plain
float32 reference (``benchmarks/reference/pangu.py``) on seeded weights,
at a small size: chunked prefill then paged decode through the one-pool
latent cache against the reference's full forward pass; each form
missed when left out; and the shares of the experts adding up to the
uncut layer."""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import pangu
from ray_tpu.models import (TransformerConfig, decode_step,
                            init_kv_cache, init_params, prefill)
from ray_tpu.models import moe
from ray_tpu.models.transformer import apply

PANGU = dict(vocab_size=128, d_model=64, n_layers=3, n_heads=4, head_dim=24,
             d_ff=96, max_seq_len=256, rotary_dim=8, rope_base=25.6e6,
             block_style="llama", dtype=jnp.float32, remat_policy="none",
             paged_impl="reference", norm_eps=1e-5,
             q_lora_rank=48, kv_lora_rank=128, qk_nope_dim=16,
             qk_rope_dim=8, v_head_dim=16, sandwich_norm=True,
             n_dense_layers=1, n_experts=16, experts_per_token=4,
             expert_width=32, shared_expert_width=32,
             router_score="sigmoid", routed_scale=2.5, experts_held=4,
             expert_first=8)
HP = dict(num_attention_heads=4, rms_norm_eps=1e-5, rope_theta=25.6e6,
          qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
          kv_lora_rank=128, num_experts_per_tok=4, norm_topk_prob=True,
          routed_scaling_factor=2.5, expert_first=8, experts_held=4)
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
    return jnp.concatenate(got), cache


@pytest.fixture(scope="module")
def seeded():
    cfg = TransformerConfig(**PANGU)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, 128, 150).astype(np.int32)
    want = pangu.forward(params, jnp.asarray(ids)[None], _hp())[0]
    return cfg, params, ids, want


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("impl,chunk", [
    ("reference", 64), ("reference", 48), ("interpret", 64),
    ("interpret", 48)])
def test_prefill_then_decode_match_the_reference(seeded, impl, chunk):
    """140 tokens prefilled in chunks (the last one part full), ten
    decoded, every position's logits against the reference's full
    forward; the cache is ONE pool of latent rows, a lane tile wide."""
    cfg, params, ids, want = seeded
    cfg = dataclasses.replace(cfg, paged_impl=impl)
    got, cache = _through_cache(cfg, params, ids, 140, chunk)
    assert _err(got, want) < 2e-5
    assert list(cache) == ["latent"]
    assert cache["latent"].shape == (3, 1 + TABLE, 1, BS, 256)
    # 128 + 8 numbers a token and layer, the rest of the row zero; the
    # pages of positions never written stay zero
    rows = np.asarray(cache["latent"][:, 1:]).reshape(3, TABLE * BS, 256)
    assert np.abs(rows[:, :150, :136]).min(axis=-1).max() > 0
    assert not rows[:, :, 136:].any() and not rows[:, 150:].any()


@pytest.mark.parametrize("left_out", [
    "sandwich_norm", "dense_layer", "sigmoid", "routed_scale",
    "shared_expert", "k_rope"])
def test_each_form_is_missed_when_left_out(seeded, left_out):
    """The program with one form switched off (on the same weights) is
    far from the reference, where the sound program reads 1e-6."""
    cfg, params, ids, want = seeded
    if left_out == "sandwich_norm":
        cfg = dataclasses.replace(cfg, sandwich_norm=False)
    elif left_out == "dense_layer":
        cfg = dataclasses.replace(cfg, n_dense_layers=0, n_layers=2)
        params = {k: v for k, v in params.items() if k != "dense_layers"}
    elif left_out == "sigmoid":
        # at the init's scale the router's logits are a few hundredths
        # and the renormalised weights of the two scores agree to half a
        # percent of the logits: told apart under a router fifty times
        # as peaky (a trained one), where the sound program still agrees
        layers = dict(params["layers"])
        layers["w_router"] = layers["w_router"] * 50.0
        params = dict(params, layers=layers)
        want = pangu.forward(params, jnp.asarray(ids[:80])[None], _hp())[0]
        sound, _ = _through_cache(cfg, params, ids[:80], 64, 64)
        assert _err(sound, want) < 2e-5
        cfg = dataclasses.replace(cfg, router_score="softmax")
    elif left_out == "routed_scale":
        cfg = dataclasses.replace(cfg, routed_scale=1.0)
    elif left_out == "shared_expert":
        layers = dict(params["layers"])
        layers["ws_down"] = jnp.zeros_like(layers["ws_down"])
        params = dict(params, layers=layers)
    else:                        # the rotated shared key, zeroed
        def cut(w):
            return w.at[..., 128:].set(0.0)
        params = dict(
            params,
            layers=dict(params["layers"],
                        wkv_a=cut(params["layers"]["wkv_a"])),
            dense_layers=dict(params["dense_layers"],
                              wkv_a=cut(params["dense_layers"]["wkv_a"])))
    got, _ = _through_cache(cfg, params, ids[:80], 64, 64)
    assert _err(got, want[:80]) > 1e-2


def test_the_shares_add_up():
    """16 experts in 4 shares of 4: the four partial results, the
    shared expert counted once, equal the uncut layer, in the program's
    layer and in the reference's."""
    whole = TransformerConfig(**dict(PANGU, experts_held=0, expert_first=0))
    shapes = moe.topk_moe_param_shapes(whole)
    ks = jax.random.split(jax.random.PRNGKey(3), len(shapes) + 1)
    lp = {name: jax.random.normal(k, shape) * 0.2
          for k, (name, shape) in zip(ks, sorted(shapes.items()))}
    h = jax.random.normal(ks[-1], (2, 24, 64))
    uncut = moe.topk_moe_mlp(whole, lp, h)
    shared = moe._shared_expert(whole, lp, h.reshape(48, 64)).reshape(h.shape)
    parts, ref_parts = [], []
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(whole, experts_held=4, expert_first=first)
        mine = {k: v[first:first + 4] if k in moe.EXPERT_LEAVES else v
                for k, v in lp.items()}
        parts.append(moe.topk_moe_mlp(cfg, mine, h))
        hp = dict(_hp(expert_first=first))
        with jax.default_matmul_precision("highest"):
            ref_parts.append(pangu._experts(
                h, mine, 0, *(mine[k][None] for k in moe.EXPERT_LEAVES), hp))
        np.testing.assert_allclose(parts[-1], ref_parts[-1], atol=2e-5)
        # the stack-wide form a layer scan uses: layer 1 of two
        stacked = {k: jnp.stack([jnp.zeros_like(mine[k]), mine[k]])
                   for k in moe.EXPERT_LEAVES}
        np.testing.assert_allclose(
            moe.topk_moe_mlp(cfg, {**mine, **stacked}, h, jnp.int32(1)),
            parts[-1], atol=1e-6)
    np.testing.assert_allclose(sum(parts) - 3 * shared, uncut, atol=2e-5)
    # every share saw some assignment and none saw all of them
    assert all(float(jnp.abs(p - shared).max()) > 1e-3 for p in parts)
    with jax.default_matmul_precision("highest"):
        ref_uncut = pangu._experts(
            h, lp, 0, *(lp[k][None] for k in moe.EXPERT_LEAVES),
            dict(_hp(expert_first=0, experts_held=16)))
    np.testing.assert_allclose(uncut, ref_uncut, atol=2e-5)


def test_a_share_that_meets_no_assignment_gives_the_shared_expert():
    """A decode step whose tokens all chose experts held elsewhere: the
    grouped product has no row, every group is empty."""
    cfg = TransformerConfig(**PANGU)
    shapes = moe.topk_moe_param_shapes(cfg)
    ks = jax.random.split(jax.random.PRNGKey(4), len(shapes) + 1)
    lp = {name: jax.random.normal(k, shape) * 0.2
          for k, (name, shape) in zip(ks, sorted(shapes.items()))}
    # the router strongly prefers experts 0..3; this share holds 8..11
    lp["w_router"] = lp["w_router"].at[:, :4].add(50.0 * jnp.sign(
        jnp.ones((64, 1))))
    h = jnp.abs(jax.random.normal(ks[-1], (3, 1, 64)))
    got = moe.topk_moe_mlp(cfg, lp, h)
    want = moe._shared_expert(cfg, lp, h.reshape(3, 64)).reshape(h.shape)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_counts_and_refusals():
    cfg = TransformerConfig(**PANGU)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert cfg.num_params == sum(x.size for x in jax.tree.leaves(params))
    assert set(params) == {"embed", "dense_layers", "layers", "final_norm",
                           "lm_head"}
    assert params["layers"]["we_gate"].shape == (2, 4, 64, 32)
    assert params["layers"]["w_router"].shape == (2, 64, 16)
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 96)
    assert cfg.served_only
    with pytest.raises(NotImplementedError, match="served through"):
        apply(cfg, params, jnp.zeros((1, 8), jnp.int32))
    for bad in (dict(kv_lora_rank=0), dict(head_dim=32),
                dict(n_dense_layers=3), dict(expert_first=14),
                dict(experts_per_token=0, n_experts=0),
                dict(block_style="gptj"), dict(qk_norm=True)):
        with pytest.raises(ValueError):
            init_params(TransformerConfig(**dict(PANGU, **bad)),
                        jax.random.PRNGKey(0))
    # the published widths: the table of benchmarks/configs/openpangu-*
    import json
    import os
    from benchmarks import spec
    with open(os.path.join(spec.HERE, "configs",
                           "openpangu-ultra-moe-718b.json")) as f:
        file = json.load(f)
    kw = dict(file["program"], n_layers=file["num_hidden_layers"],
              dtype=jnp.bfloat16)
    assert TransformerConfig(**kw).num_params == 4_919_139_840
