"""A learned key selection over a latent cache, with the forms that come
with it (``index_topk`` with ``kv_lora_rank`` and ``index_q_lora``, YaRN
and its softmax scale, ``head_gate``, ``gated_norm_rank``, ``n_group`` /
``topk_group`` / ``router_bias``), against the plain float32 reference
(``benchmarks/reference/axk2.py``) on seeded weights at a small size:
chunked prefill then paged decode through the two-pool cache against the
reference's full forward pass, past the top-k; every control of the
chip's check told apart here; the shares of the experts adding up to the
uncut layer; the identity selection equal to the dense latent path; and
the new keys refused for training by name."""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import axk2
from ray_tpu.models import (TransformerConfig, decode_step,
                            init_kv_cache, init_params, prefill)
from ray_tpu.models import moe
from ray_tpu.models.transformer import apply, refuse_training

AXK2 = dict(vocab_size=128, d_model=64, n_layers=3, n_heads=4, head_dim=24,
            d_ff=96, max_seq_len=256, rope_base=1e6, block_style="llama",
            dtype=jnp.float32, remat_policy="none", paged_impl="reference",
            norm_eps=1e-6, q_lora_rank=48, kv_lora_rank=128, qk_nope_dim=16,
            qk_rope_dim=8, v_head_dim=16, n_dense_layers=1, n_experts=16,
            experts_per_token=4, expert_width=32, shared_expert_width=32,
            router_score="sigmoid", routed_scale=2.5, experts_held=4,
            expert_first=0, n_group=4, topk_group=2, router_bias=True,
            index_topk=32, index_heads=4, index_dim=16, index_q_lora=True,
            rope_yarn=(2.0, 64, 32.0, 1.0, 1.0),
            rope_softmax_scale=(0.1 * np.log(2.0) + 1.0) ** 2,
            head_gate=True, gated_norm_rank=4)
HP = dict(num_attention_heads=4, rms_norm_eps=1e-6, rope_theta=1e6,
          qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
          kv_lora_rank=128, yarn_factor=2.0, yarn_original=64,
          yarn_beta_fast=32.0, yarn_beta_slow=1.0, mscale=1.0,
          mscale_all_dim=1.0, index_n_heads=4, index_head_dim=16,
          index_topk=32, attention_output_gate=True, gated_norm=True,
          n_group=4, topk_group=2, num_experts_per_tok=4,
          norm_topk_prob=True, routed_scaling_factor=2.5, expert_first=0,
          experts_held=4)
BS, TABLE = 16, 16
#: the controls of the chip's check: the reference altered (on the
#: program's weights), each by ``hp``
CONTROLS = dict(
    attend_every_key={"index_topk": 10 ** 9},
    group_limit_off={"topk_group": 4},
    head_gate_off={"attention_output_gate": False},
    gated_norms_off={"gated_norm": False},
    **{name: {"control": name} for name in axk2.CONTROLS})


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
    cfg = TransformerConfig(**AXK2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, 128, 150).astype(np.int32)
    want = axk2.forward(params, jnp.asarray(ids)[None], _hp())[0]
    got, cache = _through_cache(cfg, params, ids, 140, 64)
    return cfg, params, ids, want, got, cache


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("chunk", [64, 48])
def test_prefill_then_decode_match_the_reference_past_the_topk(seeded,
                                                               chunk):
    """140 tokens prefilled in chunks (the last one part full), ten
    decoded, every position's logits against the reference's full
    forward: from position 32 on a query attends 32 keys of its own
    choosing. The cache is TWO pools under one table: latent rows a lane
    tile wide and the index keys."""
    cfg, params, ids, want, got, cache = seeded
    if chunk != 64:
        got, cache = _through_cache(cfg, params, ids, 140, chunk)
    assert _err(got, want) < 2e-5
    assert sorted(cache) == ["ki", "latent"]
    assert cache["latent"].shape == (3, 1 + TABLE, 1, BS, 256)
    assert cache["ki"].shape == (3, 1 + TABLE, 1, BS, 16)
    # every written position has its index key, no other has
    keys = np.asarray(cache["ki"][:, 1:]).reshape(3, TABLE * BS, 16)
    assert np.abs(keys[:, :150]).max(axis=-1).min() > 0
    assert not keys[:, 150:].any()


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_is_told_apart(seeded, control):
    """The reference with one fault (on the program's weights) is far
    from the sound program, which reads 3e-7: the characteristic fault
    of each new mechanism moves the logits."""
    _, params, ids, _, got, _ = seeded
    wrong = axk2.forward(params, jnp.asarray(ids)[None],
                         _hp(**CONTROLS[control]))[0]
    assert _err(got, wrong) > 5e-3


def test_the_selection_is_past_the_topk_and_exact(seeded):
    """Under the top-k every visible key is attended (the first 32
    positions equal a program with no indexer); past it the program and
    a reference that attends every key part ways."""
    cfg, params, ids, want, got, _ = seeded
    every = axk2.forward(params, jnp.asarray(ids)[None],
                         _hp(index_topk=10 ** 9))[0]
    assert _err(got[:32], every[:32]) < 2e-5
    assert _err(got[32:], every[32:]) > 1e-2


def test_the_identity_selection_is_the_dense_latent_path_bit_for_bit(seeded):
    """A window of no more than ``index_topk`` tokens selects every key:
    the sublayer takes the dense latent path, and its logits are those
    of a model without an indexer on the same weights, bit for bit."""
    cfg, params, ids, _, _, _ = seeded
    wide = dataclasses.replace(cfg, index_topk=BS * TABLE)
    none = dataclasses.replace(cfg, index_topk=0, index_heads=0,
                               index_dim=0, index_q_lora=False)
    indexer = ("wq_idx", "wk_idx", "ww_idx", "k_idx_scale", "k_idx_bias")
    bare = {k: ({n: w for n, w in v.items() if n not in indexer}
                if k.endswith("layers") else v) for k, v in params.items()}
    got, cache = _through_cache(wide, params, ids[:100], 90, 64)
    want, _ = _through_cache(none, bare, ids[:100], 90, 64)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert sorted(cache) == ["ki", "latent"]       # the keys are kept


def test_the_sixteen_shares_add_up():
    """32 experts in 16 shares of 2, 4 groups of which 2 are kept, a
    seeded bias: the sixteen partial results, the shared expert counted
    once, equal the uncut layer, in the program's layer and in the
    reference's."""
    kw = dict(AXK2, n_experts=32, experts_held=0, expert_first=0)
    whole = TransformerConfig(**kw)
    shapes = moe.topk_moe_param_shapes(whole)
    ks = jax.random.split(jax.random.PRNGKey(3), len(shapes) + 2)
    lp = {name: jax.random.normal(k, shape) * 0.2
          for k, (name, shape) in zip(ks, sorted(shapes.items()))}
    lp["router_bias"] = 0.3 * jax.random.normal(ks[-2], (32,))
    h = jax.random.normal(ks[-1], (2, 24, 64))
    uncut = moe.topk_moe_mlp(whole, lp, h)
    shared = moe._shared_expert(whole, lp, h.reshape(48, 64)).reshape(h.shape)
    parts = []
    for first in range(0, 32, 2):
        cfg = dataclasses.replace(whole, experts_held=2, expert_first=first)
        mine = {k: v[first:first + 2] if k in moe.EXPERT_LEAVES else v
                for k, v in lp.items()}
        parts.append(moe.topk_moe_mlp(cfg, mine, h))
        with jax.default_matmul_precision("highest"):
            ref_part = axk2._experts(
                h, mine, 0, *(mine[k][None] for k in moe.EXPERT_LEAVES),
                dict(_hp(expert_first=first, experts_held=2)))
        np.testing.assert_allclose(parts[-1], ref_part, atol=2e-5)
    np.testing.assert_allclose(sum(parts) - 15 * shared, uncut, atol=5e-5)
    with jax.default_matmul_precision("highest"):
        ref_uncut = axk2._experts(
            h, lp, 0, *(lp[k][None] for k in moe.EXPERT_LEAVES),
            dict(_hp(expert_first=0, experts_held=32)))
    np.testing.assert_allclose(uncut, ref_uncut, atol=5e-5)


def test_the_router_keeps_to_its_groups_and_weighs_without_the_bias():
    """Every token's four experts lie in two of the four groups; the
    bias moves who is chosen and not what a chosen expert weighs."""
    cfg = TransformerConfig(**AXK2)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    lp = {"w_router": jax.random.normal(ks[0], (64, 16)),
          "router_bias": 0.5 * jax.random.normal(ks[1], (16,))}
    x = jax.random.normal(ks[2], (200, 64))
    weights, experts = moe.route_topk(cfg, lp, x)
    assert all(len(set(row // 4)) <= 2 for row in np.asarray(experts))
    scores = np.asarray(jax.nn.sigmoid(x @ lp["w_router"]))
    chosen = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    _, unbiased = moe.route_topk(
        cfg, dict(lp, router_bias=jnp.zeros(16)), x)
    assert (np.sort(experts, -1) != np.sort(unbiased, -1)).any()
    # with neither key set the router is the plain top-k it was
    plain = dataclasses.replace(cfg, n_group=0, topk_group=0,
                                router_bias=False)
    w, e = moe.route_topk(plain, lp, x)
    top = np.sort(scores, -1)[:, :-5:-1]
    np.testing.assert_allclose(w, 2.5 * top / top.sum(-1, keepdims=True),
                               rtol=1e-5)


NEW_KEYS = dict(index_q_lora=True, rope_softmax_scale=1.1, gated_norm_rank=4,
                n_group=4, topk_group=2, router_bias=True)


@pytest.mark.parametrize("key", sorted(NEW_KEYS))
def test_training_refuses_each_new_key_by_name(key):
    plain = {k: v for k, v in AXK2.items() if k not in NEW_KEYS}
    cfg = TransformerConfig(**dict(plain, **{key: NEW_KEYS[key]}))
    assert key in cfg.served_keys
    with pytest.raises(NotImplementedError, match=key):
        refuse_training(cfg)


def test_counts_and_refusals():
    cfg = TransformerConfig(**AXK2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert cfg.num_params == sum(x.size for x in jax.tree.leaves(params))
    assert params["layers"]["wq_idx"].shape == (2, 48, 64)   # from cq
    assert params["layers"]["router_bias"].shape == (2, 16)
    assert "router_bias" not in params["dense_layers"]
    assert float(jnp.abs(params["layers"]["router_bias"]).max()) > 0
    assert params["dense_layers"]["attn_gn_down"].shape == (1, 64, 4)
    with pytest.raises(NotImplementedError, match="served through"):
        apply(cfg, params, jnp.zeros((1, 8), jnp.int32))
    hidden = TransformerConfig(**dict(AXK2, index_q_lora=False))
    assert init_params(hidden, jax.random.PRNGKey(0))["layers"][
        "wq_idx"].shape == (2, 64, 64)
    assert hidden.num_params == cfg.num_params + 3 * 16 * 64
    for bad in (dict(qk_norm=True), dict(index_heads=0),
                dict(index_dim=4), dict(n_group=3), dict(topk_group=0),
                dict(topk_group=5), dict(n_group=16, topk_group=1),
                dict(kv_lora_rank=0), dict(index_topk=0),
                dict(rope_yarn=(2.0, 64))):
        with pytest.raises(ValueError):
            init_params(TransformerConfig(**dict(AXK2, **bad)),
                        jax.random.PRNGKey(0))
    # the published widths: the table of benchmarks/configs/a.x-k2.json
    import json
    import os
    from benchmarks import spec
    with open(os.path.join(spec.HERE, "configs", "a.x-k2.json")) as f:
        file = json.load(f)
    kw = dict(file["program"], n_layers=file["num_hidden_layers"],
              dtype=jnp.bfloat16)
    assert TransformerConfig(**kw).num_params == 4_272_121_088
    whole = TransformerConfig(**dict(
        kw, n_layers=61, experts_held=0, vocab_size=163840))
    assert round(whole.num_params / 1e9, 1) == 689.0
