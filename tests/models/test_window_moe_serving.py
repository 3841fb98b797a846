"""A stack by kind of layer (``layer_pattern``: full and window
attention layers with their own head counts and rotary, a head gate, a
leading dense layer, sigmoid-routed experts beside a shared one) against
the plain float32 reference (``benchmarks/reference/laguna.py``) on
seeded weights, at a small size: chunked prefill then paged decode
through the two-kind cache, at a window shorter than the prompt,
against the reference's full forward pass; with one table for both
kinds (the harness's check) and with the window layers' own short
table; each departure missed by its tolerance; the counts and the
refusals."""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import laguna
from ray_tpu.models import (TransformerConfig, decode_step,
                            init_kv_cache, init_params, prefill)
from ray_tpu.models.transformer import apply

YARN = (8.0, 32, 4.0, 1.0, 1.2)
LAGUNA = dict(vocab_size=128, d_model=64, n_layers=5, n_heads=6, head_dim=16,
              n_kv_heads=2, d_ff=96, max_seq_len=256, rotary_dim=8,
              rope_base=5e5, block_style="llama", dtype=jnp.float32,
              remat_policy="none", paged_impl="reference", norm_eps=1e-6,
              layer_pattern=["full", "window", "window", "window"],
              window_heads=8, sliding_window=32, window_rope_base=1e4,
              rope_yarn=list(YARN), head_gate=True, n_dense_layers=1,
              n_experts=16, experts_per_token=4, expert_width=32,
              shared_expert_width=32, router_score="sigmoid",
              routed_scale=2.5)
HP = dict(num_attention_heads=6, window_heads=8, num_key_value_heads=2,
          head_dim=16, rms_norm_eps=1e-6, sliding_window=32,
          rope_theta=5e5, partial_rotary_factor=0.5, yarn_factor=8.0,
          yarn_original=32, yarn_beta_fast=4.0, yarn_beta_slow=1.0,
          yarn_attention_factor=1.2, window_rope_theta=1e4,
          num_experts_per_tok=4, moe_routed_scaling_factor=2.5,
          gating=True, layer_pattern="full window window window")
BS, TABLE = 16, 16


def _hp(**over):
    return tuple(sorted({**HP, **over}.items()))


def _window_table(bt, start, n, window, width):
    """The short table of a call over positions ``start .. start + n -
    1``: the pages from the first key the first query sees, and the
    position the first of them starts at."""
    first = max(0, start - window + 1) // BS
    last = (start + n - 1) // BS
    row = np.zeros((1, width), np.int32)
    row[0, :last - first + 1] = np.asarray(bt)[0, first:last + 1]
    return jnp.asarray(row), jnp.full((1,), first * BS, jnp.int32)


def _through_cache(cfg, params, ids, prompt_len, chunk, short=False):
    """Logits of every position: the prompt in chunks of ``chunk``, then
    one decode step a token, through a paged cache of one sequence.
    ``short``: the window layers read a table of their own that holds no
    page behind the window (the engine's way); else both kinds read the
    one identity table (the harness check's way)."""
    cache = init_kv_cache(cfg, 1 + TABLE, BS)
    bt = jnp.arange(1, 1 + TABLE, dtype=jnp.int32)[None]
    width = -(-(cfg.sliding_window + chunk) // BS) + 1
    jp = jax.jit(functools.partial(prefill, cfg))
    jd = jax.jit(functools.partial(decode_step, cfg))
    got = []
    for start in range(0, prompt_len, chunk):
        n = min(chunk, prompt_len - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = ids[start:start + n]
        extra = _window_table(bt, start, n, cfg.sliding_window, width) \
            if short else ()
        logits, cache = jp(params, jnp.asarray(toks), cache, bt,
                           jnp.full((1,), start, jnp.int32),
                           jnp.full((1,), n, jnp.int32), *extra)
        got.append(logits[0, :n])
    for pos in range(prompt_len, len(ids)):
        extra = _window_table(bt, pos, 1, cfg.sliding_window, width) \
            if short else ()
        logits, cache = jd(params, jnp.asarray(ids[pos:pos + 1]), cache, bt,
                           jnp.full((1,), pos, jnp.int32), *extra)
        got.append(logits)
    return jnp.concatenate(got), cache


@pytest.fixture(scope="module")
def seeded():
    cfg = TransformerConfig(**LAGUNA)
    params = init_params(cfg, jax.random.PRNGKey(0))
    # at 64 wide the init's scores are a few hundredths and every
    # softmax is flat, so no rotary or window could be seen: queries and
    # keys times six, scores of order one as at the published widths
    for name in ("dense_layers", "layers", "window_layers"):
        params[name] = dict(params[name], wq=params[name]["wq"] * 6.0,
                            wk=params[name]["wk"] * 6.0)
    ids = np.random.default_rng(0).integers(0, 128, 150).astype(np.int32)
    want = laguna.forward(params, jnp.asarray(ids)[None], _hp())[0]
    return cfg, params, ids, want


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("impl,chunk,short", [
    ("reference", 64, False), ("reference", 48, True),
    ("interpret", 64, True), ("interpret", 48, False)])
def test_prefill_then_decode_match_the_reference(seeded, impl, chunk,
                                                 short):
    """140 tokens prefilled in chunks (the last one part full), ten
    decoded, every position's logits against the reference's full
    forward: the prompt is over four windows long. The cache has two
    kinds of page: the full layers' pools hold layers 0 and 4, the
    window layers' layers 1 to 3."""
    cfg, params, ids, want = seeded
    cfg = dataclasses.replace(cfg, paged_impl=impl)
    got, cache = _through_cache(cfg, params, ids, 140, chunk, short)
    assert _err(got, want) < 2e-5
    assert sorted(cache) == ["k", "k_window", "v", "v_window"]
    assert cache["k"].shape == (2, 1 + TABLE, 2, BS, 16)
    assert cache["k_window"].shape == (3, 1 + TABLE, 2, BS, 16)
    assert init_kv_cache(cfg, 9, BS, 5)["v_window"].shape[:2] == (3, 5)


def test_the_logits_from_option_of_the_reference(seeded):
    cfg, params, ids, want = seeded
    tail = laguna.forward(params, jnp.asarray(ids)[None],
                          _hp(logits_from=140))[0]
    assert tail.shape == (10, 128)
    np.testing.assert_allclose(tail, want[140:], atol=1e-5)


@pytest.mark.parametrize("departure,least", [
    ("window_off", 0.05), ("one_head_count", 0.05),
    ("plain_rope", 0.01), ("gate_off", 0.05), ("top7", 0.01),
    ("window_plus_one", 1e-3)])
def test_each_departure_is_missed(seeded, departure, least):
    """The reference with one mechanism altered (on the program's
    weights) is far from the program, which agrees with the sound one to
    2e-5."""
    cfg, params, ids, want = seeded
    got, _ = _through_cache(cfg, params, ids, 140, 64, short=True)
    assert _err(got, want) < 2e-5
    hp = {"window_off": dict(sliding_window=10**6),
          "plain_rope": dict(yarn_factor=1.0, yarn_attention_factor=1.0),
          "gate_off": dict(gating=False),
          "top7": dict(num_experts_per_tok=3),
          "window_plus_one": dict(sliding_window=33)}.get(departure, {})
    if departure == "one_head_count":
        # the window layers' two last heads dropped: 6 for all
        window = dict(params["window_layers"])
        window["wq"] = window["wq"].reshape(3, 64, 8, 16)[:, :, :6] \
            .reshape(3, 64, 96)
        window["wo"] = window["wo"].reshape(3, 8, 16, 64)[:, :6] \
            .reshape(3, 96, 64)
        window["wg"] = window["wg"][:, :, :6]
        params = dict(params, window_layers=window)
        hp = dict(window_heads=6)
    wrong = laguna.forward(params, jnp.asarray(ids)[None], _hp(**hp))[0]
    assert _err(got, wrong) > least


def test_counts_and_refusals():
    cfg = TransformerConfig(**LAGUNA)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert cfg.num_params == sum(x.size for x in jax.tree.leaves(params))
    assert set(params) == {"embed", "dense_layers", "layers",
                           "window_layers", "final_norm", "lm_head"}
    assert params["dense_layers"]["wq"].shape == (1, 64, 96)
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 96)
    assert params["layers"]["wq"].shape == (1, 64, 96)
    assert params["layers"]["wg"].shape == (1, 64, 6)
    assert params["window_layers"]["wq"].shape == (3, 64, 128)
    assert params["window_layers"]["we_gate"].shape == (3, 16, 64, 32)
    assert cfg.layer_pattern == ("full", "window", "window", "window")
    hash(cfg)                          # lists became tuples
    from ray_tpu.models import logical_axes
    assert jax.tree.structure(params) == jax.tree.structure(
        logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert cfg.served_only
    # (PR 62) window and full layers, YaRN and softmax-routed dropless
    # experts train now: the refusal names the keys STILL at fault, ahead
    # of its colon, and no key that trains
    with pytest.raises(NotImplementedError, match="n_dense_layers") as e:
        apply(cfg, params, jnp.zeros((1, 8), jnp.int32))
    at_fault = str(e.value).split(":")[0]
    assert "served through" in str(e.value) \
        and "window_heads" in at_fault and "shared_expert_width" in at_fault
    assert not any(k in at_fault for k in ("layer_pattern", "rope_yarn",
                                           "sliding_window"))
    from ray_tpu.parallel.plan import ParallelPlan
    with pytest.raises(NotImplementedError, match="head_gate"):
        ParallelPlan().build(cfg)
    from ray_tpu.models import make_train_step
    with pytest.raises(NotImplementedError, match="router_score"):
        make_train_step(cfg, mesh=None)
    # a configuration with the keys that train and no other is let through
    from ray_tpu.models.transformer import refuse_training, untrained_keys
    trains = TransformerConfig(**{
        k: v for k, v in LAGUNA.items() if k not in (
            "head_gate", "window_heads", "n_dense_layers",
            "shared_expert_width", "router_score", "routed_scale")})
    assert set(trains.served_keys) == {"experts_per_token", "layer_pattern",
                                       "sliding_window", "rope_yarn"}
    assert untrained_keys(trains) == ()
    refuse_training(trains)
    for bad in (dict(sliding_window=0), dict(layer_pattern=["full"]),
                dict(layer_pattern=["full", "local"]),
                dict(n_dense_layers=2), dict(qk_norm=True),
                dict(block_style="gptj"), dict(rope_yarn=[8.0, 32]),
                dict(experts_per_token=0, n_experts=0)):
        with pytest.raises(ValueError):
            init_params(TransformerConfig(**dict(LAGUNA, **bad)),
                        jax.random.PRNGKey(0))
    # a GQA stack with a leading dense layer and one kind of layer: the
    # same path, no window pools
    plain = TransformerConfig(**dict(
        LAGUNA, layer_pattern=[], sliding_window=0, window_heads=0,
        rope_yarn=[], head_gate=False, n_layers=3))
    tree = init_params(plain, jax.random.PRNGKey(0))
    assert set(tree) == {"embed", "dense_layers", "layers", "final_norm",
                         "lm_head"}
    assert list(init_kv_cache(plain, 4, BS)) == ["k", "v"]
    assert plain.num_params == sum(x.size for x in jax.tree.leaves(tree))
    # the published widths: the table of benchmarks/configs/laguna-xs.2
    import json
    import os
    from benchmarks import spec
    with open(os.path.join(spec.HERE, "configs", "laguna-xs.2.json")) as f:
        file = json.load(f)
    kw = dict(file["program"], dtype=jnp.bfloat16)
    assert TransformerConfig(**dict(kw, n_layers=5)).num_params \
        == 3_869_857_792
    assert round(TransformerConfig(**dict(kw, n_layers=40)).num_params
                 / 1e9, 2) == 33.44
