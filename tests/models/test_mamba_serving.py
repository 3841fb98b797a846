"""A stack with "mamba" layers (``layer_pattern``: Mamba-2 mixers with a
per-slot recurrent state beside one paged attention layer with no
rotary, four scalar multipliers, a tied head, a held share of softmax-
routed experts beside a shared one) against the plain float32 reference
(``benchmarks/reference/granite.py``) on seeded weights, at a small
size: chunked prefill then decode through the slots against the
reference's full forward pass (logits, not tokens); each published term
told from its absence; the shares of the experts adding up; the router's
weights; state slots; the refusals."""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import granite
from ray_tpu.models import (TransformerConfig, decode_step,
                            init_kv_cache, init_params, prefill)
from ray_tpu.models.transformer import (_layer_plan, cache_pools,
                                        logical_axes, refuse_training)

PATTERN = ["mamba", "mamba", "full", "mamba"]
GRANITE = dict(vocab_size=128, d_model=64, n_layers=4, n_heads=4,
               head_dim=16, n_kv_heads=2, d_ff=96, max_seq_len=128,
               rotary_dim=0, block_style="llama", dtype=jnp.float32,
               remat_policy="none", paged_impl="reference", norm_eps=1e-5,
               layer_pattern=PATTERN, ssm_heads=8, ssm_head_dim=16,
               ssm_state=8, ssm_conv=4, ssm_chunk=8, attn_scale=1 / 16,
               embed_scale=12.0, residual_scale=0.22, logit_scale=1 / 16,
               tie_embeddings=True, n_experts=12, experts_per_token=4,
               expert_width=32, shared_expert_width=48)
HP = dict(num_attention_heads=4, num_key_value_heads=2, rms_norm_eps=1e-5,
          attention_multiplier=1 / 16, embedding_multiplier=12.0,
          residual_multiplier=0.22, logits_scaling=16.0, mamba_n_heads=8,
          mamba_d_head=16, mamba_d_state=8, mamba_d_conv=4,
          num_experts_per_tok=4, expert_first=0, experts_held=12,
          layer_types="mamba,mamba,attention,mamba")
BS, TABLE = 16, 8


def _hp(**over):
    return tuple(sorted({**HP, **over}.items()))


@functools.lru_cache(maxsize=None)
def _model(**over):
    cfg = TransformerConfig(**{**GRANITE, **dict(over)})
    params = init_params(cfg, jax.random.PRNGKey(3))
    # at the init's scale every score is near 0 and the softmax near
    # uniform whatever its scale or the rotary, and ten routed experts
    # are a thousandth of the stream: sharper queries and keys, a louder
    # attention output and louder routed experts, so that each of their
    # terms can be told from its absence
    louder = {"wq": 24.0, "wk": 24.0, "wo": 4.0, "we_down": 16.0}
    for stack in ("layers", "mamba_layers"):
        if stack in params:
            params[stack] = {k: v * louder.get(k, 1.0)
                             for k, v in params[stack].items()}
    return cfg, params


@functools.lru_cache(maxsize=None)
def _programs(cfg):
    return (jax.jit(functools.partial(prefill, cfg)),
            jax.jit(functools.partial(decode_step, cfg)))


def _through_cache(cfg, params, ids, prompt_len, chunk, slot=None,
                   slots=None, cache=None):
    """Logits of the prompt's last position and of every decoded one: the
    prompt in chunks of ``chunk``, then one decode step a token, through
    a cache of one sequence whose state lives in ``slot`` of ``slots``
    (None: the default, one slot, row 0)."""
    if cache is None:
        cache = init_kv_cache(cfg, 1 + TABLE, BS, state_slots=slots)
    bt = jnp.arange(1, 1 + TABLE, dtype=jnp.int32)[None]
    rows = {} if slot is None else \
        {"state_rows": jnp.full((1,), slot, jnp.int32)}
    jp, jd = _programs(cfg)
    got = []
    for start in range(0, prompt_len, chunk):
        n = min(chunk, prompt_len - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = ids[start:start + n]
        logits, cache = jp(params, jnp.asarray(toks), cache, bt,
                           jnp.full((1,), start, jnp.int32),
                           jnp.full((1,), n, jnp.int32), **rows)
    got.append(logits[0, n - 1])
    for pos in range(prompt_len, len(ids)):
        logits, cache = jd(params, jnp.asarray(ids[pos:pos + 1]), cache, bt,
                           jnp.full((1,), pos, jnp.int32), **rows)
        got.append(logits[0])
    return jnp.stack(got), cache


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


IDS = np.random.default_rng(0).integers(0, 128, size=(61,)).astype(np.int32)
PROMPT = 53           # chunks of 24: two whole, a ragged one; 3+ scan blocks


@functools.lru_cache(maxsize=None)
def _sound():
    """The uncut model's answer through the cache, chunks of 24."""
    cfg, params = _model()
    return _through_cache(cfg, params, IDS[:-1], PROMPT, 24)[0]


def _want(params, **over):
    return granite.forward(params, jnp.asarray(IDS)[None], _hp(**over))[
        0, PROMPT - 1:-1]


def test_prefill_in_chunks_then_decode_is_the_reference():
    assert _err(_sound(), _want(_model()[1])) < 2e-5


@pytest.mark.parametrize("chunk", [8, 16, 53, 64])
def test_the_chunk_size_changes_nothing(chunk):
    cfg, params = _model()
    got, _ = _through_cache(cfg, params, IDS[:-1], PROMPT, chunk)
    assert _err(got, _want(params)) < 2e-5


@pytest.mark.parametrize("slot", [None, 2])
def test_the_step_kernel_decodes_what_the_plain_step_decodes(slot):
    """The decode steps' state update as the Pallas kernel, interpreted
    (``paged_impl``; the paged layer's kernel with it), on the whole
    per-slot array through the layer scan: the reference's logits, row b
    in slot b and in the slot it is told."""
    cfg, params = _model(paged_impl="interpret")
    got, _ = _through_cache(cfg, params, IDS[:-1], PROMPT, 24, slot=slot,
                            slots=None if slot is None else 4)
    assert _err(got, _want(params)) < 2e-5
    np.testing.assert_allclose(got, _sound(), rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("control", granite.CONTROLS)
def test_each_control_is_told_apart(control):
    """The sound program against a reference with one published term
    left out: each departure reads far over what rounding does."""
    assert _err(_sound(), _want(_model()[1], control=control)) > 1e-3


@pytest.mark.parametrize("key,without", [
    ("embed_scale", 1.0), ("residual_scale", 1.0), ("logit_scale", 1.0),
    ("attn_scale", 0.0), ("rotary_dim", 8)])
def test_a_program_without_one_key_fails(key, without):
    """The other way round: the program with one of its new keys at what
    it was before this model, against the sound reference."""
    cfg, params = _model()
    off = dataclasses.replace(cfg, **{key: without})
    got, _ = _through_cache(off, params, IDS[:-1], PROMPT, 24)
    assert _err(got, _want(params)) > 1e-3


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Experts 0..5 and 6..11, each with what every chip computes alike
    (the shared expert) counted once, add up to the whole layer."""
    from ray_tpu.models.moe import topk_moe_mlp
    cfg, params = _model(n_layers=1, layer_pattern=("mamba",))
    lp = {k: v[0] for k, v in params["mamba_layers"].items()}
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 64))
    whole = topk_moe_mlp(cfg, lp, h)
    shared = topk_moe_mlp(
        dataclasses.replace(cfg, experts_held=1, expert_first=0),
        {**lp, **{k: jnp.zeros_like(lp[k][:1])
                  for k in ("we_gate", "we_up", "we_down")}}, h)
    total = -shared                     # counted in both shares
    for first in (0, 6):
        part = dataclasses.replace(cfg, experts_held=6, expert_first=first)
        held = {k: lp[k][first:first + 6]
                for k in ("we_gate", "we_up", "we_down")}
        total = total + topk_moe_mlp(part, {**lp, **held}, h)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("first", [0, 6])
def test_a_held_share_is_the_references_share(first):
    """Through the whole model: the program holding half of the experts
    against the reference given the same half."""
    cfg, params = _model(experts_held=6, expert_first=first)
    got, _ = _through_cache(cfg, params, IDS[:-1], PROMPT, 24)
    want = _want(params, experts_held=6, expert_first=first)
    assert _err(got, want) < 2e-5
    # and not the other half's
    assert _err(got, _want(params, experts_held=6,
                           expert_first=6 - first)) > 1e-3


def test_the_router_is_a_softmax_over_the_largest_logits():
    from ray_tpu.models.moe import route_topk
    cfg, params = _model()
    lp = {k: v[0] for k, v in params["mamba_layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 64))
    weights, experts = route_topk(cfg, lp, x)
    logits = np.asarray(x @ lp["w_router"], np.float64)
    for t in range(40):
        top = np.argsort(-logits[t], kind="stable")[:4]
        assert list(np.asarray(experts[t])) == list(top)
        e = np.exp(logits[t, top] - logits[t, top].max())
        np.testing.assert_allclose(weights[t], e / e.sum(), rtol=1e-5)
    ref = np.asarray(granite._route(x[None], lp, dict(_hp()))[0])
    np.testing.assert_allclose(
        np.take_along_axis(ref, np.asarray(experts), -1), weights,
        rtol=1e-5)


def test_the_state_lives_in_the_slot_it_is_told():
    """A sequence in slot 2 of 4 answers as it does in a cache of its
    own, the other slots' rows are never read into it and never written,
    and a second sequence started in the same slot finds nothing of the
    first."""
    cfg, params = _model()
    alone = _sound()
    cache = init_kv_cache(cfg, 1 + TABLE, BS, state_slots=4)
    noise = {k: jax.random.normal(jax.random.PRNGKey(7), v.shape, v.dtype)
             for k, v in cache.items() if k in ("ssm", "conv")}
    got, after = _through_cache(cfg, params, IDS[:-1], PROMPT, 24, slot=2,
                                cache={**cache, **noise})
    # (two programs: the slots given, and row b in slot b)
    np.testing.assert_allclose(got, alone, rtol=1e-4, atol=1e-7)
    for name, arr in noise.items():
        others = [0, 1, 3]
        np.testing.assert_array_equal(after[name][:, others],
                                      arr[:, others])
        assert not np.array_equal(after[name][:, 2], arr[:, 2])
    again, _ = _through_cache(cfg, params, IDS[:-1], PROMPT, 24, slot=2,
                              cache=after)
    np.testing.assert_array_equal(again, got)


def test_a_decode_row_with_no_sequence_leaves_its_slot_alone():
    cfg, params = _model()
    cache = init_kv_cache(cfg, 1 + TABLE, BS, state_slots=3)
    cache = {k: jax.random.normal(jax.random.PRNGKey(9), v.shape, v.dtype)
             if k in ("ssm", "conv") else v for k, v in cache.items()}
    bt = jnp.zeros((3, TABLE), jnp.int32).at[1].set(jnp.arange(1, 1 + TABLE))
    lens = jnp.asarray([-1, 5, -1], jnp.int32)
    _, after = jax.jit(functools.partial(decode_step, cfg))(
        params, jnp.asarray([0, 7, 0], jnp.int32), cache, bt, lens)
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(after[name][:, [0, 2]],
                                      cache[name][:, [0, 2]])
        assert not np.array_equal(after[name][:, 1], cache[name][:, 1])


def test_the_cache_and_the_tree_are_what_the_plan_says():
    cfg, params = _model()
    cache = init_kv_cache(cfg, 5, BS, state_slots=3)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, 5, 2, 16, 16), "v": (1, 5, 2, 16, 16),
        "ssm": (3, 3, 8, 128), "conv": (3, 3, 3, 144)}
    assert cache["ssm"].dtype == jnp.float32
    assert set(cache_pools(cache)) == {"k", "v"}
    assert init_kv_cache(cfg, 5, BS)["ssm"].shape[1] == 1     # the default
    assert "lm_head" not in params and set(params) == {
        "embed", "final_norm", "layers", "mamba_layers"}
    assert params["mamba_layers"]["w_in"].shape == (3, 64, 128 + 144 + 8)
    axes = logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(params)
    assert cfg.num_params == sum(x.size for x in jax.tree.leaves(params))
    plan = _layer_plan(cfg)
    assert [(r.stack, r.kind.name, r.at, r.n, r.cache_layer)
            for r in plan.runs] == [("mamba_layers", "mamba", 0, 2, 0),
                                    ("layers", "full", 0, 1, 0),
                                    ("mamba_layers", "mamba", 2, 1, 2)]


def test_the_new_keys_are_refused_by_name():
    cfg, _ = _model()
    with pytest.raises(NotImplementedError, match="ssm_heads"):
        refuse_training(cfg)
    with pytest.raises(ValueError, match="ssm_heads"):
        _layer_plan(dataclasses.replace(cfg, ssm_heads=0))
    with pytest.raises(ValueError, match="'mamba' layers"):
        _layer_plan(dataclasses.replace(cfg, layer_pattern=("full",)))
    with pytest.raises(ValueError, match="tie_embeddings"):
        _layer_plan(TransformerConfig(
            block_style="llama", tie_embeddings=True, n_layers=1))
