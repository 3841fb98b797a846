"""A stack whose layers are ONE sublayer each (``layer_pattern`` with
"ffn", ``mixer_only``): Mamba-2 mixers with B and C in groups and a norm a
group, one NoPE grouped-query attention layer, expert layers alone whose
ungated experts live in a latent behind a biased sigmoid router
(``ssm_groups``, ``expert_act``, ``moe_latent``), against the plain
float32 reference (``benchmarks/reference/nemotron_h.py``) on seeded
weights, at a small size: chunked prefill then decode through the slots
against the reference's full forward pass (logits, not tokens); each
term told from its absence; a held share; the tree, the cache and the
plan; the engine's counters; the refusals."""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import nemotron_h
from ray_tpu.models import (TransformerConfig, decode_step,
                            init_kv_cache, init_params, prefill)
from ray_tpu.models.transformer import (_layer_plan, cache_pools,
                                        logical_axes, refuse_training)

LETTERS = "MEMEMEM*EME"           # the published pattern's first eleven
KIND = {"M": "mamba", "E": "ffn", "*": "full"}
NEMOTRON = dict(vocab_size=128, d_model=64, n_layers=11, n_heads=4,
                head_dim=16, n_kv_heads=2, d_ff=96, max_seq_len=128,
                rotary_dim=0, block_style="llama", dtype=jnp.float32,
                remat_policy="none", paged_impl="reference", norm_eps=1e-5,
                layer_pattern=[KIND[c] for c in LETTERS], mixer_only=True,
                ssm_heads=8, ssm_head_dim=16, ssm_state=8, ssm_conv=4,
                ssm_chunk=8, ssm_groups=4, n_experts=16,
                experts_per_token=5, expert_width=24,
                shared_expert_width=40, router_score="sigmoid",
                router_bias=True, routed_scale=5.0, expert_act="relu2",
                moe_latent=32)
HP = dict(num_attention_heads=4, num_key_value_heads=2,
          layer_norm_epsilon=1e-5, mamba_num_heads=8, mamba_head_dim=16,
          ssm_state_size=8, n_groups=4, conv_kernel=4,
          num_experts_per_tok=5, routed_scaling_factor=5.0, expert_first=0,
          experts_held=16, pattern=LETTERS)
BS, TABLE = 16, 8


def _hp(**over):
    return tuple(sorted({**HP, **over}.items()))


@functools.lru_cache(maxsize=None)
def _model(**over):
    cfg = TransformerConfig(**{**NEMOTRON, **dict(over)})
    params = init_params(cfg, jax.random.PRNGKey(3))
    # at the init's scale every attention score is near 0, the routed
    # experts are a thousandth of the stream and the router's bias moves
    # no choice: sharper queries and keys, louder outputs and a larger
    # bias, so that each term can be told from its absence
    louder = {"wq": 24.0, "wk": 24.0, "wo": 8.0, "w_out": 4.0,
              "we_up": 8.0, "we_down": 64.0, "router_bias": 40.0}
    for stack in ("layers", "mamba_layers", "ffn_layers"):
        params[stack] = {k: v * louder.get(k, 1.0)
                         for k, v in params[stack].items()}
    return cfg, params


@functools.lru_cache(maxsize=None)
def _programs(cfg):
    return (jax.jit(functools.partial(prefill, cfg)),
            jax.jit(functools.partial(decode_step, cfg)))


def _through_cache(cfg, params, ids, prompt_len, chunk, slot=None,
                   slots=None):
    """Logits of the prompt's last position and of every decoded one: the
    prompt in chunks of ``chunk``, then one decode step a token, through
    a cache of one sequence whose state lives in ``slot`` of ``slots``."""
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
    cfg, params = _model()
    return _through_cache(cfg, params, IDS[:-1], PROMPT, 24)[0]


def _want(params, **over):
    return nemotron_h.forward(params, jnp.asarray(IDS)[None], _hp(**over))[
        0, PROMPT - 1:-1]


def test_prefill_in_chunks_then_decode_is_the_reference():
    assert _err(_sound(), _want(_model()[1])) < 2e-5


@pytest.mark.parametrize("chunk", [8, 53, 64])
def test_the_chunk_size_changes_nothing(chunk):
    cfg, params = _model()
    got, _ = _through_cache(cfg, params, IDS[:-1], PROMPT, chunk)
    assert _err(got, _want(params)) < 2e-5


def test_the_step_kernel_decodes_what_the_plain_step_decodes():
    """The decode steps' state update as the Pallas kernel, interpreted,
    a tile of lanes reading its group's column, through the layer scan
    on the whole per-slot array, the state in a slot it is told."""
    cfg, params = _model(paged_impl="interpret")
    got, _ = _through_cache(cfg, params, IDS[:-1], PROMPT, 24, slot=2,
                            slots=4)
    assert _err(got, _want(params)) < 2e-5


@pytest.mark.parametrize("control", nemotron_h.CONTROLS)
def test_each_control_is_told_apart(control):
    """The sound program against a reference with one term of the
    description left out or replaced: each reads far over rounding."""
    assert _err(_sound(), _want(_model()[1], control=control)) > 1e-3


@pytest.mark.parametrize("key,without", [
    ("ssm_groups", 1), ("rotary_dim", 8), ("routed_scale", 1.0),
    ("experts_per_token", 4), ("router_score", "softmax")])
def test_a_program_without_one_key_fails(key, without):
    """The other way round: the program with one of this model's keys at
    another value (the tree's shapes are the same), against the sound
    reference."""
    cfg, params = _model()
    off = dataclasses.replace(cfg, **{key: without})
    if key == "ssm_groups":
        # one group's B and C are narrower: the first group's columns
        di, n, g = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_groups
        keep = np.r_[0:2 * di + n, 2 * di + g * n:2 * di + g * n + n,
                     2 * di + 2 * g * n:2 * di + 2 * g * n + cfg.ssm_heads]
        conv = keep[di:-cfg.ssm_heads] - di
        m = params["mamba_layers"]
        params = {**params, "mamba_layers": {
            **m, "w_in": m["w_in"][:, :, keep],
            "conv_w": m["conv_w"][:, conv], "conv_b": m["conv_b"][:, conv]}}
    got, _ = _through_cache(off, params, IDS[:-1], PROMPT, 24)
    assert _err(got, _want(_model()[1])) > 1e-3


@pytest.mark.parametrize("first", [0, 12])
def test_a_held_share_is_the_references_share(first):
    cfg, params = _model(experts_held=4, expert_first=first)
    got, _ = _through_cache(cfg, params, IDS[:-1], PROMPT, 24)
    want = _want(params, experts_held=4, expert_first=first)
    assert _err(got, want) < 2e-5
    assert _err(got, _want(params, experts_held=4,
                           expert_first=12 - first)) > 1e-3


def test_the_cache_the_tree_and_the_plan_of_one_sublayer_a_layer():
    cfg, params = _model()
    cache = init_kv_cache(cfg, 5, BS, state_slots=3)
    # the five expert layers own no pool and no state
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, 5, 2, 16, 16), "v": (1, 5, 2, 16, 16),
        "ssm": (5, 3, 8, 128), "conv": (5, 3, 3, 128 + 2 * 4 * 8)}
    assert set(cache_pools(cache)) == {"k", "v"}
    assert set(params) == {"embed", "final_norm", "lm_head", "layers",
                           "mamba_layers", "ffn_layers"}
    # ONE norm a stack; the mixers' stacks carry no MLP leaf, the expert
    # layers' no mixer leaf and no gate stack
    assert set(params["layers"]) == {"attn_norm", "wq", "wk", "wv", "wo"}
    assert set(params["mamba_layers"]) == {
        "attn_norm", "w_in", "conv_w", "conv_b", "w_out", "A_log",
        "dt_bias", "D", "ssm_norm"}
    assert set(params["ffn_layers"]) == {
        "mlp_norm", "w_router", "router_bias", "w_lat_down", "w_lat_up",
        "we_up", "we_down", "ws_up", "ws_down"}
    assert params["mamba_layers"]["w_in"].shape == (
        5, 64, 128 + 128 + 2 * 4 * 8 + 8)
    assert params["ffn_layers"]["we_up"].shape == (5, 16, 32, 24)
    axes = logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(params)
    assert cfg.num_params == sum(x.size for x in jax.tree.leaves(params))
    assert cfg.expert_layers == 5
    plan = _layer_plan(cfg)
    ffn = next(k for k in plan.kinds if k.name == "ffn")
    assert ffn.mixer is None and ffn.table is None and ffn.mlp \
        and not ffn.pools and not ffn.state and ffn.scope is None
    assert all(not k.mlp for k in plan.kinds if k.name != "ffn")
    assert [(r.stack, r.n, r.experts) for r in plan.runs][:4] == [
        ("mamba_layers", 1, False), ("ffn_layers", 1, True),
        ("mamba_layers", 1, False), ("ffn_layers", 1, True)]
    assert [r.cache_layer for r in plan.runs if r.stack == "mamba_layers"] \
        == [0, 1, 2, 3, 4]


def test_a_layer_with_both_sublayers_is_another_model():
    """Without ``mixer_only`` a "mamba" or "full" layer has a feed-forward
    of its own behind a second norm, as every model before this one."""
    both = dataclasses.replace(_model()[0], mixer_only=False)
    shapes = jax.eval_shape(lambda: init_params(both, jax.random.PRNGKey(0)))
    assert {"attn_norm", "mlp_norm", "we_up"} <= set(shapes["mamba_layers"])
    assert set(shapes["ffn_layers"]) == set(_model()[1]["ffn_layers"])
    assert both.expert_layers == 11 and all(
        k.mlp for k in _layer_plan(both).kinds)


def test_the_engine_counts_the_expert_layers_and_their_assignments():
    from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine
    cfg, _ = _model()
    eng = LLMEngine(cfg, EngineConfig(
        decode_slots=2, kv_block_size=16, max_seq_len=64, prefill_chunk=16,
        max_new_tokens=4, enable_prefix_sharing=False))
    try:
        out = list(eng.generate_sync(list(range(2, 22)), max_new_tokens=3))
        assert len(out) == 3
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["ffn_layers"] == 5
    # 20 prompt tokens and 2 decoded queries (the last token is asked of
    # no layer), 5 experts each in 5 layers
    assert st["moe_prefill_assignments_total"] == 20 * 5 * 5
    assert st["moe_decode_assignments_total"] == 2 * 5 * 5
    assert st["moe_assignments_total"] == 22 * 5 * 5
    assert st["ssm_prefill_tokens_total"] == 20
    assert st["state_bytes_per_slot"] == 5 * (8 * 128 * 4 + 3 * 192 * 4)


def test_the_new_keys_are_refused_by_name():
    cfg, _ = _model()
    with pytest.raises(NotImplementedError, match="mixer_only.*ssm_groups"
                       ".*expert_act.*moe_latent"):
        refuse_training(cfg)
    with pytest.raises(ValueError, match="ssm_groups"):
        _layer_plan(dataclasses.replace(cfg, ssm_groups=3))
    with pytest.raises(ValueError, match="expert_act"):
        _layer_plan(dataclasses.replace(cfg, expert_act="gelu"))
    with pytest.raises(ValueError, match="'ffn'"):
        _layer_plan(dataclasses.replace(cfg, layer_pattern=("mlp",)))
    for key, value in (("moe_latent", 32), ("expert_act", "relu2"),
                       ("mixer_only", True)):
        with pytest.raises(ValueError, match=key):
            _layer_plan(TransformerConfig(
                block_style="llama", n_layers=1, n_experts=4,
                experts_per_token=2, **{key: value}))
    with pytest.raises(ValueError, match="moe_latent"):
        _layer_plan(dataclasses.replace(cfg, experts_per_token=0,
                                        n_experts=0))
