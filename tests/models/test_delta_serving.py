"""A stack with "delta" layers (``layer_pattern``: gated-delta-rule
mixers with a per-slot recurrent state, three in four, beside a paged
attention layer with no rotary and a QK-norm over the whole projected
width, in a block that norms each sublayer's OUTPUT and nothing ahead of
it) against the plain float32 reference
(``benchmarks/reference/olmo_hybrid.py``) on seeded weights, at a small
size: chunked prefill then decode through the slots against the
reference's full forward pass (logits, not tokens); each term told from
its absence; snapshot rows written at a call's boundaries and a sequence
resumed from one; state slots; the refusals."""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import olmo_hybrid
from ray_tpu.models import (TransformerConfig, decode_step,
                            init_kv_cache, init_params, prefill)
from ray_tpu.models.transformer import (_layer_plan, cache_pools,
                                        logical_axes, refuse_training)

PATTERN = ["delta", "delta", "delta", "full"]
OLMO = dict(vocab_size=128, d_model=64, n_layers=4, n_heads=4,
            head_dim=16, n_kv_heads=4, d_ff=96, max_seq_len=128,
            rotary_dim=0, block_style="llama", dtype=jnp.float32,
            remat_policy="none", paged_impl="reference", norm_eps=1e-6,
            layer_pattern=PATTERN, delta_heads=4, delta_key_dim=8,
            delta_value_dim=16, delta_conv=4,
            delta_neg_eigval=True, output_norm=True, qk_norm_whole=True)
HP = dict(num_attention_heads=4, num_key_value_heads=4, rms_norm_eps=1e-6,
          linear_num_key_heads=4, linear_num_value_heads=4,
          linear_key_head_dim=8, linear_value_head_dim=16,
          linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
          layer_types="linear_attention,linear_attention,"
                      "linear_attention,full_attention")
BS, TABLE = 16, 8
STATE = ("delta", "delta_conv")


def _hp(**over):
    return tuple(sorted({**HP, **over}.items()))


@functools.lru_cache(maxsize=None)
def _model(**over):
    cfg = TransformerConfig(**{**OLMO, **dict(over)})
    params = init_params(cfg, jax.random.PRNGKey(3))
    # at the init's scale every score is near 0 and the softmax near
    # uniform whatever its rotary: sharper queries and keys. The norm
    # weights are drawn (at one a head-wise and a whole-width QK-norm
    # differ by the statistic alone, and an output norm left out by a
    # scale): every leaf at one moves to 1 + 0.3 n(0, 1)
    louder = {"wq": 24.0, "wk": 24.0}
    key = jax.random.PRNGKey(11)
    for stack in ("layers", "delta_layers"):
        new = {}
        for i, (name, leaf) in enumerate(sorted(params[stack].items())):
            if name.endswith("_norm"):
                leaf = leaf + 0.3 * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape)
            new[name] = leaf * louder.get(name, 1.0)
        params[stack] = new
    return cfg, params


@functools.lru_cache(maxsize=None)
def _programs(cfg):
    return (jax.jit(functools.partial(prefill, cfg)),
            jax.jit(functools.partial(decode_step, cfg)))


def _through_cache(cfg, params, ids, prompt_len, chunk, slot=None,
                   slots=None, cache=None, start=0, snap_rows=None):
    """Logits of the prompt's last position and of every decoded one: the
    prompt from ``start`` on in chunks of ``chunk``, then one decode step
    a token, through a cache of one sequence whose state lives in
    ``slot`` of ``slots`` (None: the default, one slot, row 0).
    ``snap_rows``: call number -> the chunk call's ``snap_rows``."""
    if cache is None:
        cache = init_kv_cache(cfg, 1 + TABLE, BS, state_slots=slots)
    bt = jnp.arange(1, 1 + TABLE, dtype=jnp.int32)[None]
    rows = {} if slot is None else \
        {"state_rows": jnp.full((1,), slot, jnp.int32)}
    jp, jd = _programs(cfg)
    got = []
    for call, at in enumerate(range(start, prompt_len, chunk)):
        n = min(chunk, prompt_len - at)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = ids[at:at + n]
        snap = {} if snap_rows is None else \
            {"snap_rows": jnp.asarray(snap_rows[call], jnp.int32)[None]}
        logits, cache = jp(params, jnp.asarray(toks), cache, bt,
                           jnp.full((1,), at, jnp.int32),
                           jnp.full((1,), n, jnp.int32), **rows, **snap)
    got.append(logits[0, n - 1])
    for pos in range(prompt_len, len(ids)):
        logits, cache = jd(params, jnp.asarray(ids[pos:pos + 1]), cache, bt,
                           jnp.full((1,), pos, jnp.int32), **rows)
        got.append(logits[0])
    return jnp.stack(got), cache


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


IDS = np.random.default_rng(0).integers(0, 128, size=(61,)).astype(np.int32)
PROMPT = 53           # chunks of 24: two whole, a ragged one


@functools.lru_cache(maxsize=None)
def _sound():
    """The model's answer through the cache, chunks of 24."""
    cfg, params = _model()
    return _through_cache(cfg, params, IDS[:-1], PROMPT, 24)[0]


def _want(params, **over):
    return olmo_hybrid.forward(params, jnp.asarray(IDS)[None],
                               _hp(**over))[0, PROMPT - 1:-1]


def test_prefill_in_chunks_then_decode_is_the_reference():
    assert _err(_sound(), _want(_model()[1])) < 2e-5


@pytest.mark.parametrize("chunk", [8, 16, 53, 64])
def test_the_chunk_size_changes_nothing(chunk):
    cfg, params = _model()
    got, _ = _through_cache(cfg, params, IDS[:-1], PROMPT, chunk)
    assert _err(got, _want(params)) < 2e-5


def test_a_call_of_two_published_blocks_is_the_reference():
    """117 tokens in ONE call of 128 rows: two blocks of 64
    (``ops/delta.py`` ``BLOCK``), the second ragged; then seven one-token
    updates."""
    cfg, params = _model()
    ids = np.random.default_rng(1).integers(0, 128, size=(125,)) \
        .astype(np.int32)
    got, _ = _through_cache(cfg, params, ids[:-1], 117, 128)
    want = olmo_hybrid.forward(params, jnp.asarray(ids)[None],
                               _hp())[0, 116:-1]
    assert _err(got, want) < 2e-5


def test_logits_from_a_position_on_are_the_tail_of_all():
    _, params = _model()
    tail = olmo_hybrid.forward(params, jnp.asarray(IDS)[None],
                               _hp(logits_from=PROMPT - 1))[0, :-1]
    np.testing.assert_allclose(tail, _want(params), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("control", olmo_hybrid.CONTROLS)
def test_each_control_is_told_apart(control):
    """The sound program against a reference with one term of the
    description left out or replaced: each departure reads far over what
    rounding does."""
    assert _err(_sound(), _want(_model()[1], control=control)) > 1e-3


@pytest.mark.parametrize("key,without", [
    ("delta_neg_eigval", False), ("rotary_dim", 8)])
def test_a_program_without_one_key_fails(key, without):
    """The other way round: the program with one of its keys at what it
    was before this model, against the sound reference."""
    cfg, params = _model()
    off = dataclasses.replace(cfg, **{key: without})
    got, _ = _through_cache(off, params, IDS[:-1], PROMPT, 24)
    assert _err(got, _want(params)) > 1e-3


def test_snapshots_resume_a_sequence_where_they_were_taken():
    """A prompt prefilled in chunks of 32 with a snapshot every 16 tokens
    (rows 1..3; the boundary at 64 lies past the 53 live tokens and goes
    to the trash row). A second sequence in another slot whose slot rows
    are copied from the snapshot at 32 and that prefills from position 32
    on (its pages are the first's) answers as the first did; from the
    snapshot at 16 without recomputing 16..31 it does not."""
    cfg, params = _model()
    cache = init_kv_cache(cfg, 1 + TABLE, BS, state_slots=2,
                          state_snapshots=3)
    assert cache["delta_snap"].shape == (3, 4, 8, 64)
    assert cache["delta_conv_snap"].shape == (3, 4, 3, 128)
    got, cache = _through_cache(
        cfg, params, IDS[:-1], PROMPT, 32, slot=0, cache=cache,
        snap_rows={0: [1, 2], 1: [3, 0]})
    np.testing.assert_allclose(got, _sound(), rtol=1e-4, atol=1e-6)

    def resumed(row, start):
        c = dict(cache)
        for name in STATE:
            c[name] = c[name].at[:, 1].set(c[name + "_snap"][:, row])
        return _through_cache(cfg, params, IDS[:-1], PROMPT, 32, slot=1,
                              cache=c, start=start)[0]
    np.testing.assert_allclose(resumed(2, 32), got, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(resumed(3, 48), got, rtol=1e-4, atol=1e-6)
    # a wrong state under a right cache
    assert _err(resumed(1, 32), got) > 1e-3


def test_a_snapshot_is_the_state_a_shorter_prompt_ends_on():
    cfg, params = _model()
    cache = init_kv_cache(cfg, 1 + TABLE, BS, state_snapshots=2)
    _, cache = _through_cache(cfg, params, IDS[:48], 48, 32, cache=cache,
                              snap_rows={0: [0, 1], 1: [2, 0]})
    for row, end in ((1, 32), (2, 48)):
        _, short = _through_cache(cfg, params, IDS[:end], end, 32)
        for name in STATE:
            np.testing.assert_allclose(
                cache[name + "_snap"][:, row], short[name][:, 0],
                rtol=2e-5, atol=2e-6)


def test_snap_rows_want_a_cache_with_snapshots():
    cfg, params = _model()
    with pytest.raises(ValueError, match="snap_rows"):
        _through_cache(cfg, params, IDS[:-1], PROMPT, 32,
                       snap_rows={0: [1, 2], 1: [3, 0]})


def test_the_state_lives_in_the_slot_it_is_told():
    """A sequence in slot 2 of 4 answers as it does in a cache of its
    own, the other slots' rows are never read into it and never written,
    and a second sequence started in the same slot finds nothing of the
    first."""
    cfg, params = _model()
    alone = _sound()
    cache = init_kv_cache(cfg, 1 + TABLE, BS, state_slots=4)
    noise = {k: jax.random.normal(jax.random.PRNGKey(7), v.shape, v.dtype)
             for k, v in cache.items() if k in STATE}
    got, after = _through_cache(cfg, params, IDS[:-1], PROMPT, 24, slot=2,
                                cache={**cache, **noise})
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
             if k in STATE else v for k, v in cache.items()}
    bt = jnp.zeros((3, TABLE), jnp.int32).at[1].set(jnp.arange(1, 1 + TABLE))
    lens = jnp.asarray([-1, 5, -1], jnp.int32)
    _, after = jax.jit(functools.partial(decode_step, cfg))(
        params, jnp.asarray([0, 7, 0], jnp.int32), cache, bt, lens)
    for name in STATE:
        np.testing.assert_array_equal(after[name][:, [0, 2]],
                                      cache[name][:, [0, 2]])
        assert not np.array_equal(after[name][:, 1], cache[name][:, 1])


def test_the_cache_and_the_tree_are_what_the_plan_says():
    cfg, params = _model()
    cache = init_kv_cache(cfg, 5, BS, state_slots=3)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, 5, 4, 16, 16), "v": (1, 5, 4, 16, 16),
        "delta": (3, 3, 8, 64), "delta_conv": (3, 3, 3, 128)}
    assert cache["delta"].dtype == jnp.float32
    with_snaps = init_kv_cache(cfg, 5, BS, state_slots=3, state_snapshots=6)
    assert with_snaps["delta_snap"].shape == (3, 7, 8, 64)
    assert set(cache_pools(with_snaps)) == {"k", "v"}
    assert init_kv_cache(cfg, 5, BS)["delta"].shape[1] == 1   # the default
    assert set(params) == {"embed", "final_norm", "lm_head", "layers",
                           "delta_layers"}
    assert set(params["delta_layers"]) == {
        "w_qkv", "w_g", "w_ab", "conv_w", "w_out", "A_log", "dt_bias",
        "delta_norm", "w_gate", "w_up", "w_down", "post_attn_norm",
        "post_mlp_norm"}
    assert params["delta_layers"]["w_qkv"].shape == (3, 64, 128)
    assert params["layers"]["q_norm"].shape == (1, 64)
    assert "attn_norm" not in params["layers"]
    axes = logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(params)
    assert cfg.num_params == sum(x.size for x in jax.tree.leaves(params))
    plan = _layer_plan(cfg)
    assert [(r.stack, r.kind.name, r.at, r.n, r.cache_layer)
            for r in plan.runs] == [("delta_layers", "delta", 0, 3, 0),
                                    ("layers", "full", 0, 1, 0)]
    assert {k.name: (k.norm, k.post_norm, k.qk_norm, k.qk_norm_whole,
                     k.mixer) for k in plan.kinds} == {
        "full": ("none", True, False, True, "paged"),
        "delta": ("none", True, False, False, "delta")}


def test_the_new_keys_are_refused_by_name():
    cfg, _ = _model()
    with pytest.raises(NotImplementedError, match="delta_heads.*output_norm"
                       ".*qk_norm_whole"):
        refuse_training(cfg)
    with pytest.raises(ValueError, match="delta_heads"):
        _layer_plan(dataclasses.replace(cfg, delta_heads=0))
    with pytest.raises(ValueError, match="'delta' layers"):
        _layer_plan(dataclasses.replace(cfg, layer_pattern=("full",)))
    with pytest.raises(ValueError, match="not with 'mamba' layers"):
        _layer_plan(dataclasses.replace(
            cfg, layer_pattern=("delta", "mamba"), ssm_heads=2,
            ssm_head_dim=8, ssm_state=8))
    with pytest.raises(ValueError, match="output_norm"):
        _layer_plan(TransformerConfig(
            block_style="llama", output_norm=True, n_layers=1))
