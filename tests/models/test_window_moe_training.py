"""A stack of window and full attention layers (plain RoPE, YaRN) over
dropless softmax-routed experts, of which the program holds a share,
TRAINED (Mellum2-12B-A2.5B's forms, ``benchmarks/configs/
mellum2-12b-a2.5b.json``): ``lm_loss`` and every gradient leaf of the
program against ``benchmarks/reference/mellum.py``, float32, small sizes,
at a sequence three windows long; what the comparison can tell (a window
off by one, YaRN against plain RoPE); the step program; the plans; and
the held share tied to the uncut layer, forward and backward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mellum
from ray_tpu.models import TransformerConfig, init_params
from ray_tpu.models.moe import topk_moe_mlp
from ray_tpu.models.transformer import lm_loss, untrained_keys

YARN = (8.0, 32, 4.0, 1.0, 1.2)
WINDOW, SEQ = 32, 96                      # three windows
TINY = dict(vocab_size=256, d_model=64, n_layers=4, n_heads=4, head_dim=16,
            n_kv_heads=2, d_ff=96, max_seq_len=SEQ, rotary_dim=16,
            rope_base=5e5, block_style="llama", dtype=jnp.float32,
            norm_eps=1e-6, attn_impl="reference",
            layer_pattern=["window", "window", "window", "full"],
            sliding_window=WINDOW, window_rotary_dim=16,
            window_rope_base=5e5, rope_yarn=list(YARN), n_experts=8,
            experts_held=4, expert_first=2, experts_per_token=2,
            expert_width=32, router_score="softmax", routed_scale=1.0)
HP = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
          rms_norm_eps=1e-6, sliding_window=WINDOW, rope_theta=5e5,
          window_rope_theta=5e5, yarn_factor=8.0, yarn_original=32,
          yarn_beta_fast=4.0, yarn_beta_slow=1.0,
          yarn_attention_factor=1.2, num_experts=8, num_experts_per_tok=2,
          experts_held=4, expert_first=2,
          layer_pattern="window window window full")
#: float32 against float32 through four blocks, two different orders of
#: summation (the program sorts rows by expert, the reference loops over
#: experts): 3e-7 to 7e-7 a leaf, measured; 1e-4 is the issue's figure
LEAF_TOL = 1e-4


def _hp(**over):
    return tuple(sorted({**HP, **over}.items()))


def _setup(seed=0, **over):
    cfg = TransformerConfig(**{**TINY, **over})
    params = init_params(cfg, jax.random.PRNGKey(seed))
    # at the init's scale the scores are flat: no window, no rotary and
    # no routing would show. Scaled up, each does
    for stack in ("layers", "window_layers"):
        for leaf, scale in (("wq", 8.0), ("wk", 8.0), ("w_router", 20.0),
                            ("we_down", 6.0), ("wo", 6.0)):
            params[stack][leaf] = params[stack][leaf] * scale
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    return cfg, params, ids


def _system(cfg, params, ids):
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(cfg, p, {"input_ids": ids}), has_aux=True))(params)
    return loss, aux, grads


def _reference(params, ids, hp):
    return jax.value_and_grad(
        lambda p: mellum.loss(p, jnp.asarray(ids), hp))(params)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def test_loss_and_every_gradient_leaf_against_the_reference():
    cfg, params, ids = _setup()
    assert untrained_keys(cfg) == ()
    loss, aux, grads = _system(cfg, params, ids)
    want, want_grads = _reference(params, ids, _hp())
    assert abs(float(loss) - float(want)) / float(want) < 1e-6
    errs = jax.tree.map(_rel, grads, want_grads)
    flat = jax.tree_util.tree_flatten_with_path(errs)[0]
    assert len(flat) == 23               # 10 a stack, embed, norm, head
    for path, err in flat:
        assert err < LEAF_TOL, (jax.tree_util.keystr(path), err)
    # every leaf has a gradient that is not nothing
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert float(jnp.max(jnp.abs(g))) > 0, jax.tree_util.keystr(path)
    assert set(aux["moe"]) == {"held_assignments", "load_max_over_mean",
                               "balance"}


@pytest.mark.parametrize("hp,what", [
    (dict(sliding_window=WINDOW - 1), "a window one key short"),
    (dict(sliding_window=WINDOW + 1), "a window one key long"),
    (dict(sliding_window=SEQ), "no window"),
    (dict(yarn_factor=0.0), "plain RoPE on the full layer"),
    (dict(yarn_attention_factor=1.0), "YaRN without its factor"),
    (dict(window_rope_theta=1e4), "the window layers at theta 1e4"),
    (dict(num_experts_per_tok=1), "top-1"),
    (dict(expert_first=3), "another held share"),
])
def test_the_comparison_tells(hp, what):
    """A reference that differs in ONE of the forms reads far over the
    tolerance the sound comparison is held to: each is visible."""
    cfg, params, ids = _setup()
    loss, _, grads = _system(cfg, params, ids)
    want, want_grads = _reference(params, ids, _hp(**hp))
    worst = max(jax.tree.leaves(jax.tree.map(_rel, grads, want_grads)))
    assert worst > 100 * LEAF_TOL, (what, worst)


def test_the_interpreted_kernels_agree_with_the_reference():
    """The windowed and the plain flash kernels (interpret mode, 128-wide
    heads, blocks of 64: a window of 96 spans blocks) under the same
    model: loss and gradients as the XLA attention's."""
    over = dict(head_dim=128, rotary_dim=128, window_rotary_dim=128,
                n_heads=2, n_kv_heads=1, max_seq_len=256,
                sliding_window=96, attn_block_q=64, attn_block_k=64,
                n_layers=2, layer_pattern=["window", "full"])
    cfg = TransformerConfig(**{**TINY, **over, "attn_impl": "interpret"})
    ref = dataclasses.replace(cfg, attn_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(1))
    for stack in ("layers", "window_layers"):
        params[stack]["wq"] = params[stack]["wq"] * 8.0
        params[stack]["wk"] = params[stack]["wk"] * 8.0
    ids = np.random.default_rng(1).integers(0, 256, (1, 256)).astype(np.int32)
    a = _system(cfg, params, ids)
    b = _system(ref, params, ids)
    assert abs(float(a[0]) - float(b[0])) < 1e-5
    assert max(jax.tree.leaves(jax.tree.map(_rel, a[2], b[2]))) < 1e-3


def _plan_run(plan_kw, steps=6):
    from ray_tpu.parallel.plan import ParallelPlan
    cfg, _, ids = _setup()
    plan = ParallelPlan(**plan_kw)
    prog = plan.build(cfg, learning_rate=2e-3, seed=3,
                      devices=jax.devices()[:plan.world_size],
                      telemetry_interval_s=0)
    out = [prog.step({"input_ids": ids}) for _ in range(steps)]
    return cfg, prog, out


def test_the_step_program_lowers_the_loss_and_returns_the_counters():
    cfg, prog, out = _plan_run({"fsdp": 1})
    losses = [r.loss for r in out]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    d = out[-1].detail
    tokens, k = 2 * SEQ, cfg.experts_per_token
    # four expert layers; a held assignment is one of tokens x k a layer
    assert 0 < float(d["moe_held_assignments"]) <= 4 * tokens * k
    assert float(d["moe_load_max_over_mean"]) >= 1.0
    # E sum f P is 1 for an even router and at most E
    assert 0.9 < float(d["moe_balance"]) <= cfg.n_experts
    assert int(prog.state["step"]) == len(out)


def test_fsdp1_and_fsdp2_agree():
    _, _, one = _plan_run({"fsdp": 1}, steps=3)
    _, _, two = _plan_run({"fsdp": 2}, steps=3)
    for a, b in zip(one, two):
        assert abs(a.loss - b.loss) < 2e-4 * abs(a.loss)
        assert abs(a.grad_norm - b.grad_norm) < 2e-3 * a.grad_norm
        assert float(a.detail["moe_held_assignments"]) \
            == float(b.detail["moe_held_assignments"])


def test_a_pipeline_plan_and_a_split_sequence_refuse_by_name():
    from ray_tpu.parallel.plan import ParallelPlan
    cfg, _, _ = _setup()
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        ParallelPlan(pp=2, n_microbatches=2).build(cfg)


def test_the_flop_count_follows_the_share_and_the_window():
    """``num_active_params`` counts of the held experts the k x held / E
    a token meets; ``flops_per_token`` a window layer by its keys."""
    cfg, _, _ = _setup()
    per_expert = 3 * 64 * 32
    met = cfg.experts_per_token * 4 / 8
    assert cfg.num_active_params == cfg.num_params - int(
        4 * per_expert * (4 - met))
    full = dataclasses.replace(cfg, sliding_window=0,
                               layer_pattern=("full",))
    s = 4096
    w = dataclasses.replace(cfg, sliding_window=1024)
    pairs = 1024 * 1025 / 2 + (s - 1024) * 1024
    want = 12 * 16 * s * 4 * (1 + 3 * pairs / (s * (s + 1) / 2))
    got = w.flops_per_token(s) - 6.0 * w.num_active_params
    assert abs(got - want) / want < 1e-9
    assert full.flops_per_token(s) - 6.0 * full.num_active_params \
        == 12 * 4 * 4 * 16 * s


# ---------------------------------------- the share ties to the model
def _expert_layer(first, held, seed=5):
    cfg = TransformerConfig(
        d_model=32, n_layers=1, n_experts=8, experts_held=held,
        expert_first=first, experts_per_token=3, expert_width=16,
        block_style="llama", dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    whole = {"w_router": jax.random.normal(ks[0], (32, 8)),
             "we_gate": 0.3 * jax.random.normal(ks[1], (8, 32, 16)),
             "we_up": 0.3 * jax.random.normal(ks[2], (8, 32, 16)),
             "we_down": 0.3 * jax.random.normal(ks[3], (8, 16, 32))}
    lp = {k: v if k == "w_router" else v[first:first + held]
          for k, v in whole.items()}
    h = jax.random.normal(ks[4], (2, 24, 32))
    g = jax.random.normal(ks[5], (2, 24, 32))
    return cfg, lp, h, g


def _layer_and_grads(first, held):
    cfg, lp, h, g = _expert_layer(first, held)
    out, vjp = jax.vjp(lambda lp, h: topk_moe_mlp(cfg, lp, h), lp, h)
    return (out,) + vjp(g)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One expert layer, one upstream cotangent: the four shares' outputs
    sum to the uncut layer's; each share's dW for its held experts IS the
    uncut layer's for those experts; the shares' dX and router gradients
    sum to the uncut layer's. What lands on an absent expert gives
    nothing to y and nothing to any gradient but the router's."""
    out, dlp, dh = _layer_and_grads(0, 8)
    shares = [_layer_and_grads(first, 2) for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(s[0] for s in shares), out,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sum(s[2] for s in shares), dh,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sum(s[1]["w_router"] for s in shares),
                               dlp["w_router"], rtol=1e-4, atol=1e-5)
    for i, s in enumerate(shares):
        for leaf in ("we_gate", "we_up", "we_down"):
            np.testing.assert_allclose(
                s[1][leaf], dlp[leaf][2 * i:2 * i + 2], rtol=1e-4,
                atol=1e-5, err_msg=f"share {i} {leaf}")
    # a share's router gradient is not nothing where its y is: the
    # renormalised weights carry it
    assert float(jnp.max(jnp.abs(shares[1][1]["w_router"]))) > 0


def test_the_turns_of_a_long_call_are_the_call(monkeypatch):
    """A call of more tokens than ``_MANY_TOKENS`` goes through the
    experts a turn at a time: same output, same gradients."""
    import ray_tpu.models.moe as moe
    whole = _layer_and_grads(2, 4)
    monkeypatch.setattr(moe, "_MANY_TOKENS", 12)      # 48 tokens: 4 turns
    turns = _layer_and_grads(2, 4)
    for a, b in zip(jax.tree.leaves(turns), jax.tree.leaves(whole)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_the_program_serves_what_it_trains():
    """``prefill`` on the trained forms, against the same reference: the
    serving path runs this configuration with a model file alone."""
    from ray_tpu.models import init_kv_cache, prefill
    cfg, params, ids = _setup(paged_impl="reference")
    cache = init_kv_cache(cfg, 1 + SEQ // 16, 16)
    bt = jnp.arange(1, 1 + SEQ // 16, dtype=jnp.int32)[None]
    logits, _ = prefill(cfg, params, jnp.asarray(ids[:1]), cache, bt,
                        jnp.zeros((1,), jnp.int32),
                        jnp.full((1,), SEQ, jnp.int32))
    want = mellum.forward(params, jnp.asarray(ids[:1]), _hp())
    assert _rel(logits, want) < 1e-4


def test_the_embedding_std_scales_the_embedding_alone():
    """``embed_init_std`` is the init's alone: the same draw at another
    scale, every other leaf bit for bit, and at 0.02 the tree as it was."""
    key = jax.random.PRNGKey(5)
    base = init_params(TransformerConfig(**TINY), key)
    assert TransformerConfig(**TINY).embed_init_std == 0.02
    wide = init_params(TransformerConfig(**TINY, embed_init_std=0.32), key)
    np.testing.assert_allclose(wide["embed"], 16.0 * base["embed"],
                               rtol=1e-6)
    rest = lambda p: {k: v for k, v in p.items() if k != "embed"}
    for a, b in zip(jax.tree.leaves(rest(wide)),
                    jax.tree.leaves(rest(base))):
        np.testing.assert_array_equal(a, b)


def test_a_token_that_routes_by_its_own_embedding_evens_the_held_share():
    """At the init's 0.02 the attention's running mean, which neighbours
    share, is as large in the stream as a token's own embedding: the
    router sends neighbours alike and the share of the assignments that
    lands on the held experts is the seed's draw. At 0.32 it is the even
    share on every seed (the benchmark's training cell needs every seed
    to do the same work)."""
    sizes = dict(TINY, vocab_size=1024, d_model=256, head_dim=32,
                 rotary_dim=32, window_rotary_dim=32, max_seq_len=512,
                 sliding_window=128, n_experts=16, experts_held=4,
                 expert_first=4, experts_per_token=4)
    even = 4 * 2 * 512 * 4 * 4 / 16      # layers x tokens x k x held / E

    def held(std):
        cfg = TransformerConfig(**sizes, embed_init_std=std)
        stats = jax.jit(lambda p, ids: lm_loss(
            cfg, p, {"input_ids": ids})[1]["moe"]["held_assignments"])
        return np.array([float(stats(
            init_params(cfg, jax.random.PRNGKey(seed)),
            np.random.default_rng(seed).integers(
                0, 1024, (2, 512)).astype(np.int32)))
            for seed in range(5)])
    narrow, wide = held(0.02), held(0.32)
    assert np.max(np.abs(wide - even)) < 0.03 * even
    assert np.ptp(wide) < np.ptp(narrow) / 3
