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
from ray_tpu.models import moe
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


# ------------------- the differentiated grouped product's Pallas rules
ROWS, GROUPS = 1024, 5                    # two row tiles of 512


@pytest.fixture
def interpreted(monkeypatch):
    """The rules as on a TPU, their kernels in Pallas interpret mode: the
    steering is the test's, the program has no switch."""
    import functools
    import types
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(moe, "_megablox", types.SimpleNamespace(
        gmm=functools.partial(moe._megablox.gmm, interpret=True),
        tgmm=functools.partial(moe._megablox.tgmm, interpret=True)))


def _product(sizes, dtype, k=128, n=256, seed=7, one_chip=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (ROWS, k), dtype)
    w = 0.1 * jax.random.normal(ks[1], (GROUPS, k, n), jnp.float32)
    g = jax.random.normal(ks[2], (ROWS, n), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    y, vjp = jax.vjp(lambda x, w: moe._grouped_dot(x, w, sizes, one_chip),
                     x, w)
    return (y,) + vjp(g)


def _plain(sizes, dtype, k=128, n=256, seed=7):
    """``ragged_dot`` / ``ragged_dot_general`` on the same operands, with
    nothing of ``models/moe.py`` between."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (ROWS, k), dtype)
    w = (0.1 * jax.random.normal(ks[1], (GROUPS, k, n), jnp.float32))
    g = jax.random.normal(ks[2], (ROWS, n), jnp.float32).astype(dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    wc = w.astype(dtype)
    y = jax.lax.ragged_dot(x, wc, sizes, preferred_element_type=jnp.float32)
    dx = jax.lax.ragged_dot(g, jnp.swapaxes(wc, 1, 2), sizes,
                            preferred_element_type=dtype)
    dw = jax.lax.ragged_dot_general(
        x, g, sizes, moe._DW_DIMS, preferred_element_type=jnp.float32)
    return y, dx, dw


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("sizes,what", [
    ((300, 0, 400, 100, 0), "empty groups, a group across the row tile, "
                            "224 rows of no group behind the last"),
    ((512, 512, 0, 0, 0), "groups that end on the row tile, none behind"),
    ((0, 0, 0, 0, 7), "one short group behind four empty ones"),
    ((0, 0, 0, 0, 0), "no row in any group"),
    ((1, 1021, 1, 1, 0), "a group over both row tiles"),
])
def test_the_pallas_rules_are_the_grouped_product(interpreted, sizes, what,
                                                  dtype):
    """The VJP rules in Pallas interpret mode against XLA's grouped
    products: the forward, dX (zero on the rows of no group) and dW
    (float32 for a float32 master), each to the accumulation's order."""
    before = moe.grouped_product_counts()
    y, dx, dw = _product(sizes, dtype)
    after = moe.grouped_product_counts()
    assert after["pallas_gmm"] == before["pallas_gmm"] + 1, what
    assert after["xla_ragged_dot"] == before["xla_ragged_dot"]
    want_y, want_dx, want_dw = _plain(sizes, dtype)
    live = np.arange(ROWS) < sum(sizes)
    f32 = lambda a: np.asarray(a, np.float32)
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    # a row of no group is whatever the product left there, in the
    # forward: only the groups' rows are compared
    np.testing.assert_allclose(f32(y)[live], f32(want_y)[live],
                               rtol=1e-5, atol=1e-5, err_msg=what)
    assert dx.dtype == dtype and dw.dtype == jnp.float32
    np.testing.assert_allclose(f32(dx)[live], f32(want_dx)[live],
                               rtol=ulp, atol=ulp, err_msg=what)
    assert not np.any(f32(dx)[~live]), what
    np.testing.assert_allclose(f32(dw), f32(want_dw), rtol=1e-5,
                               atol=1e-4, err_msg=what)


@pytest.mark.parametrize("rows,k,n,why", [
    (1000, 128, 256, "rows no multiple of the row tile"),
    (1024, 96, 256, "the contraction no multiple of 128"),
    (1024, 128, 200, "the result's width no multiple of 128"),
])
def test_a_shape_the_rule_cannot_tile_stays_ragged_dot(interpreted, rows, k,
                                                       n, why):
    assert moe._gmm_tiles(rows, k, n, "bfloat16") is None, why
    x = jnp.ones((rows, k), jnp.bfloat16)
    w = jnp.ones((2, k, n), jnp.float32)
    sizes = jnp.asarray([rows // 2, rows // 4], jnp.int32)
    before = moe.grouped_product_counts()
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(moe._grouped_dot(x, w, sizes, True)),
        argnums=(0, 1)))(x, w))
    after = moe.grouped_product_counts()
    assert "pallas_call" not in text and "ragged_dot" in text
    assert after["xla_ragged_dot"] == before["xla_ragged_dot"] + 1
    assert after["pallas_gmm"] == before["pallas_gmm"]


def test_off_a_tpu_the_rules_are_ragged_dot_and_counted_so():
    before = moe.grouped_product_counts()
    _product((300, 0, 400, 100, 0), jnp.bfloat16)
    after = moe.grouped_product_counts()
    assert after == {"pallas_gmm": before["pallas_gmm"],
                     "xla_ragged_dot": before["xla_ragged_dot"] + 1}


def test_a_program_over_several_chips_keeps_ragged_dot(monkeypatch):
    """XLA cannot partition a Pallas custom call: under a mesh of more
    than one device (or with none named) the rules stay ``ragged_dot``
    on a TPU too, at widths the kernels would tile, and the step program
    of a plan over two chips lowers no grouped body."""
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = moe.grouped_product_counts()
    _product((300, 0, 400, 100, 0), jnp.bfloat16, one_chip=False)
    assert moe.grouped_product_counts()["xla_ragged_dot"] \
        == before["xla_ragged_dot"] + 1
    cfg = TransformerConfig(**{
        **TINY, "dtype": jnp.bfloat16, "d_model": 128, "expert_width": 128,
        "max_seq_len": 256, "head_dim": 32, "rotary_dim": 32,
        "window_rotary_dim": 32})
    counts = {}
    for chips in (1, 2):
        mesh = build_mesh(MeshSpec(dp=1, fsdp=chips), jax.devices()[:chips])
        bundle = make_train_step(cfg, mesh, telemetry_interval_s=0)
        state = jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))
        batch = {"input_ids": jax.ShapeDtypeStruct((2, 256), jnp.int32),
                 "loss_mask": jax.ShapeDtypeStruct((2, 256), jnp.float32)}
        text = bundle.step_fn.trace(state, batch).lower(
            lowering_platforms=("tpu",)).as_text()
        counts[chips] = (_grouped_bodies(text), bundle.grouped_products)
    assert counts[1][1] == {"pallas_gmm": 6, "xla_ragged_dot": 0}
    assert counts[1][0] >= 3
    assert counts[2] == (0, {"pallas_gmm": 0, "xla_ragged_dot": 6})


@pytest.mark.parametrize("rows,k,n", [
    (32768, 2304, 896), (32768, 896, 2304),    # the cell's turn, up / down
    (512, 128, 128), (4096, 4096, 14336), (8192, 1024, 512),
    (1024, 7168, 2048), (2048, 2048, 7168)])
def test_every_tiling_the_rule_picks_is_inside_its_budget(rows, k, n):
    """Tiles divide the shape, stand on whole lane tiles, and the blocks'
    bytes stay inside the budget (every tiling that does compiles for a
    v5e; tests/ops/test_tpu_lowering.py compiles the cell's)."""
    tiles = moe._gmm_tiles(rows, k, n, "bfloat16")
    for (tm, tk, tn), (kk, nn), out_size, dw in zip(
            tiles, ((k, n), (n, k), (k, n)), (4, 2, 4),
            (False, False, True)):
        assert rows % tm == 0 and kk % tk == 0 and nn % tn == 0
        assert tk % 128 == 0 and tn % 128 == 0
        assert moe._block_bytes(tm, tk, tn, 2, out_size, dw) \
            <= moe._VMEM_BUDGET
    if (k, n) == (2304, 896):
        assert tiles == ((512, 768, 896), (512, 896, 768), (512, 768, 896))


def _grouped_bodies(text):
    """Pallas bodies of the grouped kernels in a lowered program's text:
    the ``tpu_custom_call``s inside megablox's jitted ``gmm`` / ``tgmm``."""
    return sum(part.count("tpu_custom_call")
               for part in text.split("func.func")
               if part.lstrip().startswith(("private @gmm", "private @tgmm")))


def test_the_cells_two_programs_hold_at_most_eight_grouped_bodies(
        monkeypatch):
    """Lowered for a TPU with no chip, at the widths of
    ``mellum2-12b-a2.5b.train_moe_8k``: the check's loss-and-gradient
    program (2 x 4096, traced first, as the cell's set-up does) and the
    step program (2 x 8192) each lower SIX distinct kernels to at most
    eight bodies (a turn's forward and its recomputation lower one each),
    not one a product, a turn and a scan; no ``ragged_dot`` is left in
    either, and the bundle carries the step's count by form. This pins
    what the kernels add to the cell's set-up."""
    from benchmarks import spec
    from ray_tpu.models.training import default_optimizer, make_train_step
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = spec.load_cell("mellum2-12b-a2.5b.train_moe_8k")
    p = cell.params
    cfg = dataclasses.replace(
        TransformerConfig(**{**cell.model_kwargs(), "dtype": jnp.bfloat16}),
        max_seq_len=p["seq"], remat_policy=p["remat_policy"])
    mesh = build_mesh(MeshSpec(dp=1, fsdp=1), jax.devices()[:1])
    bundle = make_train_step(
        cfg, mesh, optimizer=default_optimizer(p["learning_rate"], 0.0, 1.0),
        telemetry_interval_s=0)
    assert bundle.grouped_products == {}
    state = jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))

    def batch(seq):
        return {"input_ids": jax.ShapeDtypeStruct((2, seq), jnp.int32),
                "loss_mask": jax.ShapeDtypeStruct((2, seq), jnp.float32)}

    def check(params, batch):
        return jax.value_and_grad(lambda q: lm_loss(
            cfg, q, batch, mesh=mesh, rules=bundle.rules)[0])(params)
    for name, fn, args in (
            ("check", jax.jit(check),
             (state["params"], batch(p["check_seq"]))),
            ("step", bundle.step_fn, (state, batch(p["seq"])))):
        text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        assert 6 <= _grouped_bodies(text) <= 8, name
        assert "ragged_dot" not in text, name
    # two scans (the window run, the full layer), three products each
    assert bundle.grouped_products == {"pallas_gmm": 6, "xla_ragged_dot": 0}


def test_a_served_program_lowers_no_grouped_kernel(monkeypatch):
    """One decode step and one chunk of a model whose differentiated
    products DO tile: lowered for a TPU, neither holds a ``gmm`` body,
    and each is to the letter the text it lowers to with the rules
    patched back to ``ragged_dot``; the same layer's gradient holds the
    kernels. Only differentiation reaches the rules."""
    from ray_tpu.models import decode_step, init_kv_cache, prefill
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = TransformerConfig(**{
        **TINY, "dtype": jnp.bfloat16, "d_model": 128, "expert_width": 128,
        "experts_per_token": 2, "paged_impl": "reference"})
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 1 + 256 // 16, 16))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)

    def lowered():
        chunk = jax.jit(lambda p, t, c, bt, s, n: prefill(
            cfg, p, t, c, bt, s, n)).trace(
                params, i32(1, 256), cache, i32(1, 16), i32(1), i32(1))
        step = jax.jit(lambda p, t, c, bt, pos: decode_step(
            cfg, p, t, c, bt, pos)).trace(
                params, i32(4), cache, i32(4, 16), i32(4))
        return [t.lower(lowering_platforms=("tpu",)).as_text()
                for t in (chunk, step)]
    before = moe.grouped_product_counts()
    served = lowered()
    assert moe.grouped_product_counts() == before
    for text in served:
        assert _grouped_bodies(text) == 0 and "ragged_dot" in text
    monkeypatch.setattr(moe, "_gmm_tiles", lambda *a: None)
    assert lowered() == served
    monkeypatch.undo()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lp = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                      params["layers"])
    h = jax.ShapeDtypeStruct((1, 256, 128), jnp.bfloat16)
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("fsdp",))
    grad = jax.jit(jax.grad(lambda lp, h: jnp.sum(
        topk_moe_mlp(cfg, lp, h, mesh=one).astype(jnp.float32)))).trace(lp, h)
    text = grad.lower(lowering_platforms=("tpu",)).as_text()
    assert _grouped_bodies(text) == 3     # gmm, its transpose, tgmm


def test_the_plan_logs_the_grouped_products_by_form(caplog):
    import logging
    with caplog.at_level(logging.INFO, logger="ray_tpu.parallel.plan"):
        _, prog, _ = _plan_run({"fsdp": 1}, steps=2)
    # off a TPU every product is ragged_dot: two scans, three products
    assert prog.bundle.grouped_products == {"pallas_gmm": 0,
                                            "xla_ragged_dot": 6}
    lines = [r.getMessage() for r in caplog.records
             if "grouped products" in r.getMessage()]
    assert len(lines) == 1 and lines[0].endswith(
        "experts' grouped products: 0 pallas_gmm, 6 xla_ragged_dot")

