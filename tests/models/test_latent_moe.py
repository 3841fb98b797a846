"""The dropless experts in their latent, ungated form (``models/moe.py``:
``expert_act`` "relu2", ``moe_latent``) against a dense loop over the
experts (``benchmarks/reference/nemotron_h.py``'s, which sorts nothing
and groups nothing): a whole layer, a held share, inside a stack with a
layer index; eight shares adding up to the uncut layer; the router's
bias selecting and not weighing; the gated form left as it was."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import nemotron_h
from ray_tpu.models import TransformerConfig, init_params
from ray_tpu.models.moe import (expert_leaves, route_topk, topk_moe_mlp,
                                topk_moe_param_shapes)

CFG = dict(vocab_size=64, d_model=48, n_layers=2, n_heads=4, head_dim=16,
           n_kv_heads=2, d_ff=32, max_seq_len=64, rotary_dim=0,
           block_style="llama", dtype=jnp.float32, remat_policy="none",
           norm_eps=1e-5, layer_pattern=["full", "ffn"], mixer_only=True,
           n_experts=16, experts_per_token=5, expert_width=24,
           shared_expert_width=40, router_score="sigmoid", router_bias=True,
           routed_scale=5.0, expert_act="relu2", moe_latent=32)
HP = dict(num_experts_per_tok=5, routed_scaling_factor=5.0, expert_first=0,
          experts_held=16)


def _layer(**over):
    cfg = TransformerConfig(**{**CFG, **over})
    stack = init_params(cfg, jax.random.PRNGKey(5))["ffn_layers"]
    # louder routed experts and a bias that changes the choice: at the
    # init's scale neither can be told from its absence
    stack = {**stack, "we_down": stack["we_down"] * 64.0,
             "we_up": stack["we_up"] * 8.0,
             "router_bias": stack["router_bias"] * 40.0}
    return cfg, stack


def _dense_loop(stack, h, layer=0, **hp):
    lp = {k: v[layer] for k, v in stack.items()
          if k not in ("we_up", "we_down")}
    with jax.default_matmul_precision("highest"):
        return nemotron_h._experts(h, lp, {**HP, **hp}, layer,
                                   stack["we_up"], stack["we_down"])


H_IN = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 48))


def test_the_leaves_of_the_latent_ungated_form():
    cfg, stack = _layer()
    assert expert_leaves(cfg) == ("we_up", "we_down")
    shapes = topk_moe_param_shapes(cfg)
    assert "we_gate" not in shapes and "ws_gate" not in shapes
    assert shapes["we_up"] == (16, 32, 24)
    assert shapes["we_down"] == (16, 24, 32)
    assert shapes["w_lat_down"] == (48, 32) and shapes["w_lat_up"] == (32, 48)
    assert shapes["ws_up"] == (48, 40)
    assert set(stack) == set(shapes) | {"mlp_norm", "router_bias"}


def test_the_gated_form_keeps_its_leaves():
    cfg = TransformerConfig(**{**CFG, "expert_act": "swiglu",
                               "moe_latent": 0})
    assert expert_leaves(cfg) == ("we_gate", "we_up", "we_down")
    assert topk_moe_param_shapes(cfg)["we_gate"] == (16, 48, 24)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 0), (4, 8), (2, 14)])
def test_the_layer_is_the_dense_loop(held, first):
    cfg, stack = _layer()
    part = dataclasses.replace(cfg, experts_held=held, expert_first=first)
    lp = {k: v[0] for k, v in stack.items()}
    lp.update({k: lp[k][first:first + held] for k in ("we_up", "we_down")})
    got = topk_moe_mlp(part, lp, H_IN)
    mine = {**stack, **{k: stack[k][:, first:first + held]
                        for k in ("we_up", "we_down")}}
    want = _dense_loop(mine, H_IN, experts_held=held, expert_first=first)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    if held < 16:        # and not the whole layer's
        assert float(jnp.max(jnp.abs(got - _dense_loop(stack, H_IN)))) > 1e-3


def test_a_layer_of_a_whole_stack_reads_its_own_experts():
    """Inside the layer scan the expert leaves come whole, ``[L, E, ..]``,
    with the layer's index."""
    cfg, stack = _layer(n_layers=4, layer_pattern=["ffn"])
    assert stack["we_up"].shape[0] == 4
    for layer in (0, 2, 3):
        lp = {k: v if k in ("we_up", "we_down") else v[layer]
              for k, v in stack.items()}
        got = topk_moe_mlp(cfg, lp, H_IN, jnp.int32(layer))
        np.testing.assert_allclose(got, _dense_loop(stack, H_IN, layer),
                                   rtol=2e-5, atol=2e-6)


def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each; what every chip computes alike
    (the shared expert) is counted once, and the latent's up-projection,
    being linear, adds up by itself: the sum is the uncut reference's
    whole layer."""
    cfg, stack = _layer()
    lp = {k: v[0] for k, v in stack.items()}
    nothing = {k: jnp.zeros_like(lp[k][:1]) for k in ("we_up", "we_down")}
    shared = topk_moe_mlp(
        dataclasses.replace(cfg, experts_held=1, expert_first=0),
        {**lp, **nothing}, H_IN)
    total = -7.0 * shared
    for first in range(0, 16, 2):
        part = dataclasses.replace(cfg, experts_held=2, expert_first=first)
        held = {k: lp[k][first:first + 2] for k in ("we_up", "we_down")}
        total = total + topk_moe_mlp(part, {**lp, **held}, H_IN)
    want = _dense_loop(stack, H_IN)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-2   # experts heard


def test_the_bias_selects_and_does_not_weigh():
    cfg, stack = _layer()
    lp = {k: v[0] for k, v in stack.items()}
    x = H_IN.reshape(-1, 48)
    weights, experts = route_topk(cfg, lp, x)
    scores = jax.nn.sigmoid(x @ lp["w_router"])
    _, plain = jax.lax.top_k(scores, 5)
    assert not np.array_equal(np.sort(experts, -1), np.sort(plain, -1))
    _, biased = jax.lax.top_k(scores + lp["router_bias"], 5)
    assert np.array_equal(np.asarray(experts), np.asarray(biased))
    chosen = jnp.take_along_axis(scores, experts, -1)
    np.testing.assert_allclose(
        weights, 5.0 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 5.0, rtol=1e-6)


@pytest.mark.parametrize("control", ["bias_weighs", "no_routed_scale",
                                     "one_expert_fewer", "gated_expert"])
def test_each_control_of_the_layer_is_told_apart(control):
    cfg, stack = _layer()
    got = topk_moe_mlp(cfg, {k: v[0] for k, v in stack.items()}, H_IN)
    sound = _dense_loop(stack, H_IN)
    off = _dense_loop(stack, H_IN, control=control)
    scale = float(jnp.max(jnp.abs(sound)))
    assert float(jnp.max(jnp.abs(got - sound))) / scale < 2e-5
    assert float(jnp.max(jnp.abs(got - off))) / scale > 1e-3
