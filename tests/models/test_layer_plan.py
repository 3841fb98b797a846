"""The layer description every stack is built from
(``models/transformer.py`` ``_layer_plan``): what it says of the six
benchmark configurations against a table written by hand; on synthetic
stacks, that its runs put every layer in exactly one scan, tile each
stack of the tree and count each kind's cache layers the way
``init_kv_cache`` sizes the pools; and that ``norm_eps`` reaches the
dense 'llama' block through the one block function, cached and
training, against a plain block written here."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import harness, spec
from ray_tpu.models import (TransformerConfig, init_kv_cache, init_params,
                            prefill)
from ray_tpu.models.transformer import (KIND_STACKS, WINDOW_POOLS,
                                        _layer_plan, run_layers)

# --------------------------------------------- the six configurations
# cell -> (tree, runs (stack, kind, experts, at, n, cache layer), kinds
# (name, norm, parallel, post_norm, mixer, heads, window, qk_norm,
# head_gate, index_topk, pools, table, scope, rotary (layout, dim,
# yarn?, at positions?), the indexer's rotated width or None))
KV = ("k", "v")
TABLE = {
    "gptj-6b.serve_chat": ("plain", [("layers", "full", False, 0, 6, 0)], [
        ("full", "layer", True, False, "paged", 16, 0, False, False, 0,
         KV, "main", None, ("gptj", 64, False, False), None)]),
    "mistral-7b-v0.3.serve_docqa": (
        "plain", [("layers", "full", False, 0, 8, 0)], [
            ("full", "rms", False, False, "paged", 32, 0, False, False, 0,
             KV, "main", None, ("neox", 128, False, False), None)]),
    "keye-vl-2.0-30b-a3b.serve_longdoc": (
        "plain", [("layers", "full", True, 0, 6, 0)], [
            ("full", "rms", False, False, "paged", 32, 0, True, False, 2048,
             KV + ("ki",), "main", None, ("neox", 128, False, False), 64)]),
    "openpangu-ultra-moe-718b.serve_longdoc16": (
        "latent", [("dense_layers", "full", False, 0, 1, 0),
                   ("layers", "full", True, 0, 4, 1)], [
            ("full", "rms", False, True, "latent", 128, 0, False, False, 0,
             ("latent",), "main", None, ("neox", 64, False, False), None)]),
    "laguna-xs.2.serve_repoqa": (
        "kinds", [("dense_layers", "full", False, 0, 1, 0),
                  ("window_layers", "window", True, 0, 3, 0),
                  ("layers", "full", True, 0, 1, 1)], [
            ("full", "rms", False, False, "paged", 48, 0, False, True, 0,
             KV, "main", "full", ("neox", 64, True, True), None),
            ("window", "rms", False, False, "paged", 64, 512, False, True, 0,
             WINDOW_POOLS, "window", "window", ("neox", 128, False, True),
             None)]),
    "a.x-k2.serve_longdoc64": (
        "latent", [("dense_layers", "full", False, 0, 1, 0),
                   ("layers", "full", True, 0, 4, 1)], [
            ("full", "gated", False, False, "latent", 64, 0, False, True,
             2048, ("latent", "ki"), "main", None,
             ("neox", 64, True, True), None)]),
}


def _cell_config(cell_name):
    cell = spec.load_cell(cell_name)
    kw = cell.model_kwargs()
    kw["dtype"] = harness.resolve_dtype(kw["dtype"])
    return TransformerConfig(**kw)


@pytest.mark.parametrize("cell_name", sorted(TABLE))
def test_the_plan_of_a_benchmark_configuration(cell_name):
    tree, runs, kinds = TABLE[cell_name]
    plan = _layer_plan(_cell_config(cell_name))
    assert plan.tree == tree
    assert [(r.stack, r.kind.name, r.experts, r.at, r.n, r.cache_layer)
            for r in plan.runs] == runs
    assert [(k.name, k.norm, k.parallel, k.post_norm, k.mixer, k.heads,
             k.window, k.qk_norm, k.head_gate, k.index_topk,
             tuple(p.name for p in k.pools), k.table, k.scope,
             (k.rotary.layout, k.rotary.dim, bool(k.rotary.yarn),
              k.rotary.at_positions),
             k.index_rotary and k.index_rotary.dim)
            for k in plan.kinds] == kinds
    # a run's kind is one of the plan's, the object itself
    assert all(any(r.kind is k for k in plan.kinds) for r in plan.runs)


# ------------------------------------------------- synthetic stacks
BASE = dict(vocab_size=64, d_model=32, n_heads=4, head_dim=8, n_kv_heads=2,
            d_ff=48, max_seq_len=64, rotary_dim=8, block_style="llama",
            dtype=jnp.float32, remat_policy="none")
EXPERTS = dict(n_experts=4, experts_per_token=2, expert_width=16)
WWF = dict(layer_pattern=("window", "window", "full"), sliding_window=16,
           window_heads=6)
LATENT = dict(BASE, head_dim=12, q_lora_rank=16, kv_lora_rank=128,
              qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, n_kv_heads=None,
              **EXPERTS)
STACKS = {
    "wwf at two whole periods": dict(BASE, n_layers=6, **WWF, **EXPERTS),
    "wwf at two periods and a layer": dict(BASE, n_layers=7, **WWF),
    "wwf cut in its second period, a window layer leading dense":
        dict(BASE, n_layers=4, n_dense_layers=1, **WWF, **EXPERTS),
    "fwww, a full layer leading dense":
        dict(BASE, n_layers=5, n_dense_layers=1, **EXPERTS,
             **dict(WWF, layer_pattern=("full", "window", "window",
                                        "window"))),
    "experts, none dense": dict(BASE, n_layers=3, head_gate=True, **EXPERTS),
    "experts behind one dense layer":
        dict(BASE, n_layers=4, n_dense_layers=1, **EXPERTS),
    "experts behind three dense layers":
        dict(BASE, n_layers=5, n_dense_layers=3, **EXPERTS),
    "latent behind a dense layer": dict(LATENT, n_layers=3,
                                        n_dense_layers=1),
    "latent, none dense": dict(LATENT, n_layers=2, index_topk=8,
                               index_heads=2, index_dim=8),
    "plain gptj": dict(BASE, n_layers=3, block_style="gptj",
                       n_kv_heads=None),
    "plain llama": dict(BASE, n_layers=2),
    "plain llama, selecting experts":
        dict(BASE, n_layers=2, qk_norm=True, index_topk=8, index_heads=2,
             index_dim=8, **EXPERTS),
}


@pytest.mark.parametrize("name", sorted(STACKS))
def test_the_runs_tile_the_stacks_and_count_the_cache_layers(name):
    c = TransformerConfig(**STACKS[name])
    plan = _layer_plan(c)
    params = jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0)))
    # every layer in exactly one run, in the stack and of the kind the
    # configuration gives it
    walked = [(r.stack, r.at + i, r.kind.name, r.cache_layer + i,
               r.experts)
              for r in plan.runs for i in range(r.n)]
    assert len(walked) == c.n_layers
    at, ordinal = {}, {}
    for l, (stack, i, kind, cache_layer, experts) in enumerate(walked):
        lead = l < c.n_dense_layers
        assert kind == c.layer_kind(l)
        assert stack == ("dense_layers" if lead else KIND_STACKS[kind])
        assert experts == (bool(c.experts_per_token) and not lead)
        # its place in the stack, and among its kind's cache layers, in
        # stack order
        assert i == at.get(stack, 0)
        assert cache_layer == ordinal.get(kind, 0)
        at[stack], ordinal[kind] = i + 1, cache_layer + 1
        assert ("w_gate" in params[stack]) == (
            not experts and c.block_style == "llama")
    # the slices tile each stack of the tree, and no stack is left over
    stacks = {k for k, v in params.items()
              if isinstance(v, dict) and k.endswith("layers")}
    assert stacks == set(at)
    for stack, n in at.items():
        assert {leaf.shape[0] for leaf in params[stack].values()} == {n}
    # a run never ends where the next could go on
    assert all(a.stack != b.stack for a, b in zip(plan.runs, plan.runs[1:]))
    # the pools have the layers the runs count, under each kind's table
    cache = jax.eval_shape(lambda: init_kv_cache(c, 5, 4, 3))
    assert set(cache) == {p.name for k in plan.kinds for p in k.pools}
    for kind in plan.kinds:
        for pool in kind.pools:
            assert cache[pool.name].shape == (
                ordinal.get(kind.name, 0),
                3 if kind.table == "window" else 5, pool.heads, 4,
                pool.width)


# --------------------------------- norm_eps in the dense 'llama' block
EPS_CFG = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2, head_dim=16,
               d_ff=64, max_seq_len=32, rotary_dim=16, rope_base=1e4,
               block_style="llama", dtype=jnp.float32, remat_policy="none",
               paged_impl="reference", norm_eps=1e-5)
SEQ = 12


def _plain_rms(x, w, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _plain_block(x, lp, l, eps, heads=2, head_dim=16, base=1e4):
    """One pre-RMSNorm block over ``x [s, e]`` in float64: causal
    attention with a rotate-half rotary over the whole head, then a
    SwiGLU MLP, each added to the stream."""
    s = x.shape[0]
    w = {k: np.asarray(v[l], np.float64) for k, v in lp.items()}
    h = _plain_rms(x, w["attn_norm"], eps)
    ang = np.arange(s)[:, None] * (
        1.0 / base ** (np.arange(0, head_dim, 2) / head_dim))[None]
    sin, cos = np.sin(ang)[:, None], np.cos(ang)[:, None]

    def heads_of(m):
        return (h @ m).reshape(s, heads, head_dim)

    def rope(t):
        a, b = t[..., :head_dim // 2], t[..., head_dim // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    q, k, v = rope(heads_of(w["wq"])), rope(heads_of(w["wk"])), \
        heads_of(w["wv"])
    score = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(head_dim)
    score = np.where(np.tril(np.ones((s, s), bool)), score, -np.inf)
    p = np.exp(score - score.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    x = x + np.einsum("hqk,khd->qhd", p, v).reshape(s, -1) @ w["wo"]
    h2 = _plain_rms(x, w["mlp_norm"], eps)
    gate = h2 @ w["w_gate"]
    return x + (gate / (1 + np.exp(-gate)) * (h2 @ w["w_up"])) @ w["w_down"]


def _plain_trunk(params, ids, eps):
    x = np.asarray(params["embed"], np.float64)[ids]
    for l in range(EPS_CFG["n_layers"]):
        x = _plain_block(x, params["layers"], l, eps)
    return x


def _trained(cfg, params, ids):
    x = jnp.take(params["embed"], ids, axis=0)[None]
    out, _ = run_layers(cfg, params["layers"], x)
    return np.asarray(out[0], np.float64)


def _cached(cfg, params, ids):
    """The final hidden states' logits through ``prefill``, one chunk."""
    bs = 4
    cache = init_kv_cache(cfg, 1 + SEQ // bs, bs)
    logits, _ = prefill(
        cfg, params, jnp.asarray(ids)[None], cache,
        jnp.arange(1, 1 + SEQ // bs, dtype=jnp.int32)[None],
        jnp.zeros((1,), jnp.int32), jnp.full((1,), SEQ, jnp.int32))
    return np.asarray(logits[0], np.float64)


@pytest.mark.parametrize("path", ["training", "cached"])
def test_norm_eps_reaches_the_dense_llama_block(path):
    cfg = TransformerConfig(**EPS_CFG)
    params = init_params(cfg, jax.random.PRNGKey(3))
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (SEQ,), 0,
                                        cfg.vocab_size))

    def plain(eps):
        x = _plain_trunk(params, ids, eps)
        if path == "training":
            return x
        # the final norm has read the key since it was given one
        x = _plain_rms(x, np.asarray(params["final_norm"]["scale"],
                                     np.float64), cfg.norm_eps)
        return x @ np.asarray(params["lm_head"]["w"], np.float64)
    got = (_trained if path == "training" else _cached)(cfg, params, ids)
    # at the default init the stream's mean square is 4e-4: 1e-5 against
    # 1e-6 is a hundredth of a norm's output
    err = {eps: np.abs(got - plain(eps)).max() / np.abs(plain(eps)).max()
           for eps in (1e-5, 1e-6)}
    tol = 1e-5
    assert err[1e-5] < tol, err
    assert err[1e-6] > 20 * tol, err
