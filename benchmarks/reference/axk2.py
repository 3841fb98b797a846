"""A.X-K2's language model (skt/A.X-K2 config.json, ``model_type: axk2``,
688B-A33B): sequential blocks ``h = x + Attn(GN(x))``, ``y = h +
FFN(GN(h))`` whose two leading norms are GATED (``gated_norm``: ``n =
RMSNorm(x)``, ``n * sigmoid((n W_d) W_u)``, rank ``gated_norm_rank``);
latent (MLA) attention, DeepSeek-V3's (arXiv 2412.19437, 2.1.1): queries
through a ``q_lora_rank`` bottleneck with its own RMSNorm to
``num_attention_heads`` heads of ``qk_nope_head_dim + qk_rope_head_dim``,
keys and values from ONE normed latent of ``kv_lora_rank`` a token beside
one rotated key of ``qk_rope_head_dim`` that every head shares, rotary by
a YaRN table (``rope_parameters``) whose ``mscale`` goes on the softmax
scale; a learned key selection, DeepSeek-V3.2-Exp's indexer
(``index_n_heads`` query heads of ``index_head_dim`` projected from the
query bottleneck, one shared LayerNormed key head, rotary on their first
``qk_rope_head_dim`` numbers, relu, a learned weight a head): a query
attends the ``index_topk`` keys of largest score among those it may see
and no other; each head's output times a sigmoid gate of the sublayer's
input (``attention_output_gate``); a leading dense SwiGLU layer, then
layers of routed SwiGLU experts behind DeepSeek-V3's ``noaux_tc`` router
(2.1.2): a sigmoid score an expert, selection by score plus a per-expert
bias under a group limit (``n_group`` groups, the ``topk_group`` of
largest sum of their two best kept), the weights the chosen experts'
scores WITHOUT the bias, renormalised (``norm_topk_prob``) and times
``routed_scaling_factor``, beside a shared expert; untied head. No
multi-token-prediction module (``num_nextn_predict_layers`` 0).

A chip of the deployment holds a share of the routed experts
(``expert_first`` .. ``expert_first + experts_held``, both in ``hp``):
every token is still routed over all of them and its weights normalised
over all it chose; the sum runs over the chosen experts held here.

Plain float32, nothing cached, absorbed, batched or tiled: the indexer's
full causal score matrix, selection by a stable descending sort as a
mask, every head's key and value expanded from the latent one head at a
time, one expert at a time, the wide matrices upcast a slice at a time.

``hp["control"]`` (absent in every configuration's file) names ONE
deliberate fault, for the checks that a limit refuses it: see
``CONTROLS``.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, make_api

#: where this file fills the published description in
departures = {
    "gated_norm": "gated_norm_rank 16 names a rank and no formula: n = "
                  "RMSNorm(x), n * sigmoid((n W_d) W_u), W_d (hidden, "
                  "rank), W_u (rank, hidden), no bias, nothing between "
                  "(the published GatedNorm form), on the two norms ahead "
                  "of a layer's sublayers and no other",
    "head_gate": "attention_output_gate ('head-specific output gate'): "
                 "sigmoid(g W_g), one number a head, W_g (hidden, heads), "
                 "g the sublayer's gated-normed input, times the head's "
                 "output ahead of W_o; attn_gate_fused is how the "
                 "checkpoint stores W_g",
    "indexer_k_norm": "LayerNorm (scale and bias, eps 1e-6) on the "
                      "indexer's key, DeepSeek-V3.2's convention",
    "indexer_rotary": "rotate-half by the layer's YaRN table on the first "
                      "qk_rope_head_dim numbers of every qI head and of kI",
    "indexer_weights": "w = g W_w times index_n_heads^-1/2 * "
                       "index_head_dim^-1/2",
    "indexer_query": "qI is projected from the normed query bottleneck cq "
                     "(q_lora_rank wide), the indexer's published form "
                     "where a query LoRA exists",
    "mscale": "mscale 1 and mscale_all_dim 1 give sin and cos a factor of "
              "1 and the softmax scale (0.1 ln(factor) + 1)^2, as "
              "DeepSeek-V3's public modeling file treats the same keys",
    "rotary": "rotate-half over qk_rope_head_dim; YaRN's correction range "
              "truncated to whole dimensions (the public default)",
    "router_bias": "e_score_correction_bias is seeded normal(0, 0.01): at "
                   "zero a fault that ignores it could not be seen",
}

#: the faults ``hp["control"]`` can name
CONTROLS = ("random_keys", "no_index_rotary", "index_query_hidden",
            "bias_not_in_selection", "bias_in_weights", "plain_rope",
            "no_mscale")

#: columns of a wide matrix upcast at a time
_SLICE = 2048


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _matmul(x, w):
    """``x @ w`` in float32 with ``w`` as stored, upcast ``_SLICE``
    columns at a time."""
    n = w.shape[-1]
    if n <= _SLICE:
        return x @ w.astype(F32)
    return jnp.concatenate([x @ w[:, i:i + _SLICE].astype(F32)
                            for i in range(0, n, _SLICE)], -1)


def _swiglu(h, gate, up, down):
    """SwiGLU through ``gate``/``up`` ``(d, f)`` and ``down`` ``(f, d)``
    as stored, ``_SLICE`` of the ``f`` middle columns at a time."""
    out = jnp.zeros(h.shape[:-1] + (down.shape[-1],), F32)
    for i in range(0, gate.shape[-1], _SLICE):
        g, u = (w[:, i:i + _SLICE].astype(F32) for w in (gate, up))
        out = out + (jax.nn.silu(h @ g) * (h @ u)) \
            @ down[i:i + _SLICE].astype(F32)
    return out


def _gated_norm(x, lp, name, hp):
    n = _rms_norm(x, lp[f"{name}_norm"].astype(F32), hp["rms_norm_eps"])
    if not hp["gated_norm"]:
        return n
    low = n @ lp[f"{name}_gn_down"].astype(F32)
    return n * jax.nn.sigmoid(low @ lp[f"{name}_gn_up"].astype(F32))


def _yarn_inv_freq(hp):
    """YaRN's inverse frequencies over ``qk_rope_head_dim``: dimensions
    that turn more than ``beta_fast`` times within the original length
    keep their frequency, those that turn fewer than ``beta_slow`` are
    divided by ``factor``, a linear ramp between."""
    d, base = hp["qk_rope_head_dim"], hp["rope_theta"]
    freq = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if hp.get("control") == "plain_rope":
        return freq.astype(np.float32)

    def correction_dim(turns):
        return d * math.log(hp["yarn_original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(hp["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(hp["yarn_beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return (freq / hp["yarn_factor"] * ramp
            + freq * (1.0 - ramp)).astype(np.float32)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rotary_half(x, hp):
    """Rotate-half on the first ``qk_rope_head_dim`` numbers of ``x (b,
    s, d)``, position = index along s; sin and cos times ``mscale /
    mscale_all_dim``'s ratio (1 here)."""
    d = hp["qk_rope_head_dim"]
    ratio = _mscale(hp["yarn_factor"], hp["mscale"]) \
        / _mscale(hp["yarn_factor"], hp["mscale_all_dim"])
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] \
        * jnp.asarray(_yarn_inv_freq(hp))[None]            # (s, d/2)
    sin, cos = jnp.sin(ang)[None] * ratio, jnp.cos(ang)[None] * ratio
    x1, x2, rest = x[..., :d // 2], x[..., d // 2:d], x[..., d:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _largest(x, k):
    """Mask of the k largest along the last axis, ties to the lower
    index: rank in a stable descending sort."""
    order = jnp.argsort(-x, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < k


def _selection(g, cq, lp, hp, causal):
    """(b, t, s) mask of the keys each query attends: the ``index_topk``
    of largest indexer score among those it may see (all while it sees
    fewer)."""
    b, s, _ = g.shape
    control = hp.get("control")
    hi, di = hp["index_n_heads"], hp["index_head_dim"]
    src = g[..., :cq.shape[-1]] if control == "index_query_hidden" else cq
    qi = _matmul(src, lp["wq_idx"]).reshape(b, s, hi, di)
    ki = _layer_norm(g @ lp["wk_idx"].astype(F32),
                     lp["k_idx_scale"].astype(F32),
                     lp["k_idx_bias"].astype(F32), 1e-6)
    if control != "no_index_rotary":
        ki = _rotary_half(ki, hp)
    w = (g @ lp["ww_idx"].astype(F32)) * (hi ** -0.5 * di ** -0.5)

    def head(j, total):                    # one indexer head at a time
        q = qi[:, :, j] if control == "no_index_rotary" \
            else _rotary_half(qi[:, :, j], hp)
        return total + w[:, :, j, None] * jax.nn.relu(
            jnp.einsum("btd,bsd->bts", q, ki))
    scores = jax.lax.fori_loop(0, hi, head, jnp.zeros((b, s, s), F32))
    if control == "random_keys":
        scores = jax.random.uniform(jax.random.PRNGKey(0), scores.shape)
    return _largest(jnp.where(causal, scores, -jnp.inf),
                    hp["index_topk"]) & causal


def _attention(g, lp, hp):
    b, s, _ = g.shape
    nh, eps = hp["num_attention_heads"], hp["rms_norm_eps"]
    dn, dr, dv = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"], \
        hp["v_head_dim"]
    r = hp["kv_lora_rank"]
    cq = _rms_norm(_matmul(g, lp["wq_a"]), lp["q_a_norm"].astype(F32), eps)
    ckv = _matmul(g, lp["wkv_a"])
    lat = _rms_norm(ckv[..., :r], lp["kv_a_norm"].astype(F32), eps)
    k_rope = _rotary_half(ckv[..., r:], hp)           # one, for every head
    causal = jnp.tril(jnp.ones((s, s), bool))[None]
    mask = _selection(g, cq, lp, hp, causal)
    scale = (dn + dr) ** -0.5
    if hp.get("control") != "no_mscale":
        scale *= _mscale(hp["yarn_factor"], hp["mscale_all_dim"]) ** 2
    wq_b = lp["wq_b"].reshape(-1, nh, dn + dr)
    wkv_b = lp["wkv_b"].reshape(r, nh, dn + dv)
    gate = jax.nn.sigmoid(g @ lp["wg"].astype(F32)) \
        if hp["attention_output_gate"] else jnp.ones((b, s, nh), F32)

    def head(j):
        q = cq @ wq_b[:, j].astype(F32)
        kv = lat @ wkv_b[:, j].astype(F32)
        q_nope, q_rope = q[..., :dn], _rotary_half(q[..., dn:], hp)
        att = (jnp.einsum("bqd,bkd->bqk", q_nope, kv[..., :dn])
               + jnp.einsum("bqd,bkd->bqk", q_rope, k_rope)) * scale
        att = jax.nn.softmax(jnp.where(mask, att, -jnp.inf), -1)
        return jnp.einsum("bqk,bkd->bqd", att, kv[..., dn:]) \
            * gate[:, :, j, None]
    out = jax.lax.map(head, jnp.arange(nh))            # (heads, b, s, dv)
    return _matmul(jnp.moveaxis(out, 0, 2).reshape(b, s, nh * dv), lp["wo"])


def _route(h, lp, hp):
    """(b, s, experts) weights of the routed experts: zero but for a
    token's chosen ones."""
    control = hp.get("control")
    scores = jax.nn.sigmoid(h @ lp["w_router"].astype(F32))
    pick = scores if control == "bias_not_in_selection" \
        else scores + lp["router_bias"].astype(F32)
    groups = pick.reshape(pick.shape[:-1] + (hp["n_group"], -1))
    best_two = jnp.sum(jnp.where(_largest(groups, 2), groups, 0.0), -1)
    kept = _largest(best_two, hp["topk_group"])[..., None]
    pick = jnp.where(kept, groups, -jnp.inf).reshape(pick.shape)
    weights = jnp.where(_largest(pick, hp["num_experts_per_tok"]),
                        pick if control == "bias_in_weights" else scores,
                        0.0)
    if hp["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return weights * hp["routed_scaling_factor"]


def _experts(h, lp, layer, gate, up, down, hp):
    """The shared expert, and of each token's chosen experts those held
    here; ``gate``/``up``/``down`` are the held experts of all expert
    layers as stored, ``(layers, held, ...)``."""
    weights = _route(h, lp, hp)
    first = hp["expert_first"]

    def one(e, y):
        g, u, d = (w[layer, e].astype(F32) for w in (gate, up, down))
        out = (jax.nn.silu(h @ g) * (h @ u)) @ d
        w = jax.lax.dynamic_index_in_dim(weights, first + e, -1, False)
        return y + w[..., None] * out
    y = _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return jax.lax.fori_loop(0, hp["experts_held"], one, y)


def _forward(params, ids, hp):
    hp = dict(hp)
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    experts = ("we_gate", "we_up", "we_down")
    stacks = [(params["dense_layers"], True)] \
        if "dense_layers" in params else []
    for layers, dense in stacks + [(params["layers"], False)]:
        for i in range(layers["wq_a"].shape[0]):
            lp = {k: v[i] for k, v in layers.items() if k not in experts}
            x = x + _attention(_gated_norm(x, lp, "attn", hp), lp, hp)
            h2 = _gated_norm(x, lp, "mlp", hp)
            x = x + (_swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
                     if dense else _experts(
                         h2, lp, i, *(layers[k] for k in experts), hp))
    x = _rms_norm(x, params["final_norm"]["scale"].astype(F32),
                  hp["rms_norm_eps"])
    return _matmul(x, params["lm_head"]["w"])


forward, loss, loss_and_grad_norm = make_api(_forward)
