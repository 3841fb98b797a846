"""openPangu-Ultra-MoE-718B's language model (FreedomIntelligence/
openPangu-Ultra-MoE-718B config.json, ``model_type: pangu_ultra_moe``):
sequential pre-RMSNorm blocks with a second RMSNorm on every sublayer's
output ahead of the residual add (``sandwich_norm``); latent (MLA)
attention: queries through a ``q_lora_rank`` bottleneck with its own
RMSNorm to ``num_attention_heads`` heads of ``qk_nope_head_dim +
qk_rope_head_dim``, keys and values from ONE normed latent of
``kv_lora_rank`` a token (``wkv_b`` expands it to each head's
``qk_nope_head_dim`` key and ``v_head_dim`` value) beside one rotated key
of ``qk_rope_head_dim`` that every head shares; leading dense SwiGLU
layers, then layers of routed SwiGLU experts: a sigmoid score an expert,
the ``num_experts_per_tok`` largest, renormalised (``norm_topk_prob``)
and scaled by ``routed_scaling_factor``, beside a shared expert every
token passes; untied head. The multi-token-prediction module
(``num_nextn_predict_layers``) is not run.

A chip of the deployment holds a share of the routed experts
(``expert_first`` .. ``expert_first + experts_held`` of
``n_routed_experts``, both in ``hp``): every token is still routed over
all of them and its weights normalised over all it chose; the sum runs
over the chosen experts this chip holds, what the absent ones would add
is left out, and that partial result goes on to the next layer.

Plain float32, nothing cached, absorbed, batched or tiled: every head's
key and value expanded from the latent, one head at a time (the (heads,
s, s) scores of all 128 would not fit beside the served weights), one
expert at a time, the wide matrices upcast a slice at a time.
"""
import jax
import jax.numpy as jnp

from .common import F32, make_api

#: where this file fills the published description in
departures = {
    "router": "sigmoid of each expert's logit, no group limit, no bias "
              "term: config.json has norm_topk_prob and "
              "routed_scaling_factor, the sigmoid router's keys, and no "
              "n_group / topk_group / scoring_func",
    "sandwich_norm": "RMSNorm on the attention's and on the MLP's output, "
                     "each ahead of its residual add",
    "rotary": "rotate-half over the qk_rope_head_dim dims of q_rope and "
              "of the one shared k_rope at rope_theta, no scaling (no "
              "rope_scaling key)",
    "softmax_scale": "(qk_nope_head_dim + qk_rope_head_dim)^-1/2",
    "nextn": "num_nextn_predict_layers 1: the module is dropped when the "
             "main model serves",
}

#: columns of a wide matrix upcast at a time
_SLICE = 2048


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _rotary_half(x, theta):
    """x: (b, s, d), position = index along s."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]      # (s, d/2)
    sin, cos = jnp.sin(ang)[None], jnp.cos(ang)[None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


def _attention(h, lp, hp):
    b, s, _ = h.shape
    nh, eps, theta = hp["num_attention_heads"], hp["rms_norm_eps"], \
        hp["rope_theta"]
    dn, dr, dv = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"], \
        hp["v_head_dim"]
    r = hp["kv_lora_rank"]
    cq = _rms_norm(_matmul(h, lp["wq_a"]), lp["q_a_norm"].astype(F32), eps)
    ckv = _matmul(h, lp["wkv_a"])
    lat = _rms_norm(ckv[..., :r], lp["kv_a_norm"].astype(F32), eps)
    k_rope = _rotary_half(ckv[..., r:], theta)        # one, for every head
    causal = jnp.tril(jnp.ones((s, s), bool))[None]
    wq_b = lp["wq_b"].reshape(-1, nh, dn + dr)
    wkv_b = lp["wkv_b"].reshape(r, nh, dn + dv)

    def head(j):
        q = cq @ wq_b[:, j].astype(F32)
        kv = lat @ wkv_b[:, j].astype(F32)
        q_nope, q_rope = q[..., :dn], _rotary_half(q[..., dn:], theta)
        att = (jnp.einsum("bqd,bkd->bqk", q_nope, kv[..., :dn])
               + jnp.einsum("bqd,bkd->bqk", q_rope, k_rope)) \
            / jnp.sqrt(F32(dn + dr))
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), -1)
        return jnp.einsum("bqk,bkd->bqd", att, kv[..., dn:])
    out = jax.lax.map(head, jnp.arange(nh))            # (heads, b, s, dv)
    return _matmul(jnp.moveaxis(out, 0, 2).reshape(b, s, nh * dv), lp["wo"])


def _largest(x, k):
    """Mask of the k largest along the last axis, ties to the lower
    index: rank in a stable descending sort."""
    order = jnp.argsort(-x, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < k


def _experts(h, lp, layer, gate, up, down, hp):
    """The shared expert, and of each token's chosen experts those held
    here; ``gate``/``up``/``down`` are the held experts of all expert
    layers as stored, ``(layers, held, ...)``."""
    scores = jax.nn.sigmoid(h @ lp["w_router"].astype(F32))
    weights = jnp.where(_largest(scores, hp["num_experts_per_tok"]),
                        scores, 0.0)
    if hp["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    weights = weights * hp["routed_scaling_factor"]
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
    eps = hp["rms_norm_eps"]
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    experts = ("we_gate", "we_up", "we_down")
    stacks = [(params["dense_layers"], True)] \
        if "dense_layers" in params else []
    for layers, dense in stacks + [(params["layers"], False)]:
        for i in range(layers["wq_a"].shape[0]):
            lp = {k: v[i] for k, v in layers.items() if k not in experts}
            norm = lambda y, name: _rms_norm(  # noqa: E731
                y, lp[name].astype(F32), eps)
            a = _attention(norm(x, "attn_norm"), lp, hp)
            x = x + norm(a, "post_attn_norm")
            h2 = norm(x, "mlp_norm")
            m = _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"]) \
                if dense else _experts(
                    h2, lp, i, *(layers[k] for k in experts), hp)
            x = x + norm(m, "post_mlp_norm")
    x = _rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
    return _matmul(x, params["lm_head"]["w"])


forward, loss, loss_and_grad_norm = make_api(_forward)
