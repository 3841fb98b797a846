"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye/Keye-VL-2.0-30B-A3B
config.json, the text keys and ``sa_config``): sequential pre-RMSNorm
blocks; grouped-query attention with an RMSNorm over ``head_dim`` on
each head of q and k before rotate-half rotary over the whole head; a
DeepSeek-Sparse-Attention "lightning indexer" in every layer
(``indexer_num_heads`` query heads of ``indexer_head_dim`` over one
shared key head, relu, a learned weight a head) whose ``topk`` highest
keys are the only ones a query attends; ``num_experts`` SwiGLU experts
of ``moe_intermediate_size``, each token to its ``num_experts_per_tok``
best by a float32 softmax over all of them, the weights renormalised
(``norm_topk_prob``), no token dropped, no shared expert; untied head.
Text only: with ``mrope_section`` every position id of a text token is
its index, which is ordinary rotary; the vision tower is no part of
this.

Plain float32, nothing cached, batched or tiled: the indexer's full
causal score matrix, selection by a stable descending sort as a mask, a
loop over the experts each applied to every token. At the published
widths it has to fit beside the served weights: the experts are sliced
out of the stored stack and upcast one at a time, attention runs one
key/value head at a time, and the head's upcast is left to fuse into its
product.
"""
import jax
import jax.numpy as jnp

from .common import F32, make_api

#: where this file leaves the published description, or fills it in
departures = {
    "qk_norm": "config.json has no key for it; RMSNorm over head_dim with "
               "a learned weight on each head of q and k, before rotary, "
               "is what every model of the Qwen3-MoE lineage (whose widths "
               "these are) does",
    "indexer_k_norm": "LayerNorm (scale and bias, eps 1e-6) on the "
                      "indexer's key, DeepSeek-V3.2's convention",
    "indexer_rotary": "rotate-half over all indexer_head_dim dims of qI "
                      "and kI at rope_theta",
    "indexer_weights": "w = h . WwI times indexer_num_heads^-1/2 * "
                       "indexer_head_dim^-1/2",
    "indexer_query": "qI is projected from the normed hidden state (the "
                     "model has no query LoRA to take it from)",
    "chunk_sizes": "q_chunk_size / kv_chunk_size 512 are read as the "
                   "tiling scores are computed in, with no effect on the "
                   "result: selection is by token, topk of each query",
    "mrope": "text only: the three position ids of a token are equal, so "
             "mrope_section [16, 24, 24] is ordinary rotary",
}

def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rotary_half(x, theta):
    """x: (b, s, heads, d), position = index along s."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]      # (s, d/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _largest(x, k):
    """Mask of the k largest along the last axis, ties to the lower
    index: rank in a stable descending sort."""
    order = jnp.argsort(-x, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < k


def select_keys(scores, causal, topk):
    """(b, t, s) indexer scores -> the mask of keys each query attends:
    the ``topk`` highest of those it may see (all while it sees fewer)."""
    return _largest(jnp.where(causal, scores, -jnp.inf), topk) & causal


def _attention(h, lp, hp):
    b, s, _ = h.shape
    nh, nkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]
    q = (h @ lp["wq"]).reshape(b, s, nh, -1)
    k = (h @ lp["wk"]).reshape(b, s, nkv, -1)
    v = (h @ lp["wv"]).reshape(b, s, nkv, -1)
    q = _rotary_half(_rms_norm(q, lp["q_norm"], eps), theta)
    k = _rotary_half(_rms_norm(k, lp["k_norm"], eps), theta)
    # the indexer: which keys each query attends
    hi, di = hp["indexer_num_heads"], hp["indexer_head_dim"]
    qi = _rotary_half((h @ lp["wq_idx"]).reshape(b, s, hi, di), theta)
    ki = _layer_norm(h @ lp["wk_idx"], lp["k_idx_scale"], lp["k_idx_bias"],
                     hp["indexer_layer_norm_eps"])
    ki = _rotary_half(ki[:, :, None], theta)[:, :, 0]
    w = (h @ lp["ww_idx"]) * (hi ** -0.5 * di ** -0.5)
    def head(j, total):                    # one indexer head at a time
        return total + w[:, :, j, None] * jax.nn.relu(
            jnp.einsum("btd,bsd->bts", qi[:, :, j], ki))
    scores = jax.lax.fori_loop(0, hi, head, jnp.zeros((b, s, s), F32))
    causal = jnp.tril(jnp.ones((s, s), bool))[None]
    mask = select_keys(scores, causal, hp["topk"])
    # one key/value head and the query heads that share it at a time
    # (the (heads, s, s) matrices of all 32 would not fit beside the
    # served weights)
    def group(qkv):
        qg, kg, vg = qkv                    # (b, s, rep, d), (b, s, d) x 2
        att = jnp.einsum("bqrd,bkd->brqk", qg, kg) \
            / jnp.sqrt(F32(qg.shape[-1]))
        att = jax.nn.softmax(jnp.where(mask[:, None], att, -jnp.inf), -1)
        return jnp.einsum("brqk,bkd->bqrd", att, vg)
    q = q.reshape(b, s, nkv, nh // nkv, -1)
    out = jax.lax.map(group, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                              jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, -1) @ lp["wo"]


def _experts(h, router, layer, gate, up, down, hp):
    """Every token to its best experts; ``gate``/``up``/``down`` are the
    experts of all layers as stored, ``(layers, experts, ...)``: one
    expert of layer ``layer`` is sliced out and upcast at a time."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    weights = jnp.where(_largest(probs, hp["num_experts_per_tok"]),
                        probs, 0.0)
    if hp["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)

    def one(e, y):
        g, u, d = (w[layer, e].astype(F32) for w in (gate, up, down))
        out = (jax.nn.silu(h @ g) * (h @ u)) @ d
        return y + weights[..., e][..., None] * out
    return jax.lax.fori_loop(0, router.shape[-1], one, jnp.zeros_like(h))


def _forward(params, ids, hp):
    hp = dict(hp)
    eps = hp["rms_norm_eps"]
    f = lambda a: a.astype(F32)  # noqa: E731
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    layers = params["layers"]
    experts = ("we_gate", "we_up", "we_down")
    for i in range(layers["wq"].shape[0]):
        lp = {k: f(v[i]) for k, v in layers.items() if k not in experts}
        x = x + _attention(_rms_norm(x, lp["attn_norm"], eps), lp, hp)
        x = x + _experts(_rms_norm(x, lp["mlp_norm"], eps), lp["w_router"],
                         i, *(layers[k] for k in experts), hp)
    x = _rms_norm(x, f(params["final_norm"]["scale"]), eps)
    return x @ f(params["lm_head"]["w"])


forward, loss, loss_and_grad_norm = make_api(_forward)
