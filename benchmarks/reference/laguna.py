"""Laguna-XS.2's language model (poolside/Laguna-XS.2 config.json,
``model_type: laguna``, 33.4B-A3B): sequential pre-RMSNorm blocks over
grouped-query attention whose layers are of TWO KINDS, by
``layer_types`` in periods of four (full, window, window, window):

- a full layer has ``num_attention_heads`` (48) query heads, rotates the
  first ``partial_rotary_factor`` (half) of each head by a YaRN table
  (``rope_parameters.full_attention``: theta 5e5, factor 64 from 4096
  positions, beta_fast 64, beta_slow 1; cos and sin times
  ``attention_factor``) and attends every earlier key;
- a window layer has ``num_attention_heads_per_layer`` (64) query heads,
  rotates the whole head by plain RoPE at theta 1e4 and attends the
  ``sliding_window`` (512) keys up to and including its own position;

both over ``num_key_value_heads`` (8) of ``head_dim`` 128, each head's
output times a sigmoid gate of the sublayer's normed input (``gating``)
ahead of the output projection. Layer 0's MLP is a dense SwiGLU
(``intermediate_size``); every other layer routes each token to its
``num_experts_per_tok`` (8) best of ``num_experts`` (256) SwiGLU experts
of ``moe_intermediate_size`` by a sigmoid score, renormalised and times
``moe_routed_scaling_factor``, beside one shared expert; untied head.

Plain float32, nothing cached, batched or tiled beyond what memory asks:
one head at a time and a block of queries at a time (the (s, s) scores
of 62k positions would not fit), one expert at a time over every token,
the wide matrices upcast a slice at a time. ``hp["logits_from"]`` (0 if
absent) is the first position whose logits are computed: a long
sequence's (s, vocabulary) logits are 25 GB.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, make_api

#: where this file fills the published description in
departures = {
    "gate": "gating: true is one number a head (an element-wise gate "
            "would add 0.63B parameters to the published 33.4B): "
            "sigmoid(h Wg), Wg (hidden, heads), h the sublayer's normed "
            "input, times the head's attention output ahead of Wo",
    "router": "sigmoid of each expert's logit in float32, the 8 largest, "
              "renormalised, times moe_routed_scaling_factor; no group "
              "limit, no bias term (no scoring_func key; 2.5 with 8 of "
              "256 is the sigmoid-routed family's convention)",
    "rotary": "rotate-half within the rotated prefix; YaRN's correction "
              "range truncated to whole dimensions (the public "
              "implementation's default)",
    "window": "sliding_window 512 counts the query's own position: key "
              "> query - 512",
    "qk_norm": "none: the config has no key for one",
}

#: columns of a wide matrix upcast at a time, queries scored at a time
_SLICE = 2048
_QUERIES = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _matmul(x, w):
    """``x @ w`` in float32 with ``w`` as stored, upcast ``_SLICE``
    columns at a time."""
    n = w.shape[-1]
    if n <= _SLICE:
        return x @ w.astype(F32)
    return jnp.concatenate([x @ w[:, i:i + _SLICE].astype(F32)
                            for i in range(0, n, _SLICE)], -1)


def _head(x, w):
    """``x @ w`` over the vocabulary, ``_SLICE`` columns at a time into
    ONE buffer (a loop's carry is updated in place): 3,083 positions'
    float32 logits over 100,352 are 1.2 GB, and a second copy of them,
    which a concatenation of the slices takes, does not fit beside the
    engine's pools and the check's own cache."""
    n = w.shape[-1]
    if n % _SLICE:
        return _matmul(x, w)

    def one(i, logits):
        cols = jax.lax.dynamic_slice_in_dim(w, i * _SLICE, _SLICE, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            logits, x @ cols.astype(F32), i * _SLICE, axis=-1)
    return jax.lax.fori_loop(0, n // _SLICE, one,
                             jnp.zeros(x.shape[:-1] + (n,), F32))


def _swiglu(h, gate, up, down):
    out = jnp.zeros(h.shape[:-1] + (down.shape[-1],), F32)
    for i in range(0, gate.shape[-1], _SLICE):
        g, u = (w[:, i:i + _SLICE].astype(F32) for w in (gate, up))
        out = out + (jax.nn.silu(h @ g) * (h @ u)) \
            @ down[i:i + _SLICE].astype(F32)
    return out


def _yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """``dim // 2`` inverse frequencies: the plain ones below the pair
    that turns ``beta_fast`` times in ``original`` positions, those over
    ``factor`` above the pair that turns ``beta_slow`` times, a linear
    ramp between (both ends whole dimensions)."""
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


def _rotary(x, inv_freq, scale):
    """Rotate-half over the first ``2 * len(inv_freq)`` numbers of each
    head of ``x (b, s, heads, d)``, position = index along s; cos and
    sin times ``scale``; the rest of the head passes."""
    s, rot = x.shape[1], 2 * len(inv_freq)
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)
    sin = (jnp.sin(ang) * scale)[None, :, None]
    cos = (jnp.cos(ang) * scale)[None, :, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def _attention(h, lp, hp, kind):
    b, s, _ = h.shape
    d, kvh = hp["head_dim"], hp["num_key_value_heads"]
    window = hp["sliding_window"] if kind == "window" else 0
    if kind == "window":
        nh = hp["window_heads"]
        inv = 1.0 / hp["window_rope_theta"] ** (
            np.arange(0, d, 2, dtype=np.float64) / d)
        scale = 1.0
    else:
        nh = hp["num_attention_heads"]
        inv = _yarn_inv_freq(
            int(d * hp["partial_rotary_factor"]), hp["rope_theta"],
            hp["yarn_factor"], hp["yarn_original"], hp["yarn_beta_fast"],
            hp["yarn_beta_slow"])
        scale = hp["yarn_attention_factor"]
    q = _rotary(_matmul(h, lp["wq"]).reshape(b, s, nh, d), inv, scale)
    k = _rotary(_matmul(h, lp["wk"]).reshape(b, s, kvh, d), inv, scale)
    v = _matmul(h, lp["wv"]).reshape(b, s, kvh, d)
    rep = nh // kvh
    blocks = -(-s // _QUERIES)
    pad = blocks * _QUERIES - s
    key_pos = jnp.arange(s)

    def head(j):
        qj = jnp.pad(q[:, :, j], ((0, 0), (0, pad), (0, 0)))
        kj, vj = k[:, :, j // rep], v[:, :, j // rep]

        def block(i):
            qi = jax.lax.dynamic_slice_in_dim(qj, i * _QUERIES, _QUERIES, 1)
            q_pos = i * _QUERIES + jnp.arange(_QUERIES)
            att = jnp.einsum("bqd,bkd->bqk", qi, kj) / jnp.sqrt(F32(d))
            seen = key_pos[None] <= q_pos[:, None]
            if window:
                seen &= key_pos[None] > q_pos[:, None] - window
            att = jax.nn.softmax(jnp.where(seen[None], att, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", att, vj)
        out = jax.lax.map(block, jnp.arange(blocks))   # (blocks, b, Q, d)
        return jnp.moveaxis(out, 0, 1).reshape(b, -1, d)[:, :s]
    out = jnp.moveaxis(jax.lax.map(head, jnp.arange(nh)), 0, 2)
    if hp["gating"]:
        out = out * jax.nn.sigmoid(h @ lp["wg"].astype(F32))[..., None]
    return _matmul(out.reshape(b, s, nh * d), lp["wo"])


def _largest(x, k):
    """Mask of the k largest along the last axis, ties to the lower
    index: rank in a stable descending sort."""
    order = jnp.argsort(-x, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < k


def _experts(h, lp, layer, gate, up, down, hp):
    """The shared expert and each token's chosen experts; ``gate`` /
    ``up`` / ``down`` are the experts of all of a stack's layers as
    stored, ``(layers, experts, ...)``."""
    scores = jax.nn.sigmoid(h @ lp["w_router"].astype(F32))
    weights = jnp.where(_largest(scores, hp["num_experts_per_tok"]),
                        scores, 0.0)
    weights = weights / jnp.sum(weights, -1, keepdims=True) \
        * hp["moe_routed_scaling_factor"]

    def one(e, y):
        g, u, d = (w[layer, e].astype(F32) for w in (gate, up, down))
        out = (jax.nn.silu(h @ g) * (h @ u)) @ d
        w = jax.lax.dynamic_index_in_dim(weights, e, -1, False)
        return y + w[..., None] * out
    y = _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return jax.lax.fori_loop(0, gate.shape[1], one, y)


def _forward(params, ids, hp):
    hp = dict(hp)
    eps = hp["rms_norm_eps"]
    pattern = hp["layer_pattern"].split()
    stacks = {"full": params.get("layers"),
              "window": params.get("window_layers")}
    n_dense = params["dense_layers"]["wq"].shape[0] \
        if "dense_layers" in params else 0
    depth = n_dense + sum(st["wq"].shape[0] for st in stacks.values()
                          if st is not None)
    experts = ("we_gate", "we_up", "we_down")
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    at = {"full": 0, "window": 0}
    for l in range(depth):
        kind = pattern[l % len(pattern)]
        dense = l < n_dense
        stack, i = (params["dense_layers"], l) if dense \
            else (stacks[kind], at[kind])
        lp = {k: v[i] for k, v in stack.items() if k not in experts}
        a = _attention(_rms_norm(x, lp["attn_norm"].astype(F32), eps),
                       lp, hp, kind)
        x = x + a
        h2 = _rms_norm(x, lp["mlp_norm"].astype(F32), eps)
        if dense:
            x = x + _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
            x = x + _experts(h2, lp, i, *(stack[k] for k in experts), hp)
            at[kind] += 1
    x = _rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
    return _head(x[:, int(hp.get("logits_from", 0)):],
                 params["lm_head"]["w"])


forward, loss, loss_and_grad_norm = make_api(_forward)
