"""Mellum 2's language model (JetBrains/Mellum2-12B-A2.5B-Instruct
config.json, ``model_type: mellum``, 12B-A2.5B): sequential pre-RMSNorm
blocks over grouped-query attention (32 query heads on 4 key/value heads
of 128, no bias, no QK-norm) whose layers are of TWO KINDS, by
``layer_types`` in periods of four (window, window, window, full):

- a window layer rotates the whole head by plain RoPE at theta 5e5
  (``rope_parameters.sliding_attention``) and attends the
  ``sliding_window`` (1024) keys up to and including its own position;
- a full layer rotates the whole head by a YaRN table
  (``rope_parameters.full_attention``: theta 5e5, factor 16 from 8192
  positions, beta_fast 32, beta_slow 1; cos and sin times
  ``attention_factor``) and attends every earlier key;

and whose every MLP routes each token to its ``num_experts_per_tok`` (8)
best of ``num_experts`` (64) SwiGLU experts of ``moe_intermediate_size``
(896) by a softmax over all 64, the eight probabilities renormalised
(``norm_topk_prob``); untied head. With ``u`` the residual stream:

    h = rmsnorm(u) g_attn;  q, k, v = h Wq, h Wk, h Wv;  q, k rotated
    u = u + softmax(q k^T / sqrt(128), masked by kind) v Wo
    h = rmsnorm(u) g_mlp;   p = softmax(h Wr);  T = the 8 largest
    u = u + sum_{e in T, e held} p_e / sum_T p * (silu(h Wg_e) * h Wu_e) Wd_e

A program may hold a SHARE of the experts (``hp["experts_held"]`` from
``hp["expert_first"]``; the stacks handed over then carry that many):
every token is still routed over all 64, and the sum runs over the held
ones alone. The vocabulary slice needs no word here: the embedding and
the head handed over have the slice's rows and the ids lie inside it.

Plain float32. Shaped to run beside a training state: ``jax.checkpoint``
a layer, attention one sequence's key/value head (its group of query
heads) at a time, the held experts one at a time over every token in a ``fori_loop``. It
imports nothing of the program and nothing of another reference.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, make_api

#: ``hp["control"]``: what a benchmark's controls ask for beside the
#: published keys (``hp["norm_topk_prob"]`` False, another window, top-k,
#: ``yarn_factor`` 0): a reference that is wrong in one named way
CONTROLS = ("router_gradient_stopped",)

#: where this file fills the published description in
departures = {
    "qk_norm": "none, and no bias: the config has no key for a QK-norm "
               "and attention_bias is false",
    "rotary": "rotate-half over all of head_dim; YaRN's correction range "
              "truncated to whole dimensions (the public "
              "implementation's default)",
    "window": "sliding_window counts the query's own position: key > "
              "query - 1024",
    "router": "softmax in float32 over all experts ahead of the top-k, "
              "renormalised, no scale, no bias; no balancing term in the "
              "loss",
    "mtp": "the multi-token-prediction head has no key in the config "
           "and is not built",
}


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """YaRN's ``dim // 2`` inverse frequencies (float64 numpy): per
    frequency a blend of ``f`` and ``f / factor`` by a linear ramp over
    the pair index between the correction dimensions of ``beta_fast``
    and ``beta_slow`` turns in ``original`` positions, both truncated."""
    freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def _rotary(kind, s, hp):
    """(cos, sin) ``[s, head_dim / 2]`` of a kind of layer."""
    d = hp["head_dim"]
    if kind == "full" and hp.get("yarn_factor"):
        inv = yarn_inv_freq(d, hp["rope_theta"], hp["yarn_factor"],
                            hp["yarn_original"], hp["yarn_beta_fast"],
                            hp["yarn_beta_slow"])
        scale = hp["yarn_attention_factor"]
    else:
        theta = hp["window_rope_theta"] if kind == "window" \
            else hp["rope_theta"]
        inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
        scale = 1.0
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rotate(x, cos, sin):
    """Rotate-half over the whole head. x: (b, s, heads, d)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def window_mask(s, window):
    """(s, s) bool: key <= query, and with a window key > query -
    window."""
    q = jnp.arange(s)[:, None]
    k = jnp.arange(s)[None, :]
    keep = k <= q
    if window:
        keep &= k > q - window
    return keep


def attention(h, lp, kind, hp):
    """One attention sublayer on its normed input ``h`` (b, s, e)."""
    b, s, _ = h.shape
    H, KV, d = (hp["num_attention_heads"], hp["num_key_value_heads"],
                hp["head_dim"])
    q = (h @ lp["wq"].astype(F32)).reshape(b, s, H, d)
    k = (h @ lp["wk"].astype(F32)).reshape(b, s, KV, d)
    v = (h @ lp["wv"].astype(F32)).reshape(b, s, KV, d)
    cos, sin = _rotary(kind, s, hp)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    mask = window_mask(s, hp["sliding_window"] if kind == "window" else 0)
    # query head j reads key/value head j // (H / KV)
    # one (sequence, key/value head) at a time: the scores of a group are
    # (r, s, s) float32, 0.5 GB at 4096 tokens
    qg = jnp.moveaxis(q.reshape(b, s, KV, H // KV, d), 2, 1)
    qg = qg.reshape(b * KV, s, H // KV, d)
    kg = jnp.moveaxis(k, 2, 1).reshape(b * KV, s, d)
    vg = jnp.moveaxis(v, 2, 1).reshape(b * KV, s, d)

    @jax.checkpoint
    def group(args):
        qs, ks, vs = args            # (s, r, d), (s, d), (s, d)
        sc = jnp.einsum("qrd,kd->rqk", qs, ks) / math.sqrt(d)
        sc = jnp.where(mask[None], sc, -jnp.inf)
        return jnp.einsum("rqk,kd->qrd", jax.nn.softmax(sc, -1), vs)
    out = jax.lax.map(group, (qg, kg, vg))          # (b * KV, s, r, d)
    out = jnp.moveaxis(out.reshape(b, KV, s, H // KV, d), 1, 2)
    out = out.reshape(b, s, H * d)
    return out @ lp["wo"].astype(F32)


def experts(h, lp, hp):
    """The held experts' part of the routed sum on normed input ``h``
    (b, s, e)."""
    b, s, e = h.shape
    x = h.reshape(b * s, e)
    k = hp["num_experts_per_tok"]
    p = jax.nn.softmax(x @ lp["w_router"].astype(F32), -1)     # all experts
    top, idx = jax.lax.top_k(p, k)
    w = top / jnp.sum(top, -1, keepdims=True) \
        if hp.get("norm_topk_prob", True) else top
    if hp.get("control") == "router_gradient_stopped":
        w = jax.lax.stop_gradient(w)
    # each expert's weight a token: w where chosen, else 0
    weight = jnp.zeros_like(p).at[
        jnp.arange(x.shape[0])[:, None], idx].add(w)
    held = lp["we_up"].shape[0]
    first = hp.get("expert_first", 0)

    @jax.checkpoint
    def one(i, acc):
        gate = jax.nn.silu(x @ lp["we_gate"][i].astype(F32))
        up = x @ lp["we_up"][i].astype(F32)
        y = (gate * up) @ lp["we_down"][i].astype(F32)
        return acc + y * jax.lax.dynamic_slice_in_dim(
            weight, first + i, 1, axis=1)
    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    return y.reshape(b, s, e)


def _forward(params, ids, hp):
    hp = dict(hp)
    eps = hp["rms_norm_eps"]
    pattern = hp["layer_pattern"].split()
    stacks = {"full": params["layers"],
              "window": params.get("window_layers")}
    depth = sum(next(iter(st.values())).shape[0]
                for st in stacks.values() if st)
    x = params["embed"].astype(F32)[ids]
    seen = {"full": 0, "window": 0}

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def layer(x, lp, kind):
        x = x + attention(_rms_norm(x, lp["attn_norm"], eps), lp, kind, hp)
        return x + experts(_rms_norm(x, lp["mlp_norm"], eps), lp, hp)
    for l in range(depth):
        kind = pattern[l % len(pattern)]
        i = seen[kind]
        seen[kind] += 1
        lp = {name: leaf[i] for name, leaf in stacks[kind].items()}
        x = layer(x, lp, kind)
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    return x @ params["lm_head"]["w"].astype(F32)


forward, loss, loss_and_grad_norm = make_api(_forward)
