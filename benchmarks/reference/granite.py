"""Granite 4.0-H's language model (ibm-granite/granite-4.0-h-small
config.json, ``model_type: granitemoehybrid``, 32B-A9B): sequential
pre-RMSNorm blocks whose two sublayer outputs are each multiplied by
``residual_multiplier`` ahead of their residual::

    x = x + r * mixer(RMSNorm_1(x))
    h = RMSNorm_2(x);  x = x + r * (routed(h) + shared(h))

over an embedding times ``embedding_multiplier``, under a final RMSNorm
and a head that IS the embedding (``tie_word_embeddings``), its logits
divided by ``logits_scaling``.

``layer_types`` says which mixer a layer has. ``"attention"``: grouped
query attention (``num_key_value_heads`` under ``num_attention_heads``),
no bias, NO position embedding (``position_embedding_type: nope``),
causal softmax of ``q k^T * attention_multiplier``. ``"mamba"``: a
Mamba-2 mixer (Dao & Gu 2024) of ``mamba_n_heads`` heads of
``mamba_d_head`` over a state of ``mamba_d_state``, one group of B and
C, no projection bias::

    [z | xBC | dt] = u W_in
    xBC_t = silu(b + sum_j w[:, j] xBC_raw[t - (K-1) + j])   zeros before 0
    [x | B | C] = xBC
    dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t          H_{-1} = 0
    y_t = H_t C_t + D x_t
    out = RMSNorm(y * silu(z)) W_out       one norm over all channels

The recurrence runs token by token (``lax.scan`` over positions, one
state a head): ``mamba_chunk_size`` is how the published kernels block
it and changes no result, so this file has no block.

The experts (GraniteMoe): router logits ``h W_r`` in float32, the
``num_experts_per_tok`` largest, weights the softmax over THOSE logits;
SwiGLU experts; a shared SwiGLU expert every token passes. A chip of the
deployment holds a share of the routed experts (``expert_first`` ..
``expert_first + experts_held``, both in ``hp``): every token is routed
over all of them and its weights are the softmax over all it chose; the
sum runs over the chosen experts held here.

Plain float32, nothing cached, blocked or batched; the wide matrices are
upcast a layer, an expert or a slice at a time. ``hp["logits_from"]`` (0
if absent): the head is applied from that position on. ``hp["control"]``
(absent in every configuration's file) names ONE deliberate fault, for
the checks that a limit refuses it: see ``CONTROLS``. Imports nothing of
the program.
"""
import jax
import jax.numpy as jnp

from .common import F32, make_api

#: deliberate faults, each one published term left out or replaced
CONTROLS = (
    "no_embedding_multiplier",   # x = E[ids]
    "no_residual_multiplier",    # r = 1
    "no_logits_scaling",         # logits not divided
    "rotary",                    # rotate-half RoPE at rope_theta 10000 on q, k
    "sqrt_scale",                # softmax scale head_dim^-1/2, not 1/128
    "no_conv_bias",              # the convolution without its bias
    "no_skip",                   # y without D x
    "ungated_norm",              # RMSNorm(y) * silu(z): the gate outside
    "softmax_over_all",          # weights a softmax over ALL experts, cut
)                                # to the chosen and NOT renormalised

_SLICE = 8192


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _matmul(x, w):
    """``x @ w`` with ``w`` as stored, upcast ``_SLICE`` columns at a
    time."""
    n = w.shape[-1]
    return jnp.concatenate([x @ w[:, i:i + _SLICE].astype(F32)
                            for i in range(0, n, _SLICE)], -1)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
        @ down.astype(F32)


def _rotary_half(x, theta=10000.0):
    """The ``rotary`` control's: what this model does NOT do."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, lp, hp):
    b, s, _ = h.shape
    nh, nkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    control = hp.get("control")
    q = _matmul(h, lp["wq"]).reshape(b, s, nkv, nh // nkv, -1)
    k = _matmul(h, lp["wk"]).reshape(b, s, nkv, -1)
    v = _matmul(h, lp["wv"]).reshape(b, s, nkv, -1)
    if control == "rotary":
        q = _rotary_half(q.reshape(b, s, nh, -1)).reshape(q.shape)
        k = _rotary_half(k)
    scale = hp["attention_multiplier"] if control != "sqrt_scale" \
        else q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(j):              # the query heads that share kv head j
        scores = jnp.einsum("bqgd,bkd->bgqk", q[:, :, j], k[:, :, j]) * scale
        att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("bgqk,bkd->bqgd", att, v[:, :, j])
    out = jax.lax.map(group, jnp.arange(nkv))          # (nkv, b, s, g, d)
    return _matmul(jnp.moveaxis(out, 0, 2).reshape(b, s, -1), lp["wo"])


def _mamba(u, lp, hp):
    b, s, _ = u.shape
    nh, dh, n = hp["mamba_n_heads"], hp["mamba_d_head"], hp["mamba_d_state"]
    k, control = hp["mamba_d_conv"], hp.get("control")
    di = nh * dh
    proj = _matmul(u, lp["w_in"])
    z, raw, dt = proj[..., :di], proj[..., di:di + di + 2 * n], \
        proj[..., di + di + 2 * n:]
    # depthwise, causal: tap j of w meets the input K-1-j positions back
    ext = jnp.pad(raw, ((0, 0), (k - 1, 0), (0, 0)))
    w = lp["conv_w"].astype(F32)
    acc = sum(ext[:, j:j + s] * w[:, j] for j in range(k))
    if control != "no_conv_bias":
        acc = acc + lp["conv_b"].astype(F32)
    xbc = jax.nn.silu(acc)
    x = xbc[..., :di].reshape(b, s, nh, dh)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))       # (b, s, nh)
    a = -jnp.exp(lp["A_log"].astype(F32))

    def token(state, t):
        x_t, b_t, c_t, dt_t = t
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)
    _, y = jax.lax.scan(
        token, jnp.zeros((b, nh, dh, n), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1)                                  # (b, s, nh, dh)
    if control != "no_skip":
        y = y + lp["D"].astype(F32)[:, None] * x
    y, gate = y.reshape(b, s, di), jax.nn.silu(z)
    if control == "ungated_norm":
        y = _rms_norm(y, lp["ssm_norm"], hp["rms_norm_eps"]) * gate
    else:
        y = _rms_norm(y * gate, lp["ssm_norm"], hp["rms_norm_eps"])
    return _matmul(y, lp["w_out"])


def _route(h, lp, hp):
    """(b, s, experts) weights of the routed experts: zero but for a
    token's chosen ones, over which they are a softmax of the logits."""
    logits = h @ lp["w_router"].astype(F32)
    k = hp["num_experts_per_tok"]
    # the k largest, ties to the lower index (a stable descending sort)
    order = jnp.argsort(-logits, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    chosen = rank < k
    if hp.get("control") == "softmax_over_all":
        return jnp.where(chosen, jax.nn.softmax(logits, -1), 0.0)
    return jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), -1)


def _experts(h, lp, layer, gate, up, down, hp):
    """The shared expert, and of each token's chosen experts those held
    here; ``gate``/``up``/``down`` are the held experts of a whole stack
    as stored, ``(layers, held, ...)``."""
    weights = _route(h, lp, hp)
    first = hp["expert_first"]

    def one(e, y):
        out = _swiglu(h, gate[layer, e], up[layer, e], down[layer, e])
        w = jax.lax.dynamic_index_in_dim(weights, first + e, -1, False)
        return y + w[..., None] * out
    y = _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return jax.lax.fori_loop(0, hp["experts_held"], one, y)


#: where the program's tree keeps each kind of layer, and its mixer
_STACKS = {"mamba": ("mamba_layers", _mamba),
           "attention": ("layers", _attention)}


def _forward(params, ids, hp):
    hp = dict(hp)
    control = hp.get("control")
    eps = hp["rms_norm_eps"]
    r = 1.0 if control == "no_residual_multiplier" \
        else hp["residual_multiplier"]
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    if control != "no_embedding_multiplier":
        x = x * hp["embedding_multiplier"]
    experts = ("we_gate", "we_up", "we_down")
    seen = dict.fromkeys(_STACKS, 0)       # layers of each kind so far
    pattern = hp["layer_types"].split(",")
    depth = sum(params[stack]["attn_norm"].shape[0]
                for stack, _ in _STACKS.values() if stack in params)
    for l in range(depth):
        kind = pattern[l % len(pattern)]
        stack, mixer = _STACKS[kind]
        i, seen[kind] = seen[kind], seen[kind] + 1
        layers = params[stack]
        lp = {k: v[i] for k, v in layers.items() if k not in experts}
        x = x + r * mixer(_rms_norm(x, lp["attn_norm"], eps), lp, hp)
        h = _rms_norm(x, lp["mlp_norm"], eps)
        x = x + r * _experts(h, lp, i, *(layers[k] for k in experts), hp)
    x = _rms_norm(x[:, int(hp.get("logits_from", 0)):],
                  params["final_norm"]["scale"], eps)
    logits = jnp.concatenate(
        [x @ params["embed"][i:i + _SLICE].astype(F32).T
         for i in range(0, params["embed"].shape[0], _SLICE)], -1)
    return logits if control == "no_logits_scaling" \
        else logits / hp["logits_scaling"]


forward, loss, loss_and_grad_norm = make_api(_forward)
