"""Olmo-Hybrid's language model (allenai/Olmo-Hybrid-7B config.json,
``model_type: olmo_hybrid``, 7B): sequential blocks with NO norm ahead of
a sublayer and one RMSNorm on its output, ahead of the residual (the
Olmo 2 / Olmo 3 block)::

    x = x + RMSNorm_attn_out(mixer(x))
    x = x + RMSNorm_mlp_out((silu(x W_gate) * (x W_up)) W_down)

under a final RMSNorm and an untied head. ``layer_types`` says which
mixer a layer has.

``"full_attention"``: q, k, v without bias; RMSNorm with a learned
weight over ALL projected channels of q and of k, before the heads are
split; NO position embedding (``rope_theta`` is null); causal softmax of
``q k^T / sqrt(head_dim)``; ``wo``.

``"linear_attention"``: Gated DeltaNet (Yang, Kautz & Hatamizadeh 2024,
arXiv 2412.06464). A head h of ``linear_num_value_heads``, a state ``S``
of ``linear_key_head_dim x linear_value_head_dim``, ``S_{-1} = 0``::

    [q | k | v]_raw = x W_qkv
    [q | k | v]_t = silu(sum_j w[:, j] raw[t - (K-1) + j])   zeros before 0
    q = q / |q| / sqrt(dk);  k = k / |k|          a head, eps under the root
    beta_t = 2 sigmoid(x_t W_b)        the 2 is linear_allow_neg_eigval
    g_t = -exp(A_log) softplus(x_t W_a + dt_bias);  alpha_t = exp(g_t)
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t
    out = concat_h(RMSNorm_dv(o_t) * silu(x_t W_g)) W_out

The recurrence runs token by token (``lax.scan`` over positions, 512
at a time behind a state and a window carried on; the convolution's
window carried with the state): the published blocked
form (WY transform, block 64) changes no result, so this file has no
block, no triangular solve and no state handed between calls. ``w_ab``'s
columns are ``[a | b]``, a head each.

Plain float32, nothing cached, blocked or batched; the wide matrices are
upcast a layer or a slice at a time, the attention runs a head at a time
and what is wide and position-wise (the MLP, the mixer's projections)
512 positions at a time: at the published widths and the benchmark's
sample this runs beside a deployment's weights and cache. ``hp["logits_from"]`` (0 if absent): the head is applied from that
position on. ``hp["control"]`` (absent in every configuration's file)
names ONE deliberate fault, for the checks that a limit refuses it: see
``CONTROLS``. Imports nothing of the program.
"""
import jax
import jax.numpy as jnp

from .common import F32, make_api

#: deliberate faults, each one term of the description left out or
#: replaced by what a neighbouring model does
CONTROLS = (
    "no_attn_out_norm",      # x = x + mixer(x)
    "no_mlp_out_norm",       # x = x + mlp(x)
    "pre_norm",              # the two norms AHEAD of the sublayers
    "head_qk_norm",          # q and k normed a head (the same weights)
    "no_qk_norm",            # q and k not normed
    "rotary",                # rotate-half RoPE at theta 10000 on q, k
    "beta_1",                # beta = sigmoid: no factor 2
    "no_q_l2",               # q not brought to unit length
    "no_k_l2",               # k not brought to unit length
    "no_decay",              # alpha = 1
    "no_delta",              # S_t = alpha S + beta k v^T: nothing read back
    "ungated_out",           # RMSNorm(o) without the silu gate
)

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


def _head(x, w):
    """The logits ``x @ w``, written a slice of the vocabulary at a time
    into the one array that holds them, in a loop (a concatenation of the
    slices, or the same updates unrolled, holds them twice on a TPU: 1.2
    GB each at the published vocabulary and the benchmark's sample). The
    slices are the widest equal ones of at most ``_SLICE`` columns."""
    v = w.shape[-1]
    width = max(d for d in range(1, min(v, _SLICE) + 1) if v % d == 0)

    def one(i, out):
        part = x @ jax.lax.dynamic_slice_in_dim(
            w, i * width, width, axis=1).astype(F32)
        return jax.lax.dynamic_update_slice_in_dim(out, part, i * width,
                                                   axis=-1)
    return jax.lax.fori_loop(0, v // width, one,
                             jnp.zeros(x.shape[:-1] + (v,), F32))


def _rotary_half(x, theta=10000.0):
    """The ``rotary`` control's: what this model does NOT do."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, lp, hp):
    b, s, _ = x.shape
    nh, nkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    eps, control = hp["rms_norm_eps"], hp.get("control")
    q, k, v = (_matmul(x, lp[w]) for w in ("wq", "wk", "wv"))
    if control == "head_qk_norm":
        q = _rms_norm(q.reshape(b, s, nh, -1),
                      lp["q_norm"].reshape(nh, -1), eps)
        k = _rms_norm(k.reshape(b, s, nkv, -1),
                      lp["k_norm"].reshape(nkv, -1), eps)
    elif control != "no_qk_norm":
        q = _rms_norm(q, lp["q_norm"], eps)
        k = _rms_norm(k, lp["k_norm"], eps)
    q = q.reshape(b, s, nh, -1)
    k, v = k.reshape(b, s, nkv, -1), v.reshape(b, s, nkv, -1)
    if control == "rotary":
        q, k = _rotary_half(q), _rotary_half(k)
    scale = q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(j):                 # query head j reads kv head j // group
        kj = jax.lax.dynamic_index_in_dim(k, j // (nh // nkv), 2, False)
        vj = jax.lax.dynamic_index_in_dim(v, j // (nh // nkv), 2, False)
        qj = jax.lax.dynamic_index_in_dim(q, j, 2, False)
        scores = jnp.einsum("bqd,bkd->bqk", qj, kj) * scale
        att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("bqk,bkd->bqd", att, vj)
    out = jax.lax.map(head, jnp.arange(nh))                # (nh, b, s, d)
    return _matmul(jnp.moveaxis(out, 0, 2).reshape(b, s, -1), lp["wo"])


def _unit(x, eps=1e-6):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def _by_rows(fn, x, rows=512):
    """``fn`` of ``x (b, s, e)``, position by position: ``rows``
    positions at a time, so that a wide intermediate is never held for
    the whole sequence."""
    b, s, e = x.shape
    pad = -s % rows
    parts = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(b, -1, rows, e)
    out = jax.lax.map(fn, jnp.moveaxis(parts, 1, 0))   # (n, b, rows, e')
    return jnp.moveaxis(out, 0, 1).reshape(b, s + pad, -1)[:, :s]


def _delta(x, lp, hp, rows=512):
    b, s, e = x.shape
    nh, dk, dv = hp["linear_num_value_heads"], hp["linear_key_head_dim"], \
        hp["linear_value_head_dim"]
    assert hp["linear_num_key_heads"] == nh
    kk, control = hp["linear_conv_kernel_dim"], hp.get("control")
    w = lp["conv_w"].astype(F32)

    def token(carry, t):
        S, seen_raw = carry      # S (b, nh, dk, dv); the K-1 inputs before
        raw_t, a_t, b_t = t
        # depthwise, causal, no bias: tap j of w meets the input K-1-j
        # back; zeros before position 0
        window = jnp.concatenate([seen_raw, raw_t[:, None]], 1)
        qkv = jax.nn.silu(sum(window[:, j] * w[:, j] for j in range(kk)))
        q = qkv[:, :nh * dk].reshape(b, nh, dk)
        k = qkv[:, nh * dk:2 * nh * dk].reshape(b, nh, dk)
        v = qkv[:, 2 * nh * dk:].reshape(b, nh, dv)
        if control != "no_q_l2":
            q = _unit(q)
        if control != "no_k_l2":
            k = _unit(k)
        q = q / jnp.sqrt(F32(dk))
        S = a_t[..., None, None] * S
        seen = 0.0 if control == "no_delta" \
            else jnp.einsum("bhkv,bhk->bhv", S, k)
        S = S + b_t[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", k, v - seen)
        return (S, window[:, 1:]), jnp.einsum("bhkv,bhk->bhv", S, q)

    def some(carry, xc):
        """``rows`` positions ``xc (b, rows, e)`` of the sequence behind
        the state and the window carried in: projections, the
        recurrence token by token, the gated norm, the way out."""
        n = xc.shape[1]
        raw = _matmul(xc, lp["w_qkv"])
        ab = xc @ lp["w_ab"].astype(F32)
        beta = jax.nn.sigmoid(ab[..., nh:])
        if hp["linear_allow_neg_eigval"] and control != "beta_1":
            beta = 2.0 * beta
        g = -jnp.exp(lp["A_log"].astype(F32)) \
            * jax.nn.softplus(ab[..., :nh] + lp["dt_bias"].astype(F32))
        alpha = jnp.ones_like(g) if control == "no_decay" else jnp.exp(g)
        carry, o = jax.lax.scan(
            token, carry, tuple(jnp.moveaxis(t, 1, 0)
                                for t in (raw, alpha, beta)))
        o = _rms_norm(jnp.moveaxis(o, 0, 1), lp["delta_norm"],
                      hp["rms_norm_eps"])                  # (b, n, nh, dv)
        if control != "ungated_out":
            o = o * jax.nn.silu(_matmul(xc, lp["w_g"])).reshape(
                b, n, nh, dv)
        return carry, _matmul(o.reshape(b, n, nh * dv), lp["w_out"])
    # ``rows`` positions at a time, so that nothing as wide as the
    # projections is held for the whole sequence; the zeros that fill the
    # last stretch come behind every position and are dropped
    pad = -s % rows
    parts = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(b, -1, rows, e)
    start = (jnp.zeros((b, nh, dk, dv), F32),
             jnp.zeros((b, kk - 1, nh * (2 * dk + dv)), F32))
    _, out = jax.lax.scan(some, start, jnp.moveaxis(parts, 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, s + pad, e)[:, :s]


def _mlp(x, lp):
    return _by_rows(lambda c: _matmul(
        jax.nn.silu(_matmul(c, lp["w_gate"])) * _matmul(c, lp["w_up"]),
        lp["w_down"]), x)


#: where the program's tree keeps each kind of layer, and its mixer
_STACKS = {"linear_attention": ("delta_layers", _delta),
           "full_attention": ("layers", _attention)}


def _layer(x, lp, mixer, hp):
    control, eps = hp.get("control"), hp["rms_norm_eps"]
    attn_w, mlp_w = lp["post_attn_norm"], lp["post_mlp_norm"]
    if control == "pre_norm":
        x = x + mixer(_rms_norm(x, attn_w, eps), lp, hp)
        return x + _mlp(_rms_norm(x, mlp_w, eps), lp)
    out = mixer(x, lp, hp)
    x = x + (out if control == "no_attn_out_norm"
             else _rms_norm(out, attn_w, eps))
    out = _mlp(x, lp)
    return x + (out if control == "no_mlp_out_norm"
                else _rms_norm(out, mlp_w, eps))


def _forward(params, ids, hp):
    hp = dict(hp)
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    pattern = hp["layer_types"].split(",")
    depth = sum(params[stack]["w_gate"].shape[0]
                for stack, _ in _STACKS.values() if stack in params)
    kinds = [pattern[l % len(pattern)] for l in range(depth)]
    present = sorted(set(kinds))

    def layer_of(kind):
        stack, mixer = _STACKS[kind]

        def go(x, i):            # layer i of the kind's stack
            lp = {k: jax.lax.dynamic_index_in_dim(v, i, 0, False)
                  for k, v in params[stack].items()}
            return _layer(x, lp, mixer, hp)
        return go
    # one loop over the layers, each reading its own leaves out of its
    # kind's stack: one layer's intermediates are all that is ever held
    # (layers unrolled, or stacks sliced a period at a time, hold
    # gigabytes more on a TPU)
    x, _ = jax.lax.scan(
        lambda x, l: (jax.lax.switch(
            l[0], [layer_of(kind) for kind in present], x, l[1]), None),
        x, (jnp.asarray([present.index(kind) for kind in kinds], jnp.int32),
            jnp.asarray([kinds[:l].count(kind)
                         for l, kind in enumerate(kinds)], jnp.int32)))
    x = _rms_norm(x[:, int(hp.get("logits_from", 0)):],
                  params["final_norm"]["scale"], hp["rms_norm_eps"])
    return _head(x, params["lm_head"]["w"])


forward, loss, loss_and_grad_norm = make_api(_forward)
