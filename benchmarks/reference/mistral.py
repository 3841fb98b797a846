"""Mistral 7B (Jiang et al. 2023, arXiv:2310.06825; mistralai/
Mistral-7B-v0.3 config.json): sequential pre-RMSNorm blocks, grouped
query attention (``num_key_value_heads`` < heads, each kv head shared by
a group of query heads), rotate-half rotary over the whole head at
``rope_theta``, SwiGLU, no biases, untied head. v0.3 has no sliding
window. RMSNorm uses the published ``rms_norm_eps`` (1e-5); the program
fixes 1e-6 (``ops/norms.py``), a departure of the program, not of this
file (``benchmarks/check.py`` says how the comparison keeps it out)."""
import jax
import jax.numpy as jnp

from .common import F32, causal_attention, make_api


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _rotary_half(x, theta):
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]      # (s, d/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(params, ids, hp):
    hp = dict(hp)
    nh, nkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]
    f = lambda a: a.astype(F32)  # noqa: E731
    x = f(params["embed"])[ids]
    b, s, e = x.shape
    layers = params["layers"]
    for i in range(layers["wq"].shape[0]):
        lp = {k: f(v[i]) for k, v in layers.items()}
        h = _rms_norm(x, lp["attn_norm"], eps)
        q = (h @ lp["wq"]).reshape(b, s, nh, -1)
        k = (h @ lp["wk"]).reshape(b, s, nkv, -1)
        v = (h @ lp["wv"]).reshape(b, s, nkv, -1)
        q, k = _rotary_half(q, theta), _rotary_half(k, theta)
        k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
        x = x + causal_attention(q, k, v).reshape(b, s, -1) @ lp["wo"]
        h = _rms_norm(x, lp["mlp_norm"], eps)
        x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
            @ lp["w_down"]
    x = _rms_norm(x, f(params["final_norm"]["scale"]), eps)
    return x @ f(params["lm_head"]["w"])


forward, loss, loss_and_grad_norm = make_api(_forward)
