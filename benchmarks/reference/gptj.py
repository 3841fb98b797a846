"""GPT-J (Wang & Komatsuzaki 2021; EleutherAI/gpt-j-6b config.json and
modeling_gptj.py): one LayerNorm feeds attention and the MLP in
parallel, rotary over the first ``rotary_dim`` features of each head in
interleaved (even, odd) pairs, gelu_new, untied head with a bias.
No departures from the published block."""
import jax
import jax.numpy as jnp

from .common import F32, causal_attention, make_api


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rotary_interleaved(x, rot, theta):
    """x: (b, s, h, d); rotate pairs (2i, 2i+1) of the first ``rot``."""
    s = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]      # (s, rot/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    even, odd = x[..., 0:rot:2], x[..., 1:rot:2]
    pairs = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return jnp.concatenate([pairs.reshape(x.shape[:-1] + (rot,)),
                            x[..., rot:]], -1)


def _forward(params, ids, hp):
    hp = dict(hp)
    nh, eps = hp["n_head"], hp["layer_norm_epsilon"]
    rot, theta = hp["rotary_dim"], hp.get("rope_theta", 10000.0)
    f = lambda a: a.astype(F32)  # noqa: E731
    x = f(params["embed"])[ids]
    b, s, e = x.shape
    layers = params["layers"]
    for i in range(layers["wq"].shape[0]):
        lp = {k: f(v[i]) for k, v in layers.items()}
        h = _layer_norm(x, lp["ln_scale"], lp["ln_bias"], eps)
        q, k, v = ((h @ lp[w]).reshape(b, s, nh, -1)
                   for w in ("wq", "wk", "wv"))
        q, k = (_rotary_interleaved(t, rot, theta) for t in (q, k))
        att = causal_attention(q, k, v).reshape(b, s, -1) @ lp["wo"]
        mlp = jax.nn.gelu(h @ lp["fc_in"] + lp["fc_in_b"], approximate=True)
        x = x + att + mlp @ lp["fc_out"] + lp["fc_out_b"]
    fin = params["final_norm"]
    x = _layer_norm(x, f(fin["scale"]), f(fin["bias"]), eps)
    return x @ f(params["lm_head"]["w"]) + f(params["lm_head"]["b"])


forward, loss, loss_and_grad_norm = make_api(_forward)
