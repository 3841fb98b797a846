"""Nemotron-H's language model as Nemotron 3 Super configures it
(nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json, ``model_type:
nemotron_h``, 120B-A12B): every layer is ONE sublayer behind ONE RMSNorm,
bf16 residual::

    x = x + mixer_l(RMSNorm_l(x))      mixer_l by the pattern's letter

under a final RMSNorm and an untied head. ``hp["pattern"]`` is the
published ``hybrid_override_pattern``, a letter a layer.

``M``, Mamba-2 (Dao & Gu 2024, arXiv 2405.21060): ``mamba_num_heads``
heads of ``mamba_head_dim`` over a state of ``ssm_state_size``, B and C in
``n_groups`` groups (head h reads group ``h // (heads / groups)``), no
projection bias::

    [z | xBC | dt] = u W_in
    xBC_t = silu(b + sum_j w[:, j] xBC_raw[t - (K-1) + j])   zeros before 0
    [x | B_1..B_G | C_1..C_G] = xBC
    dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_{g,t}      S_{-1} = 0
    y_t = S_t C_{g,t} + D x_t
    out = RMSNorm_group(y * silu(z)) W_out    the gate first, then a norm
                                              over each group's channels

The recurrence runs token by token (``lax.scan`` over positions, one
state a head): ``chunk_size`` is how the published kernels block it and
changes no result, so this file has no block.

``*``, attention: q, k, v, o without bias, grouped queries, causal
softmax of ``q k^T / sqrt(head_dim)``, NO position embedding.

``E``, LatentMoE: scores ``s = sigmoid(h W_r)`` in float32; the
``num_experts_per_tok`` largest of ``s + b`` (``b`` a learned bias an
expert that SELECTS only; ties to the lower index); weights
``routed_scaling_factor * s_e / sum of the chosen s``; the routed experts
are ungated, ``relu(u W_up)^2 W_down``, on ``u = h W_lat_down``, their
weighted sum is projected up once, ``r W_lat_up``; a shared expert of the
same form reads the full-width ``h``. A chip of the deployment holds a
share of the routed experts (``expert_first`` .. ``expert_first +
experts_held``, both in ``hp``): every token is routed over all of them
and the sum runs over the chosen experts held here, a dense loop over the
held ones under a mask.

Plain float32, nothing cached, blocked, sorted or batched; one loop over
the layers, each reading its own leaves out of its kind's stack, the wide
matrices upcast a layer, an expert or a slice at a time.
``hp["logits_from"]`` (0 if absent): the head is applied from that
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
    "parallel_pairs",        # an E layer reads what the layer ahead of it
                             # read: two sublayers of one layer
    "one_group",             # every head reads group 0's B and C
    "norm_all_channels",     # one norm over all channels, not a group's
    "ungated_norm",          # RMSNorm(y) * silu(z): the gate outside
    "rotary",                # rotate-half RoPE at theta 10000 on q, k
    "bias_weighs",           # the weights from s + b, not from s
    "no_routed_scale",       # routed_scaling_factor left out
    "one_expert_fewer",      # top k - 1
    "gated_expert",          # silu(u W_up) * (u W_up) for relu(u W_up)^2
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
    q = _matmul(h, lp["wq"]).reshape(b, s, nkv, nh // nkv, -1)
    k = _matmul(h, lp["wk"]).reshape(b, s, nkv, -1)
    v = _matmul(h, lp["wv"]).reshape(b, s, nkv, -1)
    if hp.get("control") == "rotary":
        q = _rotary_half(q.reshape(b, s, nh, -1)).reshape(q.shape)
        k = _rotary_half(k)
    scale = q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(j):              # the query heads that share kv head j
        scores = jnp.einsum("bqgd,bkd->bgqk", q[:, :, j], k[:, :, j]) * scale
        att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("bgqk,bkd->bqgd", att, v[:, :, j])
    out = jax.lax.map(group, jnp.arange(nkv))          # (nkv, b, s, g, d)
    return _matmul(jnp.moveaxis(out, 0, 2).reshape(b, s, -1), lp["wo"])


def _mamba(u, lp, hp):
    b, s, _ = u.shape
    nh, dh, n = hp["mamba_num_heads"], hp["mamba_head_dim"], \
        hp["ssm_state_size"]
    g, k, control = hp["n_groups"], hp["conv_kernel"], hp.get("control")
    di, eps = nh * dh, hp["layer_norm_epsilon"]
    proj = _matmul(u, lp["w_in"])
    z, raw, dt = proj[..., :di], proj[..., di:di + di + 2 * g * n], \
        proj[..., di + di + 2 * g * n:]
    # depthwise, causal: tap j of w meets the input K-1-j positions back
    ext = jnp.pad(raw, ((0, 0), (k - 1, 0), (0, 0)))
    w = lp["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(ext[:, j:j + s] * w[:, j] for j in range(k))
                      + lp["conv_b"].astype(F32))
    x = xbc[..., :di].reshape(b, s, nh, dh)
    bm = xbc[..., di:di + g * n].reshape(b, s, g, n)
    cm = xbc[..., di + g * n:].reshape(b, s, g, n)
    # a head's own B and C: its group's (the control: group 0's)
    of_head = jnp.zeros(nh, jnp.int32) if control == "one_group" \
        else jnp.arange(nh) // (nh // g)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))       # (b, s, nh)
    a = -jnp.exp(lp["A_log"].astype(F32))

    def token(state, t):
        x_t, b_t, c_t, dt_t = t                  # b_t, c_t: (b, g, n)
        b_h, c_h = b_t[:, of_head], c_t[:, of_head]            # (b, nh, n)
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_h)
    _, y = jax.lax.scan(
        token, jnp.zeros((b, nh, dh, n), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + lp["D"].astype(F32)[:, None] * x
    y, gate = y.reshape(b, s, di), jax.nn.silu(z)
    groups = 1 if control == "norm_all_channels" else g

    def norm(y):               # over each group's channels
        return _rms_norm(y.reshape(b, s, groups, di // groups),
                         lp["ssm_norm"].reshape(groups, di // groups),
                         eps).reshape(b, s, di)
    y = norm(y) * gate if control == "ungated_norm" else norm(y * gate)
    return _matmul(y, lp["w_out"])


def _route(h, lp, hp):
    """(b, s, experts) weights of the routed experts, zero but for a
    token's chosen ones."""
    control = hp.get("control")
    scores = jax.nn.sigmoid(h @ lp["w_router"].astype(F32))
    pick = scores + lp["router_bias"].astype(F32)
    k = hp["num_experts_per_tok"] - (control == "one_expert_fewer")
    # the k largest, ties to the lower index (a stable descending sort)
    order = jnp.argsort(-pick, axis=-1, stable=True)
    chosen = jnp.argsort(order, axis=-1, stable=True) < k
    w = jnp.where(chosen, pick if control == "bias_weighs" else scores, 0.0)
    w = w / jnp.sum(w, -1, keepdims=True)
    return w if control == "no_routed_scale" \
        else w * hp["routed_scaling_factor"]


def _relu2(h, up, down, hp):
    mid = h @ up.astype(F32)
    mid = jax.nn.silu(mid) * mid if hp.get("control") == "gated_expert" \
        else jnp.square(jax.nn.relu(mid))
    return mid @ down.astype(F32)


def _experts(h, lp, hp, layer, up, down):
    """The shared expert at full width, and in the latent the chosen
    experts held here; ``up`` / ``down`` are the held experts of the
    whole stack as stored, ``(layers, held, ...)``."""
    weights = _route(h, lp, hp)
    first = hp["expert_first"]
    u = _matmul(h, lp["w_lat_down"])

    def one(e, r):
        out = _relu2(u, up[layer, e], down[layer, e], hp)
        w = jax.lax.dynamic_index_in_dim(weights, first + e, -1, False)
        return r + w[..., None] * out
    r = jax.lax.fori_loop(0, hp["experts_held"], one, jnp.zeros_like(u))
    return _matmul(r, lp["w_lat_up"]) \
        + _relu2(h, lp["ws_up"], lp["ws_down"], dict(hp, control=None))


#: where the program's tree keeps each letter's layers, and the norm's leaf
_STACKS = {"M": ("mamba_layers", "attn_norm"), "*": ("layers", "attn_norm"),
           "E": ("ffn_layers", "mlp_norm")}
_EXPERTS = ("we_up", "we_down")


def _forward(params, ids, hp):
    hp = dict(hp)
    eps, control = hp["layer_norm_epsilon"], hp.get("control")
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    depth = sum(params[stack][norm].shape[0]
                for stack, norm in _STACKS.values() if stack in params)
    kinds = [hp["pattern"][l % len(hp["pattern"])] for l in range(depth)]
    present = sorted(set(kinds))

    def layer_of(kind):
        stack, norm = _STACKS[kind]

        def go(x, read, i):      # layer i of the kind's stack
            lp = {k: jax.lax.dynamic_index_in_dim(v, i, 0, False)
                  for k, v in params[stack].items() if k not in _EXPERTS}
            # ``read``: what the layer ahead read (the control's pairs)
            h = _rms_norm(read if kind == "E" and control == "parallel_pairs"
                          else x, lp[norm], eps)
            if kind == "M":
                return x + _mamba(h, lp, hp)
            if kind == "*":
                return x + _attention(h, lp, hp)
            return x + _experts(h, lp, hp, i,
                                *(params[stack][k] for k in _EXPERTS))
        return go
    # one loop over the layers, each reading its own leaves out of its
    # kind's stack: one layer's intermediates are all that is ever held

    def step(carry, l):
        x, read = carry
        return (jax.lax.switch(l[0], [layer_of(kind) for kind in present],
                               x, read, l[1]), x), None
    (x, _), _ = jax.lax.scan(
        step, (x, x),
        (jnp.asarray([present.index(kind) for kind in kinds], jnp.int32),
         jnp.asarray([kinds[:l].count(kind)
                      for l, kind in enumerate(kinds)], jnp.int32)))
    x = _rms_norm(x[:, int(hp.get("logits_from", 0)):],
                  params["final_norm"]["scale"], eps)
    return _matmul(x, params["lm_head"]["w"])


forward, loss, loss_and_grad_norm = make_api(_forward)
