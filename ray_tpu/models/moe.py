"""Mixture-of-experts MLP with capacity-based top-1 (Switch) routing.

TPU-first dispatch: token->expert movement is expressed as einsums over a
dispatch one-hot ``[tokens, experts, capacity]`` (the flaxformer/Switch
formulation). With expert weights sharded on the ``ep`` mesh axis and
tokens on ``dp``/``fsdp``, XLA lowers the two boundary einsums to
all-to-alls over ICI — no hand-written NCCL alltoall like torch MoE
stacks (reference has no in-tree MoE; SURVEY.md §2.5 commits the ``ep``
axis here).

Static shapes throughout (capacity fixes the per-expert token count, the
overflow is dropped and carried by the residual), so the whole layer
jits into the one GSPMD program like everything else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_mlp(c, lp, h):
    """h: [batch, seq, d_model] (compute dtype). Returns (out, aux_loss).

    lp carries ``moe_wg [D,E]``, ``moe_wi [E,D,F]``, ``moe_wo [E,F,D]``.
    aux_loss is the Switch load-balancing term (encourages uniform
    routing; weight it into the training loss).
    """
    dt = c.dtype
    B, S, D = h.shape
    E = c.n_experts
    N = B * S
    capacity = max(1, int(c.capacity_factor * N / E))
    x = h.reshape(N, D)

    logits = jnp.dot(x, lp["moe_wg"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)              # [N, E]
    gate = jnp.max(probs, axis=-1)                       # top-1 weight
    expert = jnp.argmax(probs, axis=-1)                  # [N]
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)

    # position of each token within its expert's buffer; tokens past
    # capacity are dropped (their residual passes through unchanged)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0      # [N, E]
    keep = ((pos >= 0.0) & (pos < capacity)).astype(jnp.float32)
    slot = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    # [N, E, capacity] dispatch one-hot
    dispatch = jax.nn.one_hot(slot, capacity, dtype=jnp.float32) \
        * (onehot * keep)[..., None]
    combine = dispatch * gate[:, None, None]

    # boundary einsums: tokens-sharded <-> expert-sharded (all-to-all)
    xin = jnp.einsum("nec,nd->ecd", dispatch.astype(dt), x)
    hmid = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin,
                                  lp["moe_wi"].astype(dt)))
    xout = jnp.einsum("ecf,efd->ecd", hmid, lp["moe_wo"].astype(dt))
    y = jnp.einsum("nec,ecd->nd", combine.astype(dt), xout)

    # Switch aux loss: E * sum_e mean(frac routed to e) * mean(prob e)
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return y.reshape(B, S, D), aux


def moe_param_shapes(c):
    """(name -> shape) for one layer's MoE parameters."""
    return {
        "moe_wg": (c.d_model, c.n_experts),
        "moe_wi": (c.n_experts, c.d_model, c.d_ff),
        "moe_wo": (c.n_experts, c.d_ff, c.d_model),
    }


def moe_logical_axes():
    return {
        "moe_wg": ("layers", "embed", None),
        "moe_wi": ("layers", "expert", "embed", "mlp"),
        "moe_wo": ("layers", "expert", "mlp", "embed"),
    }


# ------------------------------------------------------ dropless top-k
# The served form (``experts_per_token > 0``): every token goes to its k
# best experts, no capacity, no drop; gated (SwiGLU) experts of their own
# width; weights the renormalised softmax. One code path for a prefill
# chunk (thousands of rows) and a decode step (a handful): sort the
# (token, expert) assignments by expert, run one grouped product over
# the sorted rows (``jax.lax.ragged_dot``: rows of group i times ``w[i]``,
# exact, and the work is the rows', not one dense product an expert),
# unsort, combine.

def route_topk(c, lp, x):
    """Router of the dropless layer. ``x [N, D]`` -> (weights ``[N, k]``
    float32 summing to one, experts ``[N, k]`` int32): softmax over all
    experts in float32, the k largest, renormalised."""
    logits = jnp.dot(x, lp["w_router"].astype(c.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, c.experts_per_token)
    return weights / jnp.sum(weights, axis=-1, keepdims=True), experts


#: the dropless layer's stacked expert leaves
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


@jax.named_scope("moe")
def topk_moe_mlp(c, lp, h, layer=None):
    """Dropless top-k gated-expert MLP. ``h [B, S, D]`` (compute dtype)
    -> ``[B, S, D]``. ``lp`` carries ``w_router [D, E]`` and the experts
    ``we_gate / we_up [E, D, F]``, ``we_down [E, F, D]``.

    Inside a scan over layers pass ``layer`` (the scan's int32 index)
    and the expert leaves WHOLE, ``[L, E, ...]``: the grouped product
    then runs on ``[L * E, ...]`` with the rows in layer ``layer``'s
    groups and every other group empty. Scanned like the other leaves,
    each layer's experts would be sliced out of the stack — a copy of
    all of them, in every layer of every step — because a grouped
    product, unlike a plain dot, cannot read its operand through the
    slice."""
    dt = c.dtype
    B, S, D = h.shape
    E, k = c.n_experts, c.experts_per_token
    N = B * S
    x = h.reshape(N, D).astype(dt)
    weights, experts = route_topk(c, lp, x)
    flat = experts.reshape(N * k)
    order = jnp.argsort(flat)                  # stable: assignment order
    xs = jnp.take(x, order // k, axis=0)       # rows sorted by expert
    w_gate, w_up, w_down = (lp[name].astype(dt) for name in EXPERT_LEAVES)
    groups = E
    if layer is not None:
        groups = w_gate.shape[0] * E
        flat = flat + layer * E
        w_gate, w_up, w_down = (w.reshape((groups,) + w.shape[2:])
                                for w in (w_gate, w_up, w_down))
    sizes = jnp.zeros((groups,), jnp.int32).at[flat].add(1)
    gate = jax.lax.ragged_dot(xs, w_gate, sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(xs, w_up, sizes,
                            preferred_element_type=jnp.float32)
    mid = (jax.nn.silu(gate) * up).astype(dt)
    ys = jax.lax.ragged_dot(mid, w_down, sizes,
                            preferred_element_type=jnp.float32)
    # unsort: row i of ys is assignment order[i]
    inverse = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    ys = jnp.take(ys, inverse, axis=0).reshape(N, k, D)
    y = jnp.sum(ys * weights[..., None], axis=1)
    return y.reshape(B, S, D).astype(dt)


def topk_moe_param_shapes(c):
    f = c.expert_width
    return {
        "w_router": (c.d_model, c.n_experts),
        "we_gate": (c.n_experts, c.d_model, f),
        "we_up": (c.n_experts, c.d_model, f),
        "we_down": (c.n_experts, f, c.d_model),
    }


def topk_moe_logical_axes():
    return {
        "w_router": ("layers", "embed", None),
        "we_gate": ("layers", "expert", "embed", "mlp"),
        "we_up": ("layers", "expert", "embed", "mlp"),
        "we_down": ("layers", "expert", "mlp", "embed"),
    }
