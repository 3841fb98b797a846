"""Mixture-of-experts MLPs: capacity-based top-1 (Switch) routing, the
trained form, and below it the dropless top-k gated experts, the served
form (softmax or sigmoid router, a shared expert, a held share of the
experts).

Switch, TPU-first dispatch: token->expert movement is expressed as einsums over a
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
# width; weights the renormalised scores. One code path for a prefill
# chunk (thousands of rows) and a decode step (a handful): sort the
# (token, expert) assignments by expert, run one grouped product over
# the sorted rows (``jax.lax.ragged_dot``: rows of group i times ``w[i]``,
# exact, and the work is the rows', not one dense product an expert),
# unsort, combine.
#
# A program may HOLD a share of the experts (``experts_held`` of
# ``n_experts`` from ``expert_first``: one chip of a deployment that
# splits them). It still routes every token over all of them, and
# computes the part of the sum its own experts give: the assignments
# that land elsewhere are sorted behind the held ones' rows, belong to no
# group of the grouped product and are left out of the combine. Nothing
# stands in for the absent chips' part.

@jax.named_scope("moe_router")
def _group_limited(c, scores, bias):
    """The experts ``[N, k]`` a token is sent to under a group limit
    and a bias (DeepSeek-V3's ``noaux_tc``): ``scores [N, E]`` plus the
    experts' ``bias`` select; the experts stand in ``n_group`` equal
    groups, a group's score is the sum of its two largest, the
    ``topk_group`` best groups are kept, and within them the k largest.
    Ties go to the lower index at every step."""
    pick = scores if bias is None else scores + bias.astype(jnp.float32)
    if c.n_group:
        n, e = pick.shape
        groups = pick.reshape(n, c.n_group, e // c.n_group)
        best_two = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(best_two, c.topk_group)
        kept = jnp.any(kept[..., None] == jnp.arange(c.n_group), axis=1)
        pick = jnp.where(kept[..., None], groups, -jnp.inf).reshape(n, e)
    return jax.lax.top_k(pick, c.experts_per_token)[1]


def route_topk(c, lp, x):
    """Router of the dropless layer. ``x [N, D]`` -> (weights ``[N, k]``
    float32, experts ``[N, k]`` int32): each expert's score in float32
    (``router_score``: softmax over all experts, or a sigmoid of each),
    the k largest (with ``n_group`` / ``router_bias`` the k that
    :func:`_group_limited` selects, whose weights are their SCORES, the
    bias left out), renormalised to sum to one and scaled by
    ``routed_scale``."""
    logits = jnp.dot(x, lp["w_router"].astype(c.dtype),
                     preferred_element_type=jnp.float32)
    if c.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif c.router_score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router_score {c.router_score!r}")
    if c.n_group or c.router_bias:
        experts = _group_limited(c, scores, lp.get("router_bias"))
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    else:
        weights, experts = jax.lax.top_k(scores, c.experts_per_token)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if c.routed_scale != 1.0:
        weights = weights * c.routed_scale
    return weights, experts


#: the dropless layer's stacked expert leaves
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")

#: XLA:TPU does not compile a grouped product of fewer rows than this
#: against a count of groups over 128 that is no multiple of 512 (an
#: internal error: it bitcasts the group sizes between two tilings that
#: pad such a count differently; 9 layers of 36 held experts, a batch of
#: one or two tokens at ten experts each): such a call's rows are padded
#: up to it (tests/ops/test_tpu_lowering.py compiles the case)
_FEW_ROWS = 32


@jax.named_scope("moe_shared")
def _shared_expert(c, lp, x):
    """The SwiGLU expert every token passes. ``x [N, D]`` -> ``[N, D]``."""
    dt = c.dtype
    gate = jax.nn.silu(jnp.dot(x, lp["ws_gate"].astype(dt)))
    return jnp.dot(gate * jnp.dot(x, lp["ws_up"].astype(dt)),
                   lp["ws_down"].astype(dt))


@jax.named_scope("moe")
def topk_moe_mlp(c, lp, h, layer=None):
    """Dropless top-k gated-expert MLP. ``h [B, S, D]`` (compute dtype)
    -> ``[B, S, D]``. ``lp`` carries ``w_router [D, E]`` and the held
    experts ``we_gate / we_up [E_held, D, F]``, ``we_down [E_held, F,
    D]`` (``E_held`` = ``E`` unless ``experts_held`` is set), and with
    ``shared_expert_width`` the shared expert's ``ws_gate / ws_up /
    ws_down``.

    Inside a scan over layers pass ``layer`` (the scan's int32 index)
    and the expert leaves WHOLE, ``[L, E_held, ...]``: the grouped
    product then runs on ``[L * E_held, ...]`` with the rows in layer
    ``layer``'s groups and every other group empty. Scanned like the
    other leaves, each layer's experts would be sliced out of the stack
    — a copy of all of them, in every layer of every step — because a
    grouped product, unlike a plain dot, cannot read its operand through
    the slice."""
    dt = c.dtype
    B, S, D = h.shape
    E, k = c.n_experts_held, c.experts_per_token
    N = B * S
    x = h.reshape(N, D).astype(dt)
    weights, experts = route_topk(c, lp, x)
    flat = experts.reshape(N * k)
    here = None
    if E != c.n_experts:
        # an assignment to an expert held elsewhere gets group E: sorted
        # behind every held expert's rows, counted in no group
        flat = flat - c.expert_first
        here = (flat >= 0) & (flat < E)
        flat = jnp.where(here, flat, E)
    order = jnp.argsort(flat)                  # stable: assignment order
    xs = jnp.take(x, order // k, axis=0)       # rows sorted by expert
    w_gate, w_up, w_down = (lp[name].astype(dt) for name in EXPERT_LEAVES)
    groups = E
    if layer is not None:
        groups = w_gate.shape[0] * E
        flat = flat + layer * E
        w_gate, w_up, w_down = (w.reshape((groups,) + w.shape[2:])
                                for w in (w_gate, w_up, w_down))
    if here is not None:
        flat = jnp.where(here, flat, groups)   # out of bounds: dropped
    sizes = jnp.zeros((groups,), jnp.int32).at[flat].add(1, mode="drop")
    if N * k < _FEW_ROWS and groups > 128 and groups % 512:
        # rows of no group behind the others (see _FEW_ROWS)
        xs = jnp.pad(xs, ((0, _FEW_ROWS - N * k), (0, 0)))
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
    ys = jnp.take(ys, inverse, axis=0).reshape(N, k, D)   # and unpad
    if here is not None:
        # a row of no group is whatever the product left there: chosen
        # away, not multiplied by a zero weight
        ys = jnp.where(here.reshape(N, k, 1), ys, 0.0)
    y = jnp.sum(ys * weights[..., None], axis=1)
    if c.shared_expert_width:
        y = y + _shared_expert(c, lp, x).astype(jnp.float32)
    return y.reshape(B, S, D).astype(dt)


def topk_moe_param_shapes(c):
    f, held = c.expert_width, c.n_experts_held
    shapes = {
        "w_router": (c.d_model, c.n_experts),
        "we_gate": (held, c.d_model, f),
        "we_up": (held, c.d_model, f),
        "we_down": (held, f, c.d_model),
    }
    if c.shared_expert_width:
        fs = c.shared_expert_width
        shapes.update({"ws_gate": (c.d_model, fs), "ws_up": (c.d_model, fs),
                       "ws_down": (fs, c.d_model)})
    return shapes


def topk_moe_logical_axes(c):
    axes = {
        "w_router": ("layers", "embed", None),
        "we_gate": ("layers", "expert", "embed", "mlp"),
        "we_up": ("layers", "expert", "embed", "mlp"),
        "we_down": ("layers", "expert", "mlp", "embed"),
    }
    if c.shared_expert_width:
        axes.update({"ws_gate": ("layers", "embed", "mlp"),
                     "ws_up": ("layers", "embed", "mlp"),
                     "ws_down": ("layers", "mlp", "embed")})
    return axes
