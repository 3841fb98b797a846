"""Mixture-of-experts MLPs: capacity-based top-1 (Switch) routing, and
below it the dropless top-k gated experts, served in every form they
have (softmax or sigmoid router, a shared expert, a held share of the
experts, a latent) and trained in the softmax-routed form, a held share
included (``transformer.refuse_training`` names the rest).

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

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.ops import backend as _megablox

from ray_tpu.ops.attention import _resolve, dispatch_log


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
#
# The expert's form (``expert_act``): "swiglu", three matrices and a gate,
# or "relu2", two and none: ``relu(x W_up)^2 W_down``. With ``moe_latent``
# R > 0 the routed experts live in a latent of R numbers: a token is
# projected down ONCE ahead of the sort (``w_lat_down [D, R]``), the
# experts are ``[R, F]`` / ``[F, R]``, their weighted sum is taken in R and
# projected up once a token (``w_lat_up [R, D]``); the router and the
# shared expert read the full-width token.

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


@jax.named_scope("moe_router")
def route_topk(c, lp, x, scores_too=False):
    """Router of the dropless layer. ``x [N, D]`` -> (weights ``[N, k]``
    float32, experts ``[N, k]`` int32; with ``scores_too`` every expert's
    score ``[N, E]`` behind them): each expert's score in float32
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
    if scores_too:
        return weights, experts, scores
    return weights, experts


#: the stacked expert leaves of a gated expert. The program reads
#: ``expert_leaves(c)``; the name stays for the accepted tests that import
#: it (tests/models/test_latent_moe_serving.py, test_sparse_latent_serving.py)
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


def expert_leaves(c):
    """The stacked expert leaves of ``c``'s form of expert: an ungated
    one ("relu2") has no gate stack."""
    return EXPERT_LEAVES if c.expert_act == "swiglu" else EXPERT_LEAVES[1:]


def _expert_mid(gate, up):
    """The rows between an expert's matrices: ``silu(gate) * up``, or
    with no gate (``gate`` None, "relu2") ``relu(up)^2``."""
    if gate is None:
        return jnp.square(jax.nn.relu(up))
    return jax.nn.silu(gate) * up

#: XLA:TPU does not compile a grouped product of fewer rows than this
#: against a count of groups over 128 that is no multiple of 512 (an
#: internal error: it bitcasts the group sizes between two tilings that
#: pad such a count differently; 9 layers of 36 held experts, a batch of
#: one or two tokens at ten experts each): such a call's rows are padded
#: up to it (tests/ops/test_tpu_lowering.py compiles the case)
_FEW_ROWS = 32


@jax.named_scope("moe_shared")
def _shared_expert(c, lp, x):
    """The expert every token passes, in the routed experts' form at the
    model's width. ``x [N, D]`` -> ``[N, D]``."""
    dt = c.dtype
    gate = None if c.expert_act == "relu2" \
        else jnp.dot(x, lp["ws_gate"].astype(dt))
    mid = _expert_mid(gate, jnp.dot(x, lp["ws_up"].astype(dt)))
    return jnp.dot(mid, lp["ws_down"].astype(dt))


# The sort and the unsort move rows by a permutation of the N * k
# assignments, and each is the other's transpose. Differentiated as
# written, a row gather's transpose is a scatter-add of as many rows (for
# the sort into k-fold collisions); written out here it is the other
# gather: the forward's program is the one it was, the backward's reads
# rows and adds none in place.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sort_rows(x, order, inverse, k):
    """``x [N, D]`` -> row ``order[i] // k`` of it at ``i``, ``[N * k,
    D]``: each token's row where each of its k assignments sorts to."""
    return jnp.take(x, order // k, axis=0)


def _sort_rows_fwd(x, order, inverse, k):
    return _sort_rows(x, order, inverse, k), (inverse,)


def _sort_rows_bwd(k, res, g):
    inverse, = res
    back = jnp.take(g, inverse, axis=0)        # assignment order again
    return (jnp.sum(back.reshape(-1, k, g.shape[-1]), axis=1,
                    dtype=jnp.float32).astype(g.dtype), None, None)


_sort_rows.defvjp(_sort_rows_fwd, _sort_rows_bwd)


@jax.custom_vjp
def _unsort_rows(ys, order, inverse):
    """``ys [N * k (or more: padding behind), D]`` -> its rows in
    assignment order, ``[N * k, D]``: row i of ``ys`` is assignment
    ``order[i]``."""
    return jnp.take(ys, inverse, axis=0)


def _unsort_rows_fwd(ys, order, inverse):
    return _unsort_rows(ys, order, inverse), (order, ys.shape[0])


def _unsort_rows_bwd(res, g):
    order, rows = res
    back = jnp.take(g, order, axis=0)
    if rows != back.shape[0]:                  # the padding's rows: none
        back = jnp.pad(back, ((0, rows - back.shape[0]), (0, 0)))
    return back, None, None


_unsort_rows.defvjp(_unsort_rows_fwd, _unsort_rows_bwd)


# The grouped product and its two transposes. Differentiated as written,
# ``ragged_dot``'s transposes take the float32 cotangent as an operand
# (a [rows, width] float32 array a product, and a float32 pass through
# the MXU); written out here they are grouped products of the compute
# dtype's operands with float32 accumulation, as the forward: dX the
# cotangent's rows through each group's transposed matrix, dW a group's
# own rows' outer products summed. The matrices come in as they are
# stored (training's float32 masters, cast here at each use; a served
# tree's are in the compute dtype already and the cast is none), so dW
# goes out in float32 to a float32 master, with no rounding between. A
# row of no group is whatever the product left there, in dX as in the
# forward: zeroed here, because the sort's transpose adds a token's rows.
#
# Only a differentiated call reaches the two rules, and in a program that
# runs whole on one TPU chip (``one_chip``: the caller's mesh is of one
# device; XLA cannot partition a Pallas custom call, and a grouped
# product split over chips wants a ``shard_map`` of its own) they run
# the Pallas grouped matmul that ships with JAX (megablox ``gmm`` /
# ``gmm(transpose_rhs=True)`` / ``tgmm``: the same operands, the same
# float32 accumulation, two to three times ``ragged_dot``'s speed at a
# training turn's 32,768 rows of 16 experts). The primal, which every
# served program and any undifferentiated forward runs, is ``ragged_dot``
# to the instruction. The kernels are megablox's own jitted entry points
# and their tiling one cached tuple a shape, so equal calls (gate and up,
# a turn's recomputation, the scans of a stack by kind of layer, a second
# program of the same turn) are traced once a process and lowered once a
# program.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_dot(x, w, sizes, one_chip=False):
    """Rows of group i of ``x [M, K]`` times ``w[i]`` (``w [G, K, N]``,
    cast to ``x``'s dtype), ``[M, N]`` float32; the rows past
    ``sum(sizes)`` belong to no group. ``one_chip``: the program runs
    whole on one chip, so its differentiated form may be a kernel."""
    return jax.lax.ragged_dot(x, w.astype(x.dtype), sizes,
                              preferred_element_type=jnp.float32)


#: the kernels' row tile: 512 read best at a turn's rows on a v5e (256 a
#: fifth slower, 1024 past the kernel's VMEM), and a call whose rows it
#: does not divide stays ``ragged_dot``
_ROW_TILE = 512
#: what one grid step's blocks may hold, by :func:`_block_bytes`' count:
#: the v5e compiler gives a kernel 16 MiB of VMEM and the count errs
#: high: every tiling the rule can pick under it compiles (each one was;
#: tests/ops/test_tpu_lowering.py compiles the largest of each kernel),
#: the smallest that does not counts 19.4 MiB
_VMEM_BUDGET = 16 * 2 ** 20


def grouped_product_counts():
    """The differentiated grouped products this process has traced, by
    form: ``{"pallas_gmm": n, "xla_ragged_dot": m}`` (a product in a
    scan's body counts once; the second is the shape rule's fallback, a
    program over several chips, or every product off a TPU). Op
    ``"grouped_dot"`` of ``ops.attention.dispatch_log``, which has the
    reasons."""
    counts = {"pallas_gmm": 0, "xla_ragged_dot": 0}
    for entry in dispatch_log():
        if entry["op"] == "grouped_dot":
            counts["pallas_gmm" if entry["impl"] == "kernel"
                   else "xla_ragged_dot"] += entry["count"]
    return counts


def _block_bytes(tm, tk, tn, size, out_size, dw):
    """A grid step's VMEM by the blocks' shapes: the two operand blocks
    and the result's, each twice (the pipeline's two buffers), the
    float32 accumulator, and what the kernel's body holds in float32
    beside them (``gmm``: the accumulator read back and the result block
    it is selected into; ``tgmm``: both operand blocks, masked)."""
    if dw:      # [tm, tk]^T [tm, tn] -> [tk, tn]
        return (2 * size * tm * (tk + tn) + (2 * out_size + 4) * tk * tn
                + 4 * tm * (tk + tn))
    return (2 * size * (tm * tk + tk * tn)       # [tm, tk] [tk, tn]
            + (2 * out_size + 4 + 8) * tm * tn)


def _tile(width, most):
    """The largest multiple of 128 that divides ``width`` and is at most
    ``most``, or None."""
    return next((t for t in range(most - most % 128, 0, -128)
                 if not width % t), None)


@functools.lru_cache(maxsize=None)
def _gmm_tiles(rows, k, n, dtype):
    """The tilings ``(tm, tk, tn)`` of the three kernels of ``x [rows, k]
    @ w[i] [k, n]`` in ``dtype`` (forward, dX, dW), a function of the
    shapes alone, or None where the kernels cannot tile them (rows no
    multiple of the row tile, a width no multiple of 128, a dtype that
    is not theirs, no blocks inside the VMEM budget): the product then
    stays ``ragged_dot``. Wide blocks first (fewer passes over an
    operand), the wider of the two narrowed until the blocks fit."""
    if rows % _ROW_TILE or k % 128 or n % 128 \
            or dtype not in ("bfloat16", "float32"):
        return None
    size = jnp.dtype(dtype).itemsize

    def fit(k, n, out_size, dw):
        most_k = most_n = 1024
        while True:
            tk, tn = _tile(k, most_k), _tile(n, most_n)
            if tk is None or tn is None:
                return None
            if _block_bytes(_ROW_TILE, tk, tn, size, out_size,
                            dw) <= _VMEM_BUDGET:
                return _ROW_TILE, tk, tn
            if tk >= tn:
                most_k = tk - 128
            else:
                most_n = tn - 128
    tiles = (fit(k, n, 4, False), fit(n, k, size, False), fit(k, n, 4, True))
    return None if None in tiles else tiles


def _kernel_tiles(x, w, one_chip, record=False):
    """:func:`_gmm_tiles` of the call in a program whole on one TPU
    chip, where the kernels run; None elsewhere. ``record``: the choice
    goes to ``dispatch_log`` (once a product: the forward rule's)."""
    tiles = _gmm_tiles(x.shape[0], x.shape[1], w.shape[2],
                       jnp.dtype(x.dtype).name)
    unfit = None
    if not one_chip:
        unfit = "the program is not whole on one chip"
    elif tiles is None:
        unfit = f"no tiling of {x.dtype}{list(x.shape)} x {list(w.shape)}"
    choice = _resolve("grouped_dot", "auto", "kernel", unfit, record)
    return tiles if choice == "kernel" else None


def _grouped_dot_fwd(x, w, sizes, one_chip):
    tiles = _kernel_tiles(x, w, one_chip, record=True)
    if tiles is None:
        y = _grouped_dot(x, w, sizes)
    else:
        y = _megablox.gmm(x, w.astype(x.dtype), sizes, jnp.float32,
                          tiles[0])
    return y, (x, w, sizes)


_DW_DIMS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_dot_bwd(one_chip, res, g):
    x, w, sizes = res
    g = g.astype(x.dtype)
    tiles = _kernel_tiles(x, w, one_chip)
    if tiles is None:
        dx = jax.lax.ragged_dot(g, jnp.swapaxes(w.astype(x.dtype), 1, 2),
                                sizes, preferred_element_type=x.dtype)
        dw = jax.lax.ragged_dot_general(
            x, g, sizes, _DW_DIMS, preferred_element_type=jnp.float32)
    else:
        dx = _megablox.gmm(g, w.astype(x.dtype), sizes, x.dtype, tiles[1],
                           transpose_rhs=True)
        dw = _megablox.tgmm(x.T, g, sizes, jnp.float32, tiles[2])
    in_a_group = jnp.arange(x.shape[0], dtype=jnp.int32) < jnp.sum(sizes)
    dx = jnp.where(in_a_group[:, None], dx, jnp.zeros((), dx.dtype))
    return dx, dw.astype(w.dtype), None


_grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


def _expert_rows(leaves, xs, sizes, one_chip):
    """The experts on rows sorted by expert: ``xs [M, width]`` in the
    compute dtype, group i's ``sizes[i]`` rows through expert i's
    matrices (``leaves``, as stored) -> ``[M, width]`` float32; the rows
    past ``sum(sizes)`` are of no group."""
    gate = _grouped_dot(xs, leaves["we_gate"], sizes, one_chip) \
        if "we_gate" in leaves else None
    up = _grouped_dot(xs, leaves["we_up"], sizes, one_chip)
    mid = _expert_mid(gate, up).astype(xs.dtype)
    return _grouped_dot(mid, leaves["we_down"], sizes, one_chip)


@jax.named_scope("moe_experts")
def _routed_experts(c, lp, x, weights, experts, layer, one_chip):
    """The held experts' part of the weighted sum, ``[N, width of x]``
    float32: sort the assignments by expert, the grouped products over
    the sorted rows, unsort, combine. ``x [N, D]`` (``[N, R]`` in a
    latent), ``weights``, ``experts [N, k]``."""
    N, width = x.shape
    E, k = c.n_experts_held, c.experts_per_token
    flat = experts.reshape(N * k)
    here = None
    if E != c.n_experts:
        # an assignment to an expert held elsewhere gets group E: sorted
        # behind every held expert's rows, counted in no group
        flat = flat - c.expert_first
        here = (flat >= 0) & (flat < E)
        flat = jnp.where(here, flat, E)
    order = jnp.argsort(flat)                  # stable: assignment order
    # unsort: row i of the sorted rows is assignment order[i]
    inverse = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    xs = _sort_rows(x, order, inverse, k)      # rows sorted by expert
    leaves = {name: lp[name] for name in expert_leaves(c)}
    groups = E
    if layer is not None:
        groups = leaves["we_up"].shape[0] * E
        flat = flat + layer * E
        leaves = {name: w.reshape((groups,) + w.shape[2:])
                  for name, w in leaves.items()}
    if here is not None:
        flat = jnp.where(here, flat, groups)   # out of bounds: dropped
    sizes = jnp.zeros((groups,), jnp.int32).at[flat].add(1, mode="drop")
    if N * k < _FEW_ROWS and groups > 128 and groups % 512:
        # rows of no group behind the others (see _FEW_ROWS)
        xs = jnp.pad(xs, ((0, _FEW_ROWS - N * k), (0, 0)))
    ys = _expert_rows(leaves, xs, sizes, one_chip)
    ys = _unsort_rows(ys, order, inverse).reshape(N, k, width)  # and unpad
    if here is not None:
        # a row of no group is whatever the product left there: chosen
        # away, not multiplied by a zero weight
        ys = jnp.where(here.reshape(N, k, 1), ys, 0.0)
    return jnp.sum(ys * weights[..., None], axis=1)


#: a call of more tokens than this (a training step's 16,384; no served
#: call's: a prefill chunk is 2048 at most) sends them through the
#: experts this many at a time, :func:`_routed_experts_in_turns`
_MANY_TOKENS = 4096


def _routed_experts_in_turns(c, lp, x, weights, experts, layer, one_chip):
    """:func:`_routed_experts` over ``_MANY_TOKENS`` tokens at a time,
    each turn recomputed in the backward pass. What lies between the sort
    and the weighted sum is k rows a token, model-wide, several times
    over and partly in float32 (4 to 5 GB at 16,384 tokens, top-8 and
    2304 wide, forward and transposed together): a turn at a time it is
    a turn's. Every turn sorts its own assignments and drops none, and
    the turns' dW add up in float32 (``_grouped_dot``)."""
    turns = x.shape[0] // _MANY_TOKENS

    @jax.checkpoint
    def turn(args):
        return _routed_experts(c, lp, *args, layer, one_chip)
    ys = jax.lax.map(turn, tuple(
        a.reshape((turns, _MANY_TOKENS) + a.shape[1:])
        for a in (x, weights, experts)))
    return ys.reshape(x.shape[0], -1)


def route_stats(c, scores, experts):
    """One expert layer's routing counters, float32 scalars with no
    gradient: ``held_assignments`` (of the ``N * k`` assignments, those
    that landed on an expert held here), ``load_max_over_mean`` (the
    fullest held expert's rows over the held experts' mean: what a
    grouped product's slowest group is to the even share), ``balance``
    (the Switch form ``E * sum_e f_e P_e`` over ALL experts, ``f_e`` the
    share of assignments and ``P_e`` the mean score: 1 when even; a
    counter here, no term of a loss). ``scores [N, E]``, ``experts [N,
    k]``."""
    E = c.n_experts
    counts = jnp.zeros((E,), jnp.float32).at[experts.reshape(-1)].add(1.0)
    held = counts[c.expert_first:c.expert_first + c.n_experts_held]
    balance = E * jnp.sum(counts / experts.size * jnp.mean(scores, axis=0))
    return jax.lax.stop_gradient({
        "held_assignments": jnp.sum(held),
        "load_max_over_mean": jnp.max(held)
        / jnp.maximum(jnp.mean(held), 1.0),
        "balance": balance})


def sum_route_stats(per_run):
    """The step's counters from each scan's stacked :func:`route_stats`
    (``[layers of the run]`` a leaf): assignments summed over the expert
    layers, the two ratios their mean."""
    cat = {k: jnp.concatenate([r[k] for r in per_run])
           for k in per_run[0]}
    return {"held_assignments": jnp.sum(cat["held_assignments"]),
            "load_max_over_mean": jnp.mean(cat["load_max_over_mean"]),
            "balance": jnp.mean(cat["balance"])}


@jax.named_scope("moe")
def topk_moe_mlp(c, lp, h, layer=None, stats=False, mesh=None):
    """Dropless top-k expert MLP. ``h [B, S, D]`` (compute dtype) ->
    ``[B, S, D]``. ``lp`` carries ``w_router [D, E]`` and the held
    experts ``we_gate / we_up [E_held, D, F]``, ``we_down [E_held, F,
    D]`` (``E_held`` = ``E`` unless ``experts_held`` is set; no
    ``we_gate`` for ungated experts, ``expert_act`` "relu2"), and with
    ``shared_expert_width`` the shared expert's ``ws_gate / ws_up /
    ws_down``. With ``moe_latent`` R the routed experts are ``[E_held,
    R, F]`` / ``[E_held, F, R]`` between ``w_lat_down [D, R]`` and
    ``w_lat_up [R, D]``, each applied once a token.

    Inside a scan over layers pass ``layer`` (the scan's int32 index)
    and the expert leaves WHOLE, ``[L, E_held, ...]``: the grouped
    product then runs on ``[L * E_held, ...]`` with the rows in layer
    ``layer``'s groups and every other group empty. Scanned like the
    other leaves, each layer's experts would be sliced out of the stack
    — a copy of all of them, in every layer of every step — because a
    grouped product, unlike a plain dot, cannot read its operand through
    the slice. (Training scans the experts with the other leaves,
    ``layer`` None: its float32 masters are cast a layer at a time, which
    reads them through the slice, and a layer's dW is then its own
    experts' and not the whole stack's.)

    ``stats``: returns ``(out, route_stats(...))``, the layer's routing
    counters beside it (training's; the served programs ask for none).
    ``mesh``: the mesh the calling program is partitioned over, where the
    caller has one; of one device, the differentiated grouped products
    may be Pallas kernels (``_grouped_dot``)."""
    dt = c.dtype
    B, S, D = h.shape
    x = h.reshape(B * S, D).astype(dt)
    weights, experts, *scores = route_topk(c, lp, x, scores_too=stats)
    if stats:
        counters = route_stats(c, scores[0], experts)
    xin = x
    if c.moe_latent:
        with jax.named_scope("moe_latent_down"):
            xin = jnp.dot(x, lp["w_lat_down"].astype(dt))
    one_chip = mesh is not None and mesh.size == 1
    if x.shape[0] > _MANY_TOKENS and not x.shape[0] % _MANY_TOKENS:
        y = _routed_experts_in_turns(c, lp, xin, weights, experts, layer,
                                     one_chip)
    else:
        y = _routed_experts(c, lp, xin, weights, experts, layer, one_chip)
    if c.moe_latent:
        with jax.named_scope("moe_latent_up"):
            y = jnp.dot(y.astype(dt), lp["w_lat_up"].astype(dt),
                        preferred_element_type=jnp.float32)
    if c.shared_expert_width:
        y = y + _shared_expert(c, lp, x).astype(jnp.float32)
    y = y.reshape(B, S, D).astype(dt)
    return (y, counters) if stats else y


def topk_moe_param_shapes(c):
    f, held = c.expert_width, c.n_experts_held
    gated = c.expert_act == "swiglu"
    e = c.moe_latent or c.d_model               # the routed experts' width
    shapes = {"w_router": (c.d_model, c.n_experts),
              "we_up": (held, e, f), "we_down": (held, f, e)}
    if gated:
        shapes["we_gate"] = (held, e, f)
    if c.moe_latent:
        shapes.update({"w_lat_down": (c.d_model, e),
                       "w_lat_up": (e, c.d_model)})
    if c.shared_expert_width:
        fs = c.shared_expert_width
        shapes.update({"ws_up": (c.d_model, fs), "ws_down": (fs, c.d_model)})
        if gated:
            shapes["ws_gate"] = (c.d_model, fs)
    return shapes


def topk_moe_logical_axes(c):
    axes = {
        "w_router": ("layers", "embed", None),
        "we_gate": ("layers", "expert", "embed", "mlp"),
        "we_up": ("layers", "expert", "embed", "mlp"),
        "we_down": ("layers", "expert", "mlp", "embed"),
        "w_lat_down": ("layers", "embed", None),
        "w_lat_up": ("layers", None, "embed"),
        "ws_gate": ("layers", "embed", "mlp"),
        "ws_up": ("layers", "embed", "mlp"),
        "ws_down": ("layers", "mlp", "embed")}
    return {name: axes[name] for name in topk_moe_param_shapes(c)}
