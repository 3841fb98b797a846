"""The gated delta rule (Gated DeltaNet: Yang, Kautz & Hatamizadeh 2024,
arXiv 2412.06464), as serving needs it: a state comes in and a state
goes out, and a chunk's call hands out the state at boundaries inside it.

A head carries ``S [dk, dv]`` (key width x value width), float32. A
token with decay ``alpha = exp(g)``, ``g <= 0``, write strength ``beta``
(up to 2), key ``k`` (unit length), value ``v`` and query ``q``::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

It READS the state before it writes it (``S^T k``), which Mamba-2's
update (``ops/ssm.py``) does not: the one-token update is two products
with the state, and the blocked form solves a unit lower triangular
system a block (the WY / UT transform).

A sequence's state is kept ``[dk, H * dv]``: the key width on the
sublanes, heads and their value channels as ONE axis on the lanes
(``ops/ssm.py``'s layout and its reason: a program free to order heads
and channels its own way relays the whole array; and 96 is whole
sublane tiles while ``30 x 192`` is whole lanes, where ``[30 x 96, 192]``
would pad every row of 192 to 256). The one-token update's sums over
``dk`` are then plain adds of vregs. The layout is private to this
module, ``init_kv_cache``'s per-slot arrays and ``_delta_sublayer``.

- :func:`gated_delta_chunk_scan`: ``T`` tokens a sequence in the
  published blocked form (block ``L`` = 64). What does not depend on the
  state, the block's ``A``, the solve ``(I + A)^-1 [beta K e^c | beta
  V]`` and ``tril(Q K^T)``, is computed for all blocks at once; a
  ``lax.scan`` over the blocks then carries the state ``[B, H, dk, dv]``
  through three small products a block. With ``snap_every`` the scan is
  two deep, and the outer one hands out the state it holds at every
  ``snap_every`` tokens. Everything in float32 at full precision: the
  recurrence is 3% of a chunk's operations by count.
- :func:`gated_delta_step_slots`: one token a sequence over the WHOLE
  per-slot array ``[layers, slots, dk, H dv]`` with a layer index, in
  place, as every pool is handed to its kernel. On a TPU a Pallas kernel
  (``impl``, resolved as the attention kernels' are, op ``"delta_step"``
  of ``ops.attention.dispatch_log``): a grid step holds one ``[dk,
  lanes]`` tile of one slot's state, ``lanes`` whole heads and whole
  128-lane tiles (at ``30 x 192`` a row's whole state, 2.2 MB), decays
  it, sums ``S'^T k`` and ``S'^T q`` down its sublanes, and writes ``S' +
  k delta^T`` where it lay (the array is aliased through the layer
  scan's carry); ``o = S'^T q + (k . q) delta``. One read, one write.
  Off the chip, or where the shapes do not tile, the plain form: slice
  the layer, :func:`gated_delta_step`, write it back, which XLA makes
  several fusions of, beside two ``[B, dk, H dv]`` broadcasts of ``k``
  and ``q`` that are each the size of the rows' states.
- :func:`gated_delta_step`: the token-by-token recurrence on a batch of
  states, elementwise ``jax.numpy``: the CPU's form and the tests'
  oracle.

A token that is not ``live`` has ``g`` and ``beta`` set to 0: the state
passes it unchanged, exactly. Live tokens are a prefix of the call.

Traps the kernel met (PR 57). *Spreading k and q*: a head's key is a
value a sublane AND a head, so beside the state it is ``[dk, H dv]``, as
large as the state: the kernel takes ``[dk, heads]`` a tile (a few KB)
and spreads a column over its head's lanes itself, a column of 128 lanes
at a time (twelve lane broadcasts a head and operand, and one select
where a column holds two heads' halves: 192 is a lane tile and a half).
The products on the MXU instead (a one-hot ``[heads, lanes]`` for the
spread, or ``[k; q] x S``) would stream the state's worth of weights
through it at full float32 precision: several times the bytes' time.
*Rows with nothing to do*: the decode batch is every slot, with or
without a sequence. A row that is neither live nor fresh is not copied
in or out: its grid steps name the tile the step before them held (the
pipeline fetches and writes a tile only when its index changes), and
rows ahead of the first with work name ITS first tile and pass it
through, so that what is written back is what was read; the grid is
therefore walked in order (``"arbitrary"``). With 12 of 16 rows live the
kernel takes 0.102 ms a layer where it took 0.124 updating every row
(my chip run, PR 57). *Aliasing*: as ``ops/ssm.py``; a slot may appear
once in a call.
"""
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.ssm import put_slot_rows, slot_rows

F32 = jnp.float32
#: the published blocked form's block, in tokens
BLOCK = 64
_HI = jax.lax.Precision.HIGHEST
#: the most a state tile of the one-token kernel holds, in bytes: 2.5
#: MiB (two of them come in and two go out at a time, in 16 MiB)
_STEP_TILE_BYTES = 2560 * 1024


def _per_lane(a, dv: int):
    """``[..., H]`` a head -> ``[..., H dv]`` a value channel."""
    return jnp.repeat(a, dv, axis=-1)


def _over_lanes(a, dv: int):
    """``[B, H, dk]`` a head -> ``[B, dk, H dv]``: a head's key (or
    query) beside every value channel of that head."""
    b, h, dk = a.shape
    return jnp.broadcast_to(jnp.swapaxes(a, 1, 2)[..., None],
                            (b, dk, h, dv)).reshape(b, dk, h * dv)


def to_heads(state, h: int):
    """``[B, dk, H dv]`` as stored -> ``[B, H, dk, dv]``."""
    b, dk, hv = state.shape
    return jnp.transpose(state.reshape(b, dk, h, hv // h), (0, 2, 1, 3))


def from_heads(state):
    """``[B, H, dk, dv]`` -> ``[B, dk, H dv]`` as stored."""
    b, h, dk, dv = state.shape
    return jnp.transpose(state, (0, 2, 1, 3)).reshape(b, dk, h * dv)


def _step_rows(q, k, v, g, beta, live):
    """What a value channel brings to its column of the state, ``[B, H
    dv]`` float32 each: its head's decay ``exp(g)`` and ``beta`` (``g``
    and ``beta`` 0 where the row is not live), its ``v``, and its head's
    ``k . q``."""
    b, h, dv = v.shape
    g = jnp.where(live[:, None], g.astype(F32), 0.0)
    beta = jnp.where(live[:, None], beta.astype(F32), 0.0)
    kq = jnp.sum(k.astype(F32) * q.astype(F32), axis=-1)
    return (_per_lane(jnp.exp(g), dv), _per_lane(beta, dv),
            v.astype(F32).reshape(b, h * dv), _per_lane(kq, dv))


def gated_delta_step(q, k, v, g, beta, state, live):
    """One token a sequence. ``q, k [B, H, dk]``, ``v [B, H, dv]``, ``g,
    beta [B, H]`` float32, ``state [B, dk, H dv]`` float32, ``live [B]``
    bool. Returns (``o [B, H, dv]`` float32, state)."""
    b, h, dv = v.shape
    decay, beta, v, kq = _step_rows(q, k, v, g, beta, live)
    kl, ql = _over_lanes(k.astype(F32), dv), _over_lanes(q.astype(F32), dv)
    state = state * decay[:, None, :]
    from_k = jnp.sum(state * kl, axis=1)                   # S'^T k
    from_q = jnp.sum(state * ql, axis=1)                   # S'^T q
    delta = beta * (v - from_k)
    state = state + kl * delta[:, None, :]
    return (from_q + kq * delta).reshape(b, h, dv), state


def _step_kernel(layer_ref, slot_ref, flag_ref, rows_ref, kq_ref, state_ref,
                 o_ref, out_ref, *, dv: int):
    """One ``[dk, lanes]`` tile of one slot's state: read once, decayed,
    ``S'^T k`` and ``S'^T q`` summed down its sublanes, written once. A
    column of 128 lanes at a time: its heads' keys and queries are spread
    over the lanes here (``kq_ref [dk, 2 heads]``: a head's key, then its
    query, a column each). ``rows_ref [4, lanes]``: ``exp(g)``, ``beta``,
    ``v`` and ``k . q`` a lane. ``flag_ref``: 0 a row to update, 1 from
    zeros, 2 nothing to do (the tile is the one the step before held), 3
    the tile passes through."""
    del layer_ref, slot_ref                      # the index maps read them
    flag = flag_ref[pl.program_id(0)]
    dk, lanes = state_ref.shape
    heads = kq_ref.shape[1] // 2
    width = 128 if lanes % 128 == 0 else lanes

    @pl.when(flag < 2)
    def _update():
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)

        @functools.cache
        def spread(col: int):
            """Column ``col`` of ``kq_ref`` over ``width`` lanes."""
            return jnp.broadcast_to(kq_ref[:, col:col + 1], (dk, width))

        def beside(first: int, lo: int):
            """The heads' columns of ``kq_ref`` from ``first`` on, each
            beside the lanes of its head, for lanes ``lo .. lo + width``
            (the heads are ``dv`` wide)."""
            last = (lo + width - 1) // dv
            w = spread(first + last)
            for h in range(last - 1, lo // dv - 1, -1):
                w = jnp.where(lane < (h + 1) * dv - lo, spread(first + h), w)
            return w

        for lo in range(0, lanes, width):
            at = slice(lo, lo + width)
            kl, ql = beside(0, lo), beside(heads, lo)
            state = jnp.where(flag == 1, 0.0, state_ref[:, at]) \
                * rows_ref[0:1, at]
            from_k = jnp.sum(state * kl, axis=0, keepdims=True)
            from_q = jnp.sum(state * ql, axis=0, keepdims=True)
            delta = rows_ref[1:2, at] * (rows_ref[2:3, at] - from_k)
            out_ref[:, at] = state + kl * delta
            o_ref[:, at] = from_q + rows_ref[3:4, at] * delta

    @pl.when(flag >= 2)
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(flag == 3)
    def _pass():
        out_ref[...] = state_ref[...]


def _step_lanes(dk: int, h: int, dv: int) -> int:
    """Lanes of the kernel's state tile ``[dk, lanes]``: whole heads and
    whole 128-lane tiles, the widest such divisor of ``H dv`` that holds
    ``_STEP_TILE_BYTES`` or less (the narrowest where none does). An
    interpreted call of a shape that does not tile: one tile."""
    hp = h * dv
    if hp % 128:
        return hp
    unit = math.lcm(dv, 128)
    fits = [w for w in range(unit, hp + 1, unit) if hp % w == 0]
    return max([w for w in fits if 4 * dk * w <= _STEP_TILE_BYTES]
               or fits[:1])


def step_choice(impl: str, dk: int, h: int, dv: int,
                record: bool = False) -> str:
    """What the one-token update of states ``[dk, H dv]`` resolves to
    under ``impl``: "kernel" | "interpret" | "reference". A tile is
    ``[dk, lanes]`` float32: ``dk`` whole sublane tiles, ``lanes`` whole
    heads and whole lane tiles."""
    from ray_tpu.ops.attention import _resolve
    unfit = None
    if (h * dv) % 128:
        unfit = f"heads x value_dim {h * dv} % 128 != 0"
    elif dk % 8:
        unfit = f"key_dim {dk} % 8 != 0"
    return _resolve("delta_step", impl, "kernel", unfit, record)


def gated_delta_step_slots(q, k, v, g, beta, states, layer, slots, live,
                           fresh, impl: str = "auto"):
    """One token a sequence on the WHOLE per-slot array. ``states
    [layers, slots, dk, H dv]`` float32, ``layer`` an int32 scalar
    (traced inside a layer scan), ``slots [B]`` int32 each row's slot
    (None: row b is slot b; a slot once a call), ``fresh [B]`` bool (a
    fresh row starts from zeros whatever its slot held), the rest as
    :func:`gated_delta_step`. ``impl``: "auto" | "kernel" | "interpret" |
    "reference" (``ops/attention.py``'s rule). Returns (``o [B, H, dv]``
    float32, ``states`` with layer ``layer`` of the rows' slots updated;
    under the kernel the ``o`` of a row that is neither live nor fresh is
    zeros)."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    hp = h * dv
    choice = step_choice(impl, dk, h, dv, record=True)
    if choice == "reference":
        o, rows = gated_delta_step(
            q, k, v, g, beta, slot_rows(states, layer, slots, b, fresh), live)
        return o, put_slot_rows(states, layer, slots, rows)
    lanes = _step_lanes(dk, h, dv)
    tiles = hp // lanes
    if slots is None:
        slots = jnp.arange(b, dtype=jnp.int32)
    rows = jnp.stack(_step_rows(q, k, v, g, beta, live), axis=1)

    def by_tile(a):        # [B, H, dk] -> [B, tiles, dk, heads a tile]
        return jnp.swapaxes(
            a.astype(F32).reshape(b, tiles, h // tiles, dk), 2, 3)
    kq = jnp.concatenate([by_tile(k), by_tile(q)], axis=-1)
    # A row that is neither live nor fresh keeps its state, and its grid
    # steps name the tile the step before them held (the last tile of the
    # nearest row above that has work), so nothing is copied in or out
    # for it; ahead of the first such row they name ITS first tile and
    # pass it through (row 0's where no row has work).
    busy = live | fresh
    idx = jnp.arange(b, dtype=jnp.int32)
    above = jax.lax.cummax(jnp.where(busy, idx, -1))
    flag = jnp.where(busy, fresh.astype(jnp.int32),
                     jnp.where(above >= 0, 2, 3))
    slots = slots.astype(jnp.int32)[
        jnp.where(above >= 0, above, jnp.argmax(busy))]

    def tile_at(i, j, layer, slot, flag):
        f = flag[i]
        return (layer[0], slot[i], 0,
                jnp.where(f < 2, j, jnp.where(f == 2, tiles - 1, 0)))
    row = pl.BlockSpec((None, 1, lanes), lambda i, j, *_: (i, 0, j))
    tile = pl.BlockSpec((None, None, dk, lanes), tile_at)
    o, states = pl.pallas_call(
        functools.partial(_step_kernel, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, tiles),
            in_specs=[pl.BlockSpec((None, 4, lanes),
                                   lambda i, j, *_: (i, 0, j)),
                      pl.BlockSpec((None, None, dk, 2 * h // tiles),
                                   lambda i, j, *_: (i, j, 0, 0)),
                      tile],
            out_specs=[row, tile]),
        out_shape=[jax.ShapeDtypeStruct((b, 1, hp), F32),
                   jax.ShapeDtypeStruct(states.shape, F32)],
        # argument 5 (the three prefetched scalars count) is output 1
        input_output_aliases={5: 1},
        # a tile revisited is neither fetched nor written again: in order
        compiler_params=None if choice == "interpret"
        else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=choice == "interpret", name="delta_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots, flag, rows, kq,
      states)
    return o.reshape(b, h, dv), states


def _blocks(a, n: int, q: int):
    """``[B, n q, H, ...]`` -> ``[n, B, H, q, ...]``."""
    b = a.shape[0]
    a = a.reshape((b, n, q) + a.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)


def gated_delta_chunk_scan(q, k, v, g, beta, state_in, live,
                           block: int = BLOCK,
                           snap_every: Optional[int] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                      Optional[jnp.ndarray]]:
    """``T`` tokens a sequence, blocked. ``q, k [B, T, H, dk]`` (the key
    of unit length, the query scaled), ``v [B, T, H, dv]``, ``g, beta [B,
    T, H]`` float32, ``state_in [B, dk, H dv]`` float32, ``live [B, T]``
    bool (a prefix of each row). ``T`` is one block, or is padded here to
    whole blocks of ``block``. With ``snap_every`` (tokens; a multiple of
    ``block`` that divides ``T``) ``snaps [T / snap_every, B, dk, H dv]``
    is the state after local tokens ``snap_every, 2 snap_every, ..., T``.
    Returns (``o [B, T, H, dv]`` float32, ``state_out``, ``snaps`` or
    None): the outputs of tokens that are not live are finite and mean
    nothing."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    L = min(block, t)
    if snap_every is not None and (snap_every % L or t % snap_every):
        raise ValueError(f"snap_every {snap_every}: a multiple of the "
                         f"block {L} that divides the call's {t} tokens")
    g = jnp.where(live[..., None], g.astype(F32), 0.0)
    beta = jnp.where(live[..., None], beta.astype(F32), 0.0)
    pad = -t % L
    q, k, v = (a.astype(F32) for a in (q, k, v))
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (t + pad) // L
    qb, kb, vb, gb, bb = (_blocks(a, n, L) for a in (q, k, v, g, beta))
    # ---- what no state enters: every block at once. [n, B, H, L, ...]
    c = jnp.cumsum(gb, axis=-1)                     # inclusive, <= 0
    gap = c[..., :, None] - c[..., None, :]         # c_i - c_j
    idx = jnp.arange(L)
    below = idx[:, None] > idx[None, :]
    kk = jnp.einsum("nbhid,nbhjd->nbhij", kb, kb, precision=_HI)
    A = jnp.where(below, bb[..., :, None] * kk
                  * jnp.exp(jnp.where(below, gap, 0.0)), 0.0)
    ec = jnp.exp(c)[..., None]
    rhs = jnp.concatenate([kb * ec, vb], axis=-1) * bb[..., None]
    sol = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(L, dtype=F32), rhs, lower=True, unit_diagonal=True)
    W, U = sol[..., :dk], sol[..., dk:]
    upto = idx[:, None] >= idx[None, :]
    P = jnp.where(upto, jnp.einsum("nbhid,nbhjd->nbhij", qb, kb,
                                   precision=_HI)
                  * jnp.exp(jnp.where(upto, gap, 0.0)), 0.0)
    qe = qb * ec
    c_end = c[..., -1:]
    kd = kb * jnp.exp(c_end - c)[..., None]
    e_end = jnp.exp(c_end)[..., None]               # [n, B, H, 1, 1]

    # ---- what the state enters: block by block
    def one(S, blk):
        W, U, P, qe, kd, e_end = blk
        vp = U - jnp.einsum("bhld,bhdv->bhlv", W, S, precision=_HI)
        o = jnp.einsum("bhld,bhdv->bhlv", qe, S, precision=_HI) \
            + jnp.einsum("bhij,bhjv->bhiv", P, vp, precision=_HI)
        S = e_end * S + jnp.einsum("bhld,bhlv->bhdv", kd, vp,
                                   precision=_HI)
        return S, o

    xs = (W, U, P, qe, kd, e_end)
    S = to_heads(state_in.astype(F32), h)
    if snap_every is None:
        S, o = jax.lax.scan(one, S, xs)
        snaps = None
    else:
        per = snap_every // L

        def stride(S, grp):
            S, o = jax.lax.scan(one, S, grp)
            return S, (o, from_heads(S))
        S, (o, snaps) = jax.lax.scan(
            stride, S, tuple(a.reshape((n // per, per) + a.shape[1:])
                             for a in xs))
        o = o.reshape((n,) + o.shape[2:])
    # [n, B, H, L, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * L, h, dv)
    return o[:, :t], from_heads(S), snaps


def conv_tails_at(x, tail, every: int):
    """The convolution's tail at each boundary of a call: ``x [B, T,
    W]`` the new raw inputs, ``tail [B, K-1, W]`` those just before them.
    Returns ``[T / every, B, K-1, W]``: the last ``K - 1`` inputs up to
    local token ``every, 2 every, ..., T``."""
    k1 = tail.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    # inputs j - (K-1) .. j - 1 of the call are rows j .. j + K - 2 of ext
    rows = (jnp.arange(every, x.shape[1] + 1, every)[:, None]
            + jnp.arange(k1)[None, :])
    return jnp.moveaxis(ext[:, rows], 1, 0).astype(tail.dtype)
