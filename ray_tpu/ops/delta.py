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
  place in the layer scan's carry. Plain XLA: ``o = S'^T q + (k . q)
  delta`` lets one pass over the decayed state sum both ``S'^T k`` and
  ``S'^T q``, and a second writes ``S' + k delta^T``: the state is read
  twice and written once.
- :func:`gated_delta_step`: the token-by-token recurrence on a batch of
  states, the tests' oracle.

A token that is not ``live`` has ``g`` and ``beta`` set to 0: the state
passes it unchanged, exactly. Live tokens are a prefix of the call.
"""
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.ssm import put_slot_rows, slot_rows

F32 = jnp.float32
#: the published blocked form's block, in tokens
BLOCK = 64
_HI = jax.lax.Precision.HIGHEST


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


def gated_delta_step(q, k, v, g, beta, state, live):
    """One token a sequence. ``q, k [B, H, dk]``, ``v [B, H, dv]``, ``g,
    beta [B, H]`` float32, ``state [B, dk, H dv]`` float32, ``live [B]``
    bool. Returns (``o [B, H, dv]`` float32, state)."""
    b, h, _ = q.shape
    dv = v.shape[-1]
    g = jnp.where(live[:, None], g.astype(F32), 0.0)
    beta = jnp.where(live[:, None], beta.astype(F32), 0.0)
    qf, kf = q.astype(F32), k.astype(F32)
    kl, ql = _over_lanes(kf, dv), _over_lanes(qf, dv)
    state = state * _per_lane(jnp.exp(g), dv)[:, None, :]
    from_k = jnp.sum(state * kl, axis=1)                   # S'^T k
    from_q = jnp.sum(state * ql, axis=1)                   # S'^T q
    delta = _per_lane(beta, dv) * (v.astype(F32).reshape(b, h * dv) - from_k)
    state = state + kl * delta[:, None, :]
    o = from_q + _per_lane(jnp.sum(kf * qf, axis=-1), dv) * delta
    return o.reshape(b, h, dv), state


def gated_delta_step_slots(q, k, v, g, beta, states, layer, slots, live,
                           fresh):
    """One token a sequence on the WHOLE per-slot array. ``states
    [layers, slots, dk, H dv]`` float32, ``layer`` an int32 scalar
    (traced inside a layer scan), ``slots [B]`` int32 each row's slot
    (None: row b is slot b; a slot once a call), ``fresh [B]`` bool (a
    fresh row starts from zeros whatever its slot held), the rest as
    :func:`gated_delta_step`. Returns (``o [B, H, dv]`` float32,
    ``states`` with layer ``layer`` of the rows' slots updated)."""
    o, rows = gated_delta_step(
        q, k, v, g, beta, slot_rows(states, layer, slots, q.shape[0], fresh),
        live)
    return o, put_slot_rows(states, layer, slots, rows)


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
