"""The Mamba-2 mixer's recurrence (state-space duality, Dao & Gu 2024),
as serving needs it: a state comes in and a state goes out.

A head carries ``H [P, N]`` (head width x state width), float32. A token
with step ``dt > 0``, head decay ``A < 0``, input ``x [P]`` and the
group's ``B, C [N]``::

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t
    y_t = H_t C_t + D x_t

- :func:`ssd_chunk_scan`: a call of ``T`` tokens a sequence in the
  blocked form: inside a block of ``block`` tokens the outputs are one
  masked ``(C B^T) * decay`` product with ``x``, between blocks the state
  is carried (a ``lax.scan`` over the blocks). Decays and their running
  sums are float32; the block's products take ``x``'s dtype on the MXU
  and accumulate in float32.
- :func:`ssd_step`: one token, elementwise on the state.
- :func:`causal_conv`: the depthwise causal convolution ahead of the
  scan, with the last ``K - 1`` inputs carried as a tail.

A token that is not ``live`` (a chunk's zero padding, a decode row with
no sequence) has its ``dt`` set to 0: the state passes it unchanged
(``exp(0) H + 0``, exactly) and the tail is taken at the last live token.
Live tokens are a prefix of the call. One group of ``B`` / ``C`` for all
heads (``n_groups`` 1). Plain XLA; a Pallas kernel is later work and
would keep these signatures.
"""
from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def causal_conv(x, tail, w, b, n_live) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal convolution, then SiLU. ``x [B, T, W]`` the new
    inputs, ``tail [B, K-1, W]`` the inputs just before them (zeros
    before position 0), ``w [W, K]`` (tap ``K-1`` meets the current
    input), ``b [W]``, ``n_live [B]`` how many of the ``T`` are live.
    Returns (``[B, T, W]`` in ``x``'s dtype, the new tail: the last ``K -
    1`` inputs up to the last live one, the old tail's end where fewer
    are live)."""
    k = w.shape[-1]
    t = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    acc = b.astype(F32)
    for j in range(k):
        acc = acc + ext[:, j:j + t].astype(F32) * w[:, j].astype(F32)
    out = jax.nn.silu(acc).astype(x.dtype)
    # inputs n_live - (K-1) .. n_live - 1 of the call are rows n_live ..
    # n_live + K - 2 of ext
    rows = n_live[:, None] + jnp.arange(k - 1, dtype=n_live.dtype)
    new_tail = jnp.take_along_axis(ext, rows[..., None], axis=1)
    return out, new_tail.astype(tail.dtype)


def ssd_step(x, dt, A, B, C, D, state, live):
    """One token a sequence. ``x [B, H, P]``, ``dt [B, H]`` float32 (past
    its softplus), ``A, D [H]``, ``B, C [B, N]``, ``state [B, H, P, N]``
    float32, ``live [B]`` bool. Returns (``y [B, H, P]`` float32, state)."""
    dt = jnp.where(live[:, None], dt.astype(F32), 0.0)
    xf = x.astype(F32)
    decay = jnp.exp(dt * A.astype(F32))
    state = state * decay[..., None, None] \
        + (dt[..., None] * xf)[..., None] * B.astype(F32)[:, None, None, :]
    y = jnp.sum(state * C.astype(F32)[:, None, None, :], axis=-1)
    return y + D.astype(F32)[:, None] * xf, state


def _block(carry, blk, A):
    """One block of the blocked form: the state that came in, the
    block's ``x [B, Q, H, P]``, ``dt [B, Q, H]`` (0 where not live),
    ``B, C [B, Q, N]`` -> (state out, ``y [B, Q, H, P]`` float32 without
    the ``D x`` term)."""
    state = carry                                   # [B, H, P, N] f32
    x, dt, Bm, Cm = blk
    q = x.shape[1]
    dt_h = jnp.moveaxis(dt, 1, 2)                   # [B, H, Q]
    cs = jnp.cumsum(dt_h * A[:, None], axis=-1)     # inclusive, <= 0
    # inside the block: y_t += sum_{s <= t} exp(cs_t - cs_s) dt_s
    #                          (C_t . B_s) x_s
    gram = jnp.einsum("btn,bsn->bts", Cm, Bm, preferred_element_type=F32)
    causal = jnp.tril(jnp.ones((q, q), bool))
    gap = jnp.where(causal, cs[..., :, None] - cs[..., None, :], -jnp.inf)
    mix = jnp.exp(gap) * gram[:, None] * dt_h[..., None, :]   # [B, H, t, s]
    y = jnp.einsum("bhts,bshp->bthp", mix.astype(x.dtype), x,
                   preferred_element_type=F32)
    # what the state that came in adds: exp(cs_t) (H_in C_t)
    from_state = jnp.einsum("btn,bhpn->bthp", Cm.astype(F32), state,
                            preferred_element_type=F32)
    y = y + from_state * jnp.moveaxis(jnp.exp(cs), 1, 2)[..., None]
    # the state that goes out: exp(cs_Q) H_in + sum_s exp(cs_Q - cs_s)
    #                          dt_s x_s (outer) B_s
    w = jnp.exp(cs[..., -1:] - cs) * dt_h                     # [B, H, s]
    xw = x.astype(F32) * jnp.moveaxis(w, 1, 2)[..., None]     # [B, s, H, P]
    state = state * jnp.exp(cs[..., -1])[..., None, None] \
        + jnp.einsum("bshp,bsn->bhpn", xw.astype(x.dtype), Bm,
                     preferred_element_type=F32)
    return state, y


def ssd_chunk_scan(x, dt, A, B, C, D, state_in, live, block: int = 256):
    """``T`` tokens a sequence, blocked. ``x [B, T, H, P]``, ``dt [B, T,
    H]`` float32 (past its softplus), ``A, D [H]``, ``B, C [B, T, N]``,
    ``state_in [B, H, P, N]`` float32, ``live [B, T]`` bool (a prefix of
    each row). ``T`` is one block, or is padded here to whole blocks of
    ``block``. Returns (``y [B, T, H, P]`` float32, ``state_out``): the
    outputs of tokens that are not live are finite and mean nothing."""
    b, t, h, p = x.shape
    dt = jnp.where(live[..., None], dt.astype(F32), 0.0)
    q = min(block, t)
    pad = -t % q
    if pad:
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, B, C))
    n = (t + pad) // q
    A = A.astype(F32)

    def blocks(a):                 # [B, n q, ...] -> [n, B, q, ...]
        return jnp.moveaxis(a.reshape((b, n, q) + a.shape[2:]), 1, 0)
    if n == 1:
        state, y = _block(state_in.astype(F32), (x, dt, B, C), A)
    else:
        state, y = jax.lax.scan(
            lambda carry, blk: _block(carry, blk, A),
            state_in.astype(F32), tuple(blocks(a) for a in (x, dt, B, C)))
        y = jnp.moveaxis(y, 0, 1).reshape(b, n * q, h, p)
    y = y[:, :t] + D.astype(F32)[:, None] * x[:, :t].astype(F32)
    return y, state
