"""The Mamba-2 mixer's recurrence (state-space duality, Dao & Gu 2024),
as serving needs it: a state comes in and a state goes out.

A head carries ``H [P, N]`` (head width x state width), float32. A token
with step ``dt > 0``, head decay ``A < 0``, input ``x [P]`` and the
group's ``B, C [N]``::

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t
    y_t = H_t C_t + D x_t

A sequence's state is kept ``[N, H * P]``: the state's width on the
sublanes, heads and channels as ONE axis on the lanes (a program that
ordered heads and channels its own way relaid the whole array). So the
output's sum over ``N`` is plain adds of vregs, not a reduction across
lanes, a head's decay and a channel's ``dt x`` are rows broadcast down
the sublanes, ``B`` and ``C`` one column a sequence, and the blocked
form's two products with the state are plain ``[T, N] x [N, H P]`` and
``[N, S] x [S, H P]`` matmuls. The layout is private to this module,
``init_kv_cache``'s per-slot arrays and ``_scan_sublayer``.

- :func:`ssd_chunk_scan`: a call of ``T`` tokens a sequence in the
  blocked form: inside a block of ``block`` tokens the outputs are one
  masked ``(C B^T) * decay`` product with ``x``, between blocks the state
  is carried (a ``lax.scan`` over the blocks). Decays and their running
  sums are float32; the block's products take ``x``'s dtype on the MXU
  and accumulate in float32. Plain XLA on every platform.
- :func:`ssd_step_slots`: one token a sequence over the WHOLE per-slot
  array ``[layers, slots, N, H P]`` with a layer index, in place, as
  every pool is handed to its kernel. On a TPU a Pallas kernel
  (``impl``, resolved as the attention kernels' are, op ``"ssm_step"``
  of ``ops.attention.dispatch_log``): a grid step holds one ``[N,
  lanes]`` tile of one slot's state, reads it once, writes it once where
  it lay (the array is aliased through the layer scan's carry) and sums
  the output from the tile it holds. Off the chip, or where the shapes
  do not tile, the plain form: slice the layer, :func:`ssd_step`, write
  it back. XLA makes two fusions of that and reads the state twice.
- :func:`ssd_step`: the one-token update on a batch of states,
  elementwise ``jax.numpy``: the CPU's form and the tests' oracle.
- :func:`causal_conv`: the depthwise causal convolution ahead of the
  scan, with the last ``K - 1`` inputs carried as a tail.

A token that is not ``live`` (a chunk's zero padding, a decode row with
no sequence) has its ``dt`` set to 0: the state passes it unchanged
(``exp(0) H + 0``, exactly) and the tail is taken at the last live token.
Live tokens are a prefix of the call.

``B`` and ``C`` come a GROUP of heads (``n_groups``): ``[.., G, N]``,
head ``h`` reads group ``h // (H / G)``, so a group is ``H P / G``
consecutive lanes of the state (one group: ``G`` 1).

Traps the kernel met (PR 53). *Tiling*: with the state ``[H P, N]`` a
head's decay and a channel's ``dt x`` are a value a sublane and the sum
runs across lanes: three XLU or MXU passes a tile (1.39 ms a layer as
MXU products at full precision against 0.84 for this layout; XLA's two
fusions 1.54). *The reduction*: over sublanes it is 15 adds of vregs a
lane column and one small sublane fold. *Aliasing*: the state is an
input aliased to an output (``input_output_aliases`` counts the scalar
prefetch arguments too) and the layer scan's carry is then updated
where it lies: the compiled step has no temporary of the state's size
(``tests/ops/test_tpu_lowering.py``). A slot may appear once in a call.
"""
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: lanes of the kernel's state tile: the widest of these that divides
#: ``H P`` (level from 1,024 to 4,096 on a v5e, 10% behind at 512)
_STEP_LANES = (2048, 1024, 512, 256, 128)


def causal_conv(x, tail, w, b, n_live) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal convolution, then SiLU. ``x [B, T, W]`` the new
    inputs, ``tail [B, K-1, W]`` the inputs just before them (zeros
    before position 0), ``w [W, K]`` (tap ``K-1`` meets the current
    input), ``b [W]`` (None: no bias), ``n_live [B]`` how many of the ``T``
    are live.
    Returns (``[B, T, W]`` in ``x``'s dtype, the new tail: the last ``K -
    1`` inputs up to the last live one, the old tail's end where fewer
    are live)."""
    k = w.shape[-1]
    t = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    acc = 0.0 if b is None else b.astype(F32)
    for j in range(k):
        acc = acc + ext[:, j:j + t].astype(F32) * w[:, j].astype(F32)
    out = jax.nn.silu(acc).astype(x.dtype)
    # inputs n_live - (K-1) .. n_live - 1 of the call are rows n_live ..
    # n_live + K - 2 of ext
    rows = n_live[:, None] + jnp.arange(k - 1, dtype=n_live.dtype)
    new_tail = jnp.take_along_axis(ext, rows[..., None], axis=1)
    return out, new_tail.astype(tail.dtype)


def _per_lane(a, p: int):
    """``[..., H]`` a head -> ``[..., H P]`` a channel."""
    return jnp.repeat(a, p, axis=-1)


def _group_lanes(a, hp: int):
    """``[B, G, N]`` -> ``[B, N, H P]`` float32, a channel its group's
    column (``[B, N, 1]``, broadcast, where there is one group)."""
    g = a.shape[1]
    a = jnp.swapaxes(a.astype(F32), 1, 2)
    return a if g == 1 else jnp.repeat(a, hp // g, axis=-1)


def ssd_step(x, dt, A, B, C, D, state, live):
    """One token a sequence. ``x [B, H, P]``, ``dt [B, H]`` float32 (past
    its softplus), ``A, D [H]``, ``B, C [B, G, N]``, ``state [B, N, H P]``
    float32, ``live [B]`` bool. Returns (``y [B, H, P]`` float32, state)."""
    b, h, p = x.shape
    decay, dtx, skip = _step_rows(x, dt, A, D, live)
    Bl, Cl = (_group_lanes(a, h * p) for a in (B, C))
    state = state * decay[:, None, :] + Bl * dtx[:, None, :]
    y = jnp.sum(state * Cl, axis=1)
    return (y + skip).reshape(b, h, p), state


def _step_rows(x, dt, A, D, live):
    """What a channel brings to its column of the state, ``[B, H P]``
    float32 each: its head's decay ``exp(dt A)``, ``dt x`` and ``D x``,
    with ``dt`` 0 where the row is not live."""
    b, h, p = x.shape
    dt = jnp.where(live[:, None], dt.astype(F32), 0.0)
    xf = x.astype(F32)
    return (_per_lane(jnp.exp(dt * A.astype(F32)), p),
            (dt[..., None] * xf).reshape(b, h * p),
            (D.astype(F32)[:, None] * xf).reshape(b, h * p))


def slot_rows(arr, layer, slots, b: int, fresh):
    """Rows ``(layer, slot)`` of a per-slot array ``[layers, slots,
    ...]`` for a batch of ``b`` (``slots [B]``; None: row b is slot b),
    zeros where the row is ``fresh [B]``."""
    if slots is None:
        rows = jax.lax.dynamic_slice_in_dim(arr, layer, 1, axis=0)[0, :b]
    else:
        rows = arr[layer, slots]
    return jnp.where(fresh.reshape((b,) + (1,) * (rows.ndim - 1)),
                     jnp.zeros((), rows.dtype), rows)


def put_slot_rows(arr, layer, slots, rows):
    """``arr`` with ``rows`` at ``(layer, slot)``, in place in a scan's
    carry."""
    if slots is None:
        return jax.lax.dynamic_update_slice(
            arr, rows[None].astype(arr.dtype),
            (layer,) + (0,) * (arr.ndim - 1))
    return arr.at[layer, slots].set(rows.astype(arr.dtype))


def _step_kernel(layer_ref, slot_ref, fresh_ref, decay_ref, dtx_ref,
                 skip_ref, b_ref, c_ref, state_ref, y_ref, out_ref):
    """One ``[N, lanes]`` tile of one slot's state: read once, written
    once, the output summed from the tile held."""
    del layer_ref, slot_ref                      # the index maps read them
    state = jnp.where(fresh_ref[pl.program_id(0)] != 0, 0.0, state_ref[...])
    state = state * decay_ref[...] + b_ref[...] * dtx_ref[...]
    out_ref[...] = state
    y_ref[...] = jnp.sum(state * c_ref[...], axis=0, keepdims=True) \
        + skip_ref[...]


def step_choice(impl: str, n: int, hp: int, record: bool = False,
                groups: int = 1) -> str:
    """What the one-token update of states ``[N, H P]`` resolves to under
    ``impl``: "kernel" | "interpret" | "reference". A tile is ``[N,
    lanes]`` float32: ``N`` whole sublane tiles, whole lanes of ONE of
    the ``groups`` (it reads one column of ``B`` and of ``C``)."""
    from ray_tpu.ops.attention import _resolve
    unfit = None
    if hp % 128:
        unfit = f"heads x head_dim {hp} % 128 != 0"
    elif hp // groups % 128:
        unfit = f"a group's channels {hp} / {groups} % 128 != 0"
    elif n % 8:
        unfit = f"state {n} % 8 != 0"
    return _resolve("ssm_step", impl, "kernel", unfit, record)


def ssd_step_slots(x, dt, A, B, C, D, states, layer, slots, live, fresh,
                   impl: str = "auto"):
    """One token a sequence on the WHOLE per-slot array. ``states
    [layers, slots, N, H P]`` float32, ``layer`` an int32 scalar (traced
    inside a layer scan), ``slots [B]`` int32 each row's slot (None: row
    b is slot b; a slot once a call), ``live``, ``fresh [B]`` bool (a
    fresh row starts from zeros whatever its slot held), the rest as
    :func:`ssd_step`. ``impl``: "auto" | "kernel" | "interpret" |
    "reference" (``ops/attention.py``'s rule). Returns (``y [B, H, P]``
    float32, ``states`` with layer ``layer`` of the rows' slots
    updated)."""
    b, h, p = x.shape
    n, hp = states.shape[2:]
    g = B.shape[1]
    choice = step_choice(impl, n, hp, record=True, groups=g)
    if choice == "reference":
        y, rows = ssd_step(x, dt, A, B, C, D,
                           slot_rows(states, layer, slots, b, fresh), live)
        return y, put_slot_rows(states, layer, slots, rows)
    # a tile never straddles a group (an interpreted call of a shape
    # that does not tile: one tile a group)
    lanes = next((w for w in _STEP_LANES if (hp // g) % w == 0), hp // g)
    per = hp // g // lanes                       # tiles a group
    if slots is None:
        slots = jnp.arange(b, dtype=jnp.int32)
    row = pl.BlockSpec((None, 1, lanes), lambda i, j, *_: (i, 0, j))
    col = pl.BlockSpec((None, None, n, 1),
                       lambda i, j, *_: (i, j // per, 0, 0))
    tile = pl.BlockSpec(
        (None, None, n, lanes),
        lambda i, j, layer, slot, fresh: (layer[0], slot[i], 0, j))
    y, states = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, hp // lanes),
            in_specs=[row, row, row, col, col, tile],
            out_specs=[row, tile]),
        out_shape=[jax.ShapeDtypeStruct((b, 1, hp), F32),
                   jax.ShapeDtypeStruct(states.shape, F32)],
        # argument 8 (the three prefetched scalars count) is output 1
        input_output_aliases={8: 1},
        compiler_params=None if choice == "interpret"
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=choice == "interpret", name="ssm_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32),
      *(a.reshape(b, 1, hp) for a in _step_rows(x, dt, A, D, live)),
      B.astype(F32).reshape(b, g, n, 1), C.astype(F32).reshape(b, g, n, 1),
      states)
    return y.reshape(b, h, p), states


def _each_group(fn, g: int):
    """``fn(i)`` of every group, side by side on the last axis (the
    lanes): a group is a slice of whole lane tiles, which a product reads
    where it lies; a reshape of the lanes into ``[G, lanes / G]`` would
    relay the state."""
    return jnp.concatenate([fn(i) for i in range(g)], axis=-1)


def _block(carry, blk, A):
    """One block of the blocked form: the state that came in, the
    block's ``x [B, Q, H, P]``, ``dt [B, Q, H]`` (0 where not live),
    ``B, C [B, Q, G, N]`` -> (state out, ``y [B, Q, H, P]`` float32
    without the ``D x`` term). A group's heads are ``H P / G`` consecutive
    lanes of the state: each product with the state is one a group."""
    state = carry                                   # [B, N, H P] f32
    x, dt, Bm, Cm = blk
    b, q, h, p = x.shape
    g = Bm.shape[2]
    lanes = h * p // g                              # a group's channels

    def of(a, i):                  # group i's lanes of ``[.., H P]``
        return a[..., i * lanes:(i + 1) * lanes]
    dt_h = jnp.moveaxis(dt, 1, 2)                   # [B, H, Q]
    cs = jnp.cumsum(dt_h * A[:, None], axis=-1)     # inclusive, <= 0
    # inside the block: y_t += sum_{s <= t} exp(cs_t - cs_s) dt_s
    #                          (C_t . B_s) x_s
    gram = jnp.einsum("btgn,bsgn->bgts", Cm, Bm,
                      preferred_element_type=F32)           # a group
    causal = jnp.tril(jnp.ones((q, q), bool))
    gap = jnp.where(causal, cs[..., :, None] - cs[..., None, :], -jnp.inf)
    mix = (jnp.exp(gap).reshape(b, g, h // g, q, q) * gram[:, :, None]
           ).reshape(b, h, q, q) * dt_h[..., None, :]         # [B, H, t, s]
    y = jnp.einsum("bhts,bshp->bthp", mix.astype(x.dtype), x,
                   preferred_element_type=F32)
    # what the state that came in adds: exp(cs_t) (C_t H_in)
    from_state = _each_group(lambda i: jnp.einsum(
        "btn,bnk->btk", Cm[:, :, i].astype(F32), of(state, i),
        preferred_element_type=F32), g)
    y = y + from_state.reshape(b, q, h, p) \
        * jnp.moveaxis(jnp.exp(cs), 1, 2)[..., None]
    # the state that goes out: exp(cs_Q) H_in + sum_s exp(cs_Q - cs_s)
    #                          B_s (outer) dt_s x_s
    w = jnp.exp(cs[..., -1:] - cs) * dt_h                     # [B, H, s]
    xw = x.astype(F32) * jnp.moveaxis(w, 1, 2)[..., None]     # [B, s, H, P]
    xw = xw.astype(x.dtype).reshape(b, q, h * p)
    state = state * _per_lane(jnp.exp(cs[..., -1]), p)[:, None, :] \
        + _each_group(lambda i: jnp.einsum(
            "bsn,bsk->bnk", Bm[:, :, i], of(xw, i),
            preferred_element_type=F32), g)
    return state, y


def ssd_chunk_scan(x, dt, A, B, C, D, state_in, live, block: int = 256):
    """``T`` tokens a sequence, blocked. ``x [B, T, H, P]``, ``dt [B, T,
    H]`` float32 (past its softplus), ``A, D [H]``, ``B, C [B, T, G, N]``,
    ``state_in [B, N, H P]`` float32, ``live [B, T]`` bool (a prefix of
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
