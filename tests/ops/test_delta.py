"""The blocked gated delta rule, the one-token step over the whole
per-slot array and the tails at a call's boundaries
(``ray_tpu/ops/delta.py``) against the recurrence written token by
token in float64. The oracle keeps a state ``[H, dk, dv]``; the module
``[dk, H dv]``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu.ops.delta as delta_ops
from ray_tpu.ops.attention import dispatch_log
from ray_tpu.ops.delta import (conv_tails_at, from_heads,
                               gated_delta_chunk_scan, gated_delta_step,
                               gated_delta_step_slots, to_heads)
from ray_tpu.ops.ssm import causal_conv

H, DK, DV = 3, 8, 16


def _kept(state):
    """``[B, H, dk, dv]`` as the module keeps it, ``[B, dk, H dv]``."""
    return np.asarray(from_heads(jnp.asarray(state, jnp.float32)))


def _told(state):
    return np.array(to_heads(jnp.asarray(state), H))


def _inputs(seed, b, t, beta_max=2.0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    k = f(b, t, H, DK)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = f(b, t, H, DK)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(DK)
    return dict(
        q=q, k=k, v=f(b, t, H, DV),
        g=-rng.uniform(0.0, 1.6, (b, t, H)).astype(np.float32),
        beta=(beta_max / (1 + np.exp(-f(b, t, H)))).astype(np.float32),
        state=0.5 * f(b, H, DK, DV))


def _sequential(q, k, v, g, beta, state, n_live, keep_at=()):
    """Token by token; a row's tokens past ``n_live`` change nothing.
    ``keep_at``: the states after these many tokens as well."""
    state = state.astype(np.float64).copy()
    o = np.zeros(v.shape, np.float64)
    kept = {n: None for n in keep_at}
    for t in range(q.shape[1]):
        for b in range(q.shape[0]):
            if t >= int(n_live[b]):
                continue
            S = np.exp(g[b, t].astype(np.float64))[:, None, None] * state[b]
            err = v[b, t] - np.einsum("hkv,hk->hv", S, k[b, t])
            S = S + beta[b, t][:, None, None] * np.einsum(
                "hk,hv->hkv", k[b, t], err)
            state[b] = S
            o[b, t] = np.einsum("hkv,hk->hv", S, q[b, t])
        if t + 1 in kept:
            kept[t + 1] = state.copy()
    return o, state, [kept[n] for n in keep_at]


@pytest.mark.parametrize("t,block,n_live,beta_max", [
    (8, 8, (8, 8), 1.0),         # one block
    (24, 8, (24, 24), 1.0),      # whole blocks
    (21, 8, (21, 21), 2.0),      # not a multiple of the block
    (24, 8, (24, 13), 2.0),      # a padded row, beta above 1
    (16, 8, (5, 0), 2.0),        # a row with nothing live
    (5, 64, (5, 3), 2.0),        # a call shorter than a block
    (128, 64, (128, 70), 2.0),   # the published block
])
def test_the_blocked_scan_is_the_recurrence(t, block, n_live, beta_max):
    a = _inputs(t, 2, t, beta_max)
    assert beta_max <= 1.0 or a["beta"].max() > 1.0
    n_live = np.asarray(n_live)
    live = np.arange(t)[None] < n_live[:, None]
    o, state, snaps = jax.jit(gated_delta_chunk_scan,
                              static_argnames="block")(
        a["q"], a["k"], a["v"], a["g"], a["beta"], _kept(a["state"]),
        jnp.asarray(live), block=block)
    assert snaps is None
    want_o, want_state, _ = _sequential(**a, n_live=n_live)
    np.testing.assert_allclose(_told(state), want_state, rtol=3e-5,
                               atol=3e-5)
    got = np.where(live[..., None, None], np.asarray(o), 0.0)
    np.testing.assert_allclose(got, want_o, rtol=3e-5, atol=3e-5)
    for b in np.flatnonzero(n_live == 0):
        assert np.array_equal(_told(state)[b], a["state"][b])


@pytest.mark.parametrize("n_live", [(48, 48), (48, 20), (33, 16)])
def test_snapshots_are_the_states_at_the_boundaries(n_live):
    t, every = 48, 16
    a = _inputs(7, 2, t)
    live = np.arange(t)[None] < np.asarray(n_live)[:, None]
    o, state, snaps = jax.jit(
        gated_delta_chunk_scan, static_argnames=("block", "snap_every"))(
        a["q"], a["k"], a["v"], a["g"], a["beta"], _kept(a["state"]),
        jnp.asarray(live), block=8, snap_every=every)
    want_o, want_state, kept = _sequential(
        **a, n_live=n_live, keep_at=(16, 32, 48))
    assert snaps.shape == (3, 2, DK, H * DV)
    for j, want in enumerate(kept):
        np.testing.assert_allclose(_told(snaps[j]), want, rtol=3e-5,
                                   atol=3e-5)
    np.testing.assert_allclose(_told(state), want_state, rtol=3e-5,
                               atol=3e-5)
    # the same outputs as without snapshots
    plain, _, _ = gated_delta_chunk_scan(
        a["q"], a["k"], a["v"], a["g"], a["beta"], _kept(a["state"]),
        jnp.asarray(live), block=8)
    np.testing.assert_allclose(np.asarray(o), np.asarray(plain), rtol=1e-6,
                               atol=1e-6)


def test_snap_every_is_whole_blocks_of_the_call():
    a = _inputs(1, 1, 24)
    live = jnp.ones((1, 24), bool)
    for every in (12, 16):       # not whole blocks | does not divide 24
        with pytest.raises(ValueError, match="snap_every"):
            gated_delta_chunk_scan(
                a["q"], a["k"], a["v"], a["g"], a["beta"],
                _kept(a["state"]), live, block=8, snap_every=every)


def test_a_state_carried_across_calls_is_one_long_scan():
    a = _inputs(3, 1, 40)
    live = jnp.ones((1, 40), bool)
    whole, end, _ = gated_delta_chunk_scan(
        a["q"], a["k"], a["v"], a["g"], a["beta"], _kept(a["state"]), live,
        block=8)
    cut = {n: a[n][:, :24] for n in ("q", "k", "v", "g", "beta")}
    rest = {n: a[n][:, 24:] for n in ("q", "k", "v", "g", "beta")}
    first, mid, _ = gated_delta_chunk_scan(
        **cut, state_in=_kept(a["state"]), live=live[:, :24], block=8)
    second, last, _ = gated_delta_chunk_scan(
        **rest, state_in=mid, live=live[:, 24:], block=8)
    np.testing.assert_allclose(
        np.concatenate([first, second], 1), whole, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(last, end, rtol=2e-5, atol=2e-5)


def test_the_step_is_the_recurrence_over_the_whole_array():
    """Six decode steps over rows in slots (2, 0, 3) of layer 1 of a
    ``[2, 4, ...]`` array: a fresh row starts from zeros, a dead row and
    every other (layer, slot) keep their bits."""
    b, steps = 3, 6
    a = _inputs(11, b, steps)
    rng = np.random.default_rng(5)
    states = rng.standard_normal((2, 4, DK, H * DV)).astype(np.float32)
    slots = np.array([2, 0, 3], np.int32)
    live = np.array([True, True, False])
    start = _told(states[1, slots])
    start[1] = 0.0                                   # row 1 is fresh
    want_o, want_state, _ = _sequential(
        **{**a, "state": start}, n_live=(steps, steps, 0))
    step = jax.jit(gated_delta_step_slots)
    arr = jnp.asarray(states)
    for t in range(steps):
        fresh = jnp.asarray([False, t == 0, False])
        o, arr = step(a["q"][:, t], a["k"][:, t], a["v"][:, t],
                      a["g"][:, t], a["beta"][:, t], arr, jnp.int32(1),
                      jnp.asarray(slots), jnp.asarray(live), fresh)
        np.testing.assert_allclose(np.asarray(o)[:2], want_o[:2, t],
                                   rtol=3e-5, atol=3e-5)
    arr = np.asarray(arr)
    np.testing.assert_allclose(_told(arr[1, slots])[:2], want_state[:2],
                               rtol=3e-5, atol=3e-5)
    assert np.array_equal(arr[0], states[0])
    assert np.array_equal(arr[1, 1], states[1, 1])
    assert np.array_equal(arr[1, 3], states[1, 3])   # the dead row's slot


def test_row_b_is_slot_b_without_slots():
    a = _inputs(2, 2, 1)
    states = jnp.asarray(np.random.default_rng(0).standard_normal(
        (1, 2, DK, H * DV)).astype(np.float32))
    args = [a[n][:, 0] for n in ("q", "k", "v", "g", "beta")]
    live, fresh = jnp.ones(2, bool), jnp.zeros(2, bool)
    o, out = gated_delta_step_slots(*args, states, jnp.int32(0), None, live,
                                    fresh)
    want_o, want = gated_delta_step(*args, states[0], live)
    np.testing.assert_allclose(o, want_o, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out[0], want, rtol=1e-6, atol=1e-6)


def _step_inputs(seed, b, h, dk, dv):
    """One token a row at any widths: ``q, k, v, g, beta``."""
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    k = f(b, h, dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = f(b, h, dk)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    return (q, k, f(b, h, dv),
            -rng.uniform(0.0, 1.6, (b, h)).astype(np.float32),
            (2.0 / (1 + np.exp(-f(b, h)))).astype(np.float32))


def _dispatched(impl, why):
    """How often ``dispatch_log`` has the one-token update as ``impl``."""
    return sum(d["count"] for d in dispatch_log()
               if (d["op"], d["impl"], d["why"]) == ("delta_step", impl, why))


#: case -> (heads, dk, dv, layers, layer, slots in the array, each row's
#: slot (None: row b is slot b), live, fresh, the tile's bytes or None)
_KERNEL_CASES = {
    # 30 heads of 96 x 192: a column of 128 lanes holds two heads' halves
    "the_published_widths": (30, 96, 192, 2, 1, 3, None, (1, 1, 1),
                             (0, 0, 0), None),
    # four heads in one column of 128 lanes, one tile a row
    "a_small_shape": (4, 8, 32, 2, 0, 3, None, (1, 1, 1), (0, 0, 0), None),
    # ... and three tiles a row, of two heads each
    "tiles_of_whole_heads": (6, 8, 64, 2, 1, 3, None, (1, 1, 1), (0, 0, 0),
                             8 * 128 * 4),
    "the_slots_it_is_told": (4, 8, 96, 2, 1, 5, (4, 0, 2), (1, 1, 1),
                             (0, 0, 0), None),
    "a_fresh_row_over_a_state": (4, 8, 96, 2, 1, 4, (3, 1, 0), (1, 1, 1),
                                 (0, 1, 0), None),
    "a_dead_row_between_live_ones": (6, 8, 64, 2, 1, 4, (2, 0, 3, 1),
                                     (1, 0, 0, 1), (0, 0, 0, 0),
                                     8 * 128 * 4),
    "dead_rows_ahead_of_the_first_live_one": (6, 8, 64, 3, 2, 4, None,
                                              (0, 0, 1, 0), (0, 0, 1, 0),
                                              8 * 128 * 4),
    "no_row_live": (4, 8, 32, 2, 1, 3, (2, 0, 1), (0, 0, 0), (0, 0, 0),
                    None),
    "a_dead_row_that_is_fresh": (4, 8, 32, 2, 0, 3, None, (1, 0, 1),
                                 (0, 1, 0), None),
    # (under the interpreter alone: 48 lanes are one tile of one column)
    "lanes_that_are_no_tile": (H, DK, DV, 2, 1, 4, (2, 0, 3), (1, 1, 0),
                               (0, 1, 0), None),
}


@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_the_step_kernel_is_the_plain_step(case, monkeypatch):
    """The kernel, interpreted, over the WHOLE ``[layers, slots, dk, H
    dv]`` array with a layer index against ``gated_delta_step`` on the
    rows' states: the rows' outputs and states, every other (layer, slot)
    bit for bit, a row with nothing to do bit for bit, and one entry in
    the dispatch record a call."""
    h, dk, dv, layers, layer, n_slots, slots, live, fresh, tile_bytes = \
        _KERNEL_CASES[case]
    if tile_bytes:
        monkeypatch.setattr(delta_ops, "_STEP_TILE_BYTES", tile_bytes)
        assert h * dv // delta_ops._step_lanes(dk, h, dv) == 3
    live, fresh = np.array(live, bool), np.array(fresh, bool)
    b = len(live)
    q, k, v, g, beta = _step_inputs(21, b, h, dk, dv)
    rng = np.random.default_rng(22)
    states = rng.standard_normal(
        (layers, n_slots, dk, h * dv)).astype(np.float32)
    rows = list(range(b)) if slots is None else list(slots)
    if case == "a_fresh_row_over_a_state":
        states[layer, rows[1], 3, 5] = np.nan    # what it held is not read
    before = _dispatched("interpret", "requested")
    # (a function of its own: traced, and so recorded, in every case)
    o, got = jax.jit(lambda *a: gated_delta_step_slots(
        *a, impl="interpret"))(
        q, k, v, g, beta, jnp.asarray(states), jnp.int32(layer),
        None if slots is None else jnp.asarray(slots, jnp.int32),
        jnp.asarray(live), jnp.asarray(fresh))
    assert _dispatched("interpret", "requested") == before + 1
    start = np.where(fresh[:, None, None], 0.0, states[layer, rows])
    want_o, want = gated_delta_step(q, k, v, g, beta, jnp.asarray(start),
                                    jnp.asarray(live))
    got = np.asarray(got)
    assert np.isfinite(got).all() and np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[layer, rows], want, rtol=1e-6, atol=1e-6)
    others = [l for l in range(layers) if l != layer]
    assert np.array_equal(got[others], states[others])
    unnamed = [s for s in range(n_slots) if s not in rows]
    assert np.array_equal(got[layer, unnamed], states[layer, unnamed])
    for r in np.flatnonzero(~live & ~fresh):
        assert np.array_equal(got[layer, rows[r]], states[layer, rows[r]])
        assert not np.asarray(o)[r].any()
    for r in np.flatnonzero(~live & fresh):      # from zeros, and no token
        assert not got[layer, rows[r]].any()


def test_a_shape_that_does_not_tile_takes_the_plain_step(monkeypatch):
    """"auto" on a TPU: the kernel where ``[dk, H dv]`` tiles (dk a
    multiple of 8, H dv of 128), else the plain form, and the dispatch
    record says which rule ruled it out; off a TPU the plain form."""
    def trace(dk, h, dv):
        """Trace a step of two rows on states ``[1, 2, dk, h dv]``."""
        jax.eval_shape(
            lambda s: gated_delta_step_slots(
                jnp.zeros((2, h, dk)), jnp.zeros((2, h, dk)),
                jnp.zeros((2, h, dv)), jnp.zeros((2, h)), jnp.zeros((2, h)),
                s, jnp.int32(0), None, jnp.ones(2, bool),
                jnp.zeros(2, bool), impl="auto"),
            jax.ShapeDtypeStruct((1, 2, dk, h * dv), jnp.float32))

    before = _dispatched("reference", "platform is not tpu")
    trace(96, 30, 192)
    assert _dispatched("reference", "platform is not tpu") == before + 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for dk, h, dv, impl, why in (
            (DK, H, DV, "reference",
             f"heads x value_dim {H * DV} % 128 != 0"),
            (12, 4, 32, "reference", "key_dim 12 % 8 != 0"),
            (96, 30, 192, "kernel", "auto"),
            (DK, 4, 32, "kernel", "auto")):
        before = _dispatched(impl, why)
        trace(dk, h, dv)
        assert _dispatched(impl, why) == before + 1


@pytest.mark.parametrize("dk,h,dv,lanes", [
    (96, 30, 192, 5760),      # the published widths: a row's state a tile
    (128, 64, 128, 4096),     # 4 MB a row: the widest divisor in 2.5 MiB
    (96, 30, 64, 1920),       # heads narrower than a column of 128 lanes
    (8, 3, 16, 48),           # no tile: the interpreter's one
])
def test_the_tile_is_whole_heads_and_whole_lanes(dk, h, dv, lanes):
    assert delta_ops._step_lanes(dk, h, dv) == lanes


def test_tails_at_the_boundaries_are_the_convolutions_own():
    """The tail kept at boundary j is what ``causal_conv`` hands on for a
    call that ends there."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((2, 12, 5)).astype(np.float32)
    tail = rng.standard_normal((2, 3, 5)).astype(np.float32)
    w = rng.standard_normal((5, 4)).astype(np.float32)
    tails = conv_tails_at(jnp.asarray(x), jnp.asarray(tail), 4)
    assert tails.shape == (3, 2, 3, 5)
    for j, end in enumerate((4, 8, 12)):
        _, want = causal_conv(jnp.asarray(x[:, :end]), jnp.asarray(tail), w,
                              None, jnp.full((2,), end, jnp.int32))
        np.testing.assert_array_equal(tails[j], want)


def test_the_convolution_without_a_bias_adds_none():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((1, 6, 5)).astype(np.float32))
    tail = jnp.zeros((1, 3, 5), jnp.float32)
    w = jnp.asarray(rng.standard_normal((5, 4)).astype(np.float32))
    n = jnp.full((1,), 6, jnp.int32)
    none, _ = causal_conv(x, tail, w, None, n)
    zero, _ = causal_conv(x, tail, w, jnp.zeros((5,)), n)
    np.testing.assert_array_equal(none, zero)
