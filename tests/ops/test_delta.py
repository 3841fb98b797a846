"""The blocked gated delta rule, the one-token step over the whole
per-slot array and the tails at a call's boundaries
(``ray_tpu/ops/delta.py``) against the recurrence written token by
token in float64. The oracle keeps a state ``[H, dk, dv]``; the module
``[dk, H dv]``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
