"""``B`` and ``C`` a GROUP of heads (``ray_tpu/ops/ssm.py``): the blocked
scan, the plain one-token step and the kernel over the whole per-slot
array (interpreted) at 1, 2 and 8 groups against the recurrence written
token by token, a head reading its group's ``B`` and ``C``; with a state
carried in and padded rows. The oracle keeps a state ``[H, P, N]``; the
module ``[N, H P]``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.ssm import (ssd_chunk_scan, ssd_step, ssd_step_slots,
                             step_choice)

H, P, N = 8, 16, 8
GROUPS = (1, 2, 8)


def _kept(state):
    """``[..., H, P, N]`` as the module keeps it, ``[..., N, H P]``."""
    state = np.asarray(state)
    return np.swapaxes(state.reshape(state.shape[:-3] + (H * P, N)), -1, -2)


def _told(state):
    state = np.swapaxes(np.asarray(state), -1, -2)
    return state.reshape(state.shape[:-2] + (H, P, N))


def _inputs(seed, b, t, g):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return dict(
        x=f(b, t, H, P), dt=np.log1p(np.exp(f(b, t, H) - 2.0)),
        A=-np.exp(rng.uniform(0.0, 2.0, H)).astype(np.float32),
        B=f(b, t, g, N), C=f(b, t, g, N), D=f(H), state=f(b, H, P, N))


def _sequential(x, dt, A, B, C, D, state, n_live):
    """Token by token, head h reading group ``h // (H / G)``; a row's
    tokens past ``n_live`` change nothing."""
    state = state.astype(np.float64).copy()
    y = np.zeros(x.shape, np.float64)
    of_head = np.arange(H) // (H // B.shape[2])
    for b in range(x.shape[0]):
        for t in range(int(n_live[b])):
            decay = np.exp(dt[b, t] * A)[:, None, None]
            state[b] = decay * state[b] + (dt[b, t][:, None] * x[b, t])[
                ..., None] * B[b, t][of_head][:, None, :]
            y[b, t] = np.einsum("hpn,hn->hp", state[b], C[b, t][of_head]) \
                + D[:, None] * x[b, t]
    return y, state


@pytest.mark.parametrize("g", GROUPS)
@pytest.mark.parametrize("t,block,n_live", [
    (24, 8, (24, 24)),       # whole blocks, a state carried in
    (21, 8, (21, 13)),       # not a multiple of the block, a padded row
    (16, 8, (5, 0)),         # a row with nothing live
])
def test_the_blocked_scan_is_the_recurrence_a_group(g, t, block, n_live):
    a = _inputs(t + g, 2, t, g)
    n_live = np.asarray(n_live)
    live = np.arange(t)[None] < n_live[:, None]
    y, state = jax.jit(ssd_chunk_scan, static_argnames="block")(
        a["x"], a["dt"], a["A"], a["B"], a["C"], a["D"], _kept(a["state"]),
        jnp.asarray(live), block=block)
    want_y, want_state = _sequential(**a, n_live=n_live)
    np.testing.assert_allclose(_told(state), want_state, rtol=2e-5,
                               atol=2e-5)
    got = np.where(live[..., None, None], np.asarray(y), 0.0)
    np.testing.assert_allclose(got, want_y, rtol=2e-5, atol=2e-5)
    for b in np.flatnonzero(n_live == 0):
        assert np.array_equal(_told(state)[b], a["state"][b])


@pytest.mark.parametrize("g", GROUPS)
def test_the_plain_step_is_the_recurrence_a_group(g):
    a = _inputs(7 + g, 3, 1, g)
    live = np.asarray([True, False, True])
    y, state = ssd_step(a["x"][:, 0], a["dt"][:, 0], a["A"], a["B"][:, 0],
                        a["C"][:, 0], a["D"], _kept(a["state"]),
                        jnp.asarray(live))
    want_y, want_state = _sequential(**a, n_live=live.astype(int))
    np.testing.assert_allclose(_told(state), want_state, rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live, 0],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("g", GROUPS)
def test_the_step_over_the_slots_is_the_recurrence_a_group(g, impl):
    """The whole per-slot array ``[layers, slots, N, H P]`` with a layer
    index and the rows' slots: the kernel (a tile holds lanes of ONE
    group and reads that group's column) and the plain form; a row that
    is not live and a fresh row; the other layers and slots untouched."""
    a = _inputs(11 + g, 3, 1, g)
    layers, slots, layer = 2, 5, 1
    rng = np.random.default_rng(g)
    states = rng.standard_normal((layers, slots, N, H * P)).astype(
        np.float32)
    rows = np.asarray([3, 0, 4])
    states[layer, rows] = _kept(a["state"])
    live, fresh = np.asarray([True, False, True]), \
        np.asarray([False, False, True])
    start = np.where(fresh[:, None, None, None], 0.0, a["state"])
    y, out = jax.jit(ssd_step_slots, static_argnames="impl")(
        a["x"][:, 0], a["dt"][:, 0], a["A"], a["B"][:, 0], a["C"][:, 0],
        a["D"], jnp.asarray(states), jnp.int32(layer), jnp.asarray(rows),
        jnp.asarray(live), jnp.asarray(fresh), impl=impl)
    want_y, want_state = _sequential(**{**a, "state": start},
                                     n_live=live.astype(int))
    out = np.asarray(out)
    np.testing.assert_allclose(_told(out[layer, rows[live]]),
                               want_state[live], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live, 0],
                               rtol=2e-5, atol=2e-5)
    untouched = np.ones((layers, slots), bool)
    untouched[layer, rows] = False
    assert np.array_equal(out[untouched], states[untouched])


def test_a_tile_holds_lanes_of_one_group(monkeypatch):
    """"auto" on a TPU: the kernel where a GROUP's channels are whole
    lanes (H P / G a multiple of 128), else the plain form."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert step_choice("auto", 128, 8192, groups=8) == "kernel"
    assert step_choice("auto", 128, 8192) == "kernel"
    assert step_choice("auto", 128, 512, groups=8) == "reference"
