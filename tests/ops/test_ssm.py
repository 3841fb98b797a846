"""The blocked scan, the one-token step (plain, and the kernel over the
whole per-slot array, interpreted) and the carried convolution
(``ray_tpu/ops/ssm.py``) against the recurrence written token by token.
The oracle keeps a state ``[H, P, N]``; the module ``[N, H P]``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import dispatch_log
from ray_tpu.ops.ssm import (causal_conv, ssd_chunk_scan, ssd_step,
                             ssd_step_slots)

H, P, N = 4, 8, 16


def _kept(state):
    """``[..., H, P, N]`` as the module keeps it, ``[..., N, H P]``."""
    state = np.asarray(state)
    lead = state.shape[:-3]
    return np.swapaxes(state.reshape(lead + (H * P, N)), -1, -2)


def _told(state):
    """The inverse of :func:`_kept`."""
    state = np.swapaxes(np.asarray(state), -1, -2)
    return state.reshape(state.shape[:-2] + (H, P, N))


def _inputs(seed, b, t):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return dict(
        x=f(b, t, H, P), dt=np.log1p(np.exp(f(b, t, H) - 2.0)),
        A=-np.exp(rng.uniform(0.0, 2.0, H)).astype(np.float32),
        B=f(b, t, N), C=f(b, t, N), D=f(H), state=f(b, H, P, N))


def _g(a):
    """``B`` or ``C`` as the ops take it: one group for all heads."""
    return a[..., None, :]


def _sequential(x, dt, A, B, C, D, state, n_live):
    """Token by token; a row's tokens past ``n_live`` change nothing."""
    state = state.astype(np.float64).copy()
    y = np.zeros(x.shape, np.float64)
    for b in range(x.shape[0]):
        for t in range(int(n_live[b])):
            decay = np.exp(dt[b, t] * A)[:, None, None]
            state[b] = decay * state[b] + (dt[b, t][:, None] * x[b, t])[
                ..., None] * B[b, t][None, None]
            y[b, t] = state[b] @ C[b, t] + D[:, None] * x[b, t]
    return y, state


@pytest.mark.parametrize("t,block,n_live", [
    (8, 8, (8, 8)),          # one block
    (24, 8, (24, 24)),       # whole blocks
    (21, 8, (21, 21)),       # not a multiple of the block
    (24, 8, (24, 13)),       # a padded row
    (16, 8, (5, 0)),         # a row with nothing live
    (5, 256, (5, 3)),        # a call shorter than a block
])
def test_the_blocked_scan_is_the_recurrence(t, block, n_live):
    a = _inputs(t, 2, t)
    n_live = np.asarray(n_live)
    live = np.arange(t)[None] < n_live[:, None]
    y, state = jax.jit(ssd_chunk_scan, static_argnames="block")(
        a["x"], a["dt"], a["A"], _g(a["B"]), _g(a["C"]), a["D"],
        _kept(a["state"]),
        jnp.asarray(live), block=block)
    want_y, want_state = _sequential(**a, n_live=n_live)
    state = _told(state)
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-5)
    got = np.where(live[..., None, None], np.asarray(y), 0.0)
    np.testing.assert_allclose(got, want_y, rtol=2e-5, atol=2e-5)
    # a row with nothing live hands its state on bit for bit
    for b in np.flatnonzero(n_live == 0):
        assert np.array_equal(np.asarray(state)[b], a["state"][b])


def test_a_state_carried_across_calls_is_one_long_scan():
    a = _inputs(3, 1, 40)
    whole, end = ssd_chunk_scan(
        a["x"], a["dt"], a["A"], _g(a["B"]), _g(a["C"]), a["D"],
        _kept(a["state"]),
        jnp.ones((1, 40), bool), block=8)
    parts, state = [], jnp.asarray(_kept(a["state"]))
    for lo, hi in ((0, 16), (16, 29), (29, 40)):
        y, state = ssd_chunk_scan(
            a["x"][:, lo:hi], a["dt"][:, lo:hi], a["A"], _g(a["B"][:, lo:hi]),
            _g(a["C"][:, lo:hi]), a["D"], state, jnp.ones((1, hi - lo), bool),
            block=8)
        parts.append(y)
    np.testing.assert_allclose(np.concatenate(parts, 1), whole, rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(state, end, rtol=2e-5, atol=2e-5)


def test_the_one_token_step_is_the_recurrence():
    a = _inputs(5, 3, 6)
    live = np.array([True, False, True])
    state = jnp.asarray(_kept(a["state"]))
    ys = []
    for t in range(6):
        y, state = ssd_step(a["x"][:, t], a["dt"][:, t], a["A"],
                            _g(a["B"][:, t]), _g(a["C"][:, t]), a["D"], state,
                            jnp.asarray(live))
        ys.append(y)
    want_y, want_state = _sequential(**a, n_live=np.where(live, 6, 0))
    state = _told(state)
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.stack(ys, 1)[live], want_y[live],
                               rtol=2e-5, atol=2e-5)
    # the row that is not live: untouched, bit for bit
    assert np.array_equal(state[1], a["state"][1])


def _whole_array(seed, layers, slots):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((layers, slots, N, H * P)).astype(np.float32)


def _step_slots(a, t, states, layer, slots, live, fresh, impl):
    return jax.jit(ssd_step_slots, static_argnames="impl")(
        a["x"][:, t], a["dt"][:, t], a["A"], _g(a["B"][:, t]),
        _g(a["C"][:, t]), a["D"], jnp.asarray(states), jnp.asarray(layer, jnp.int32),
        None if slots is None else jnp.asarray(slots, jnp.int32),
        jnp.asarray(live), jnp.asarray(fresh), impl=impl)


@pytest.mark.parametrize("case", [
    "a_layer_of_the_whole_array", "rows_that_are_not_live",
    "a_fresh_row_over_a_nan", "six_steps_in_a_row", "the_slots_it_is_told"])
def test_the_step_kernel_is_the_plain_step(case):
    """The kernel, interpreted, over the WHOLE ``[layers, slots, N, H
    P]`` array with a layer index, against the plain form (and, over six
    steps, the sequential recurrence)."""
    b, layers, layer = 3, 3, 1
    a = _inputs(11, b, 6)
    states = _whole_array(12, layers, 4 if case == "the_slots_it_is_told"
                          else b)
    slots = [3, 0, 2] if case == "the_slots_it_is_told" else None
    rows = [0, 1, 2] if slots is None else slots
    live = np.array([True, case != "rows_that_are_not_live", True])
    fresh = np.zeros(b, bool)
    if case == "a_fresh_row_over_a_nan":
        fresh[2] = True
        states[layer, 2, 3, 5] = np.nan
    steps = 6 if case == "six_steps_in_a_row" else 1
    got, want = jnp.asarray(states), jnp.asarray(states)
    ys = []
    for t in range(steps):
        y, got = _step_slots(a, t, got, layer, slots, live, fresh,
                             "interpret")
        want_y, want = _step_slots(a, t, want, layer, slots, live, fresh,
                                   "reference")
        np.testing.assert_allclose(y[live], want_y[live], rtol=1e-6,
                                   atol=1e-6)
        ys.append(y)
    got = np.asarray(got)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert np.isfinite(got).all()
    # the other layers, and the slots no row names, bit for bit
    others = [l for l in range(layers) if l != layer]
    assert np.array_equal(got[others], states[others])
    unnamed = [s for s in range(states.shape[1]) if s not in rows]
    assert np.array_equal(got[layer, unnamed], states[layer, unnamed])
    # a row that is not live leaves its state bit for bit
    for r in np.flatnonzero(~live):
        assert np.array_equal(got[layer, rows[r]], states[layer, rows[r]])
    if case == "a_fresh_row_over_a_nan":
        # what the slot held is not read: the state is the token's own
        alone, _ = ssd_step(a["x"][2:, 0], a["dt"][2:, 0], a["A"],
                            _g(a["B"][2:, 0]), _g(a["C"][2:, 0]), a["D"],
                            jnp.zeros((1, N, H * P)), jnp.ones(1, bool))
        np.testing.assert_allclose(ys[0][2], alone[0], rtol=1e-6, atol=1e-6)
    if case == "six_steps_in_a_row":
        want_y, want_state = _sequential(
            **{**a, "state": _told(states[layer])}, n_live=np.full(b, 6))
        np.testing.assert_allclose(_told(got[layer]), want_state,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.stack(ys, 1), want_y, rtol=2e-5,
                                   atol=2e-5)


def _dispatched(op):
    return {(d["impl"], d["why"]): d["count"] for d in dispatch_log()
            if d["op"] == op}


def test_a_shape_that_does_not_tile_takes_the_plain_step(monkeypatch):
    """"auto" on a TPU: the kernel where ``[N, H P]`` tiles (N a multiple
    of 8, H P of 128), else the plain form, and the dispatch record says
    which rule ruled it out."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def trace(n, heads):
        """Trace a step of two rows on states ``[1, 2, n, heads P]``."""
        jax.eval_shape(
            lambda s: ssd_step_slots(
                jnp.zeros((2, heads, P)), jnp.zeros((2, heads)),
                jnp.zeros(heads), jnp.zeros((2, 1, n)), jnp.zeros((2, 1, n)),
                jnp.zeros(heads), s, jnp.int32(0), None, jnp.ones(2, bool),
                jnp.zeros(2, bool), impl="auto"),
            jax.ShapeDtypeStruct((1, 2, n, heads * P), jnp.float32))
    for n, heads, impl, why in (
            (N, H, "reference", f"heads x head_dim {H * P} % 128 != 0"),
            (12, 4 * H, "reference", "state 12 % 8 != 0"),
            (N, 4 * H, "kernel", "auto")):
        before = _dispatched("ssm_step").get((impl, why), 0)
        trace(n, heads)
        assert _dispatched("ssm_step")[impl, why] == before + 1


def _conv_whole(x, w, b):
    """The convolution over a whole sequence, zeros before position 0."""
    k = w.shape[-1]
    ext = np.concatenate([np.zeros((x.shape[0], k - 1, x.shape[2])), x], 1)
    acc = b + sum(ext[:, j:j + x.shape[1]] * w[:, j] for j in range(k))
    return acc / (1.0 + np.exp(-acc))


@pytest.mark.parametrize("cuts", [(0, 12), (0, 5, 12), (0, 1, 2, 3, 12),
                                  (0, 2, 4, 12)])
def test_the_convolution_carries_its_tail(cuts):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 12, 6)).astype(np.float32)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    b = rng.standard_normal(6).astype(np.float32)
    tail, outs = jnp.zeros((2, 3, 6)), []
    for lo, hi in zip(cuts, cuts[1:]):
        out, tail = causal_conv(x[:, lo:hi], tail, w, b,
                                jnp.full((2,), hi - lo, jnp.int32))
        outs.append(out)
    np.testing.assert_allclose(np.concatenate(outs, 1), _conv_whole(x, w, b),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(tail, x[:, -3:])


def test_padding_moves_neither_the_tail_nor_the_live_outputs():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 8, 6)).astype(np.float32)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    b = np.zeros(6, np.float32)
    tail = rng.standard_normal((2, 3, 6)).astype(np.float32)
    n_live = jnp.asarray([2, 0], jnp.int32)
    out, new = causal_conv(x, tail, w, b, n_live)
    short, want = causal_conv(x[:, :2], tail, w, b, jnp.asarray([2, 2]))
    np.testing.assert_allclose(out[0, :2], short[0], rtol=1e-6)
    np.testing.assert_array_equal(new[0], want[0])
    np.testing.assert_array_equal(new[1], tail[1])     # nothing live
