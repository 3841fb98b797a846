"""The windowed flash kernels (``flash_attention(..., window=W)``: the
calls named ``flash_window_*``) in Pallas interpret mode against dense
masked attention: forward, dq, dk and dv, for windows smaller than, equal
to and larger than a block and than the sequence; and ``window=0`` is the
accepted kernels, name and jaxpr."""
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.attention import attention_reference, multihead_attention
from ray_tpu.ops.flash_attention import flash_attention

B, H, S, D = 1, 2, 256, 64


def _inputs(seed=0, s=S):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (B, H, s, D), jnp.float32)
                 for k in ks)


def _dense(q, k, v, window):
    """Dense attention under ``query - window < key <= query``, written
    here and not taken from the program: (B, H, S, D) in and out."""
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
    rows, cols = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = cols <= rows
    if window:
        keep &= cols > rows - window
    scores = jnp.where(keep, scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("window,block_q,block_k", [
    (1, 64, 64),        # the query's own key alone
    (17, 64, 64),       # inside a block
    (64, 64, 64),       # a block exactly
    (65, 64, 64),       # a block and one key
    (100, 32, 64),      # q blocks narrower than k blocks
    (48, 64, 32),       # and wider
    (128, 64, 64),      # two blocks
    (255, 64, 64),      # the sequence less one
    (256, 64, 64),      # the sequence: every earlier key
    (300, 64, 64),      # more than the sequence
    (96, 256, 256),     # one block holds the sequence
])
def test_windowed_kernels_against_dense_attention(window, block_q, block_k):
    q, k, v, g = _inputs()

    def kernel(q, k, v):
        return jnp.sum(g * flash_attention(
            q, k, v, causal=True, window=window, block_q=block_q,
            block_k=block_k, interpret=True))

    def dense(q, k, v):
        return jnp.sum(g * _dense(q, k, v, window))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=block_q, block_k=block_k, interpret=True)
    assert float(jnp.max(jnp.abs(out - _dense(q, k, v, window)))) < 2e-5
    got = jax.grad(kernel, (0, 1, 2))(q, k, v)
    want = jax.grad(dense, (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4, name


def test_an_off_by_one_window_is_told():
    """What the comparison above can see: a window one key short moves
    the output by far more than its tolerance."""
    q, k, v, _ = _inputs()
    a = flash_attention(q, k, v, causal=True, window=64, block_q=64,
                        block_k=64, interpret=True)
    assert float(jnp.max(jnp.abs(a - _dense(q, k, v, 63)))) > 1e-2


def test_the_xla_backward_knows_the_window():
    q, k, v, g = _inputs(1)

    def f(backward):
        return jax.grad(lambda q, k, v: jnp.sum(g * flash_attention(
            q, k, v, causal=True, window=80, block_q=64, block_k=64,
            interpret=True, backward=backward)), (0, 1, 2))(q, k, v)
    for a, b in zip(f("pallas"), f("xla")):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_the_dispatcher_passes_the_window_to_every_impl():
    q, k, v, _ = (jnp.swapaxes(a, 1, 2) for a in _inputs(2))
    ref = attention_reference(q, k, v, causal=True, window=40)
    want = jnp.swapaxes(_dense(*(jnp.swapaxes(a, 1, 2)
                                 for a in (q, k, v)), 40), 1, 2)
    assert float(jnp.max(jnp.abs(ref - want))) < 2e-5
    got = multihead_attention(q, k, v, causal=True, window=40,
                              impl="interpret", block_q=64, block_k=64)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_a_window_is_a_form_of_causal_attention():
    q, k, v, _ = _inputs()
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=8, interpret=True)


def _grad_jaxpr(**kw):
    q, k, v, g = _inputs()
    return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        g * flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True, **kw)), (0, 1, 2)))(q, k, v))


def test_window_zero_is_the_accepted_kernels_jaxpr():
    """``window=0`` traces to the program the call without the argument
    traces to, equation for equation, under the accepted names; a window
    changes the names of all four calls and narrows the innermost grid
    axis."""
    plain, zero, windowed = _grad_jaxpr(), _grad_jaxpr(window=0), \
        _grad_jaxpr(window=64)
    assert zero == plain
    assert "flash_window" not in plain
    for name in ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq",
                 "flash_bwd_delta"):
        assert name in plain
        assert name.replace("flash_", "flash_window_") in windowed
    # 256 / 64 = 4 key blocks a query block; a window of a block walks 3
    assert "grid=(1, 2, 4, 4)" in plain and "(1, 2, 4, 3)" not in plain
    assert "grid=(1, 2, 4, 3)" in windowed
