"""Flash/ring attention correctness vs the reference implementation.

The Pallas kernel runs in interpreter mode on CPU (same program the TPU
backend compiles); ring attention runs under shard_map on the 8-device
virtual mesh from conftest.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (
    attention_reference,
    flash_attention,
    multihead_attention,
    ring_attention,
    rms_norm,
    layer_norm,
    rotary_table,
    apply_rotary,
    cross_entropy_loss,
)


def _rand_qkv(key, b=2, s=256, h=4, d=64, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return [jax.random.normal(k, shape, dtype) for k in ks]


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    ref = attention_reference(q, k, v, causal=causal)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out = flash_attention(qt, kt, vt, causal=causal,
                          block_q=128, block_k=128, interpret=True)
    out = jnp.swapaxes(out, 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_grads_match_reference():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), s=128)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        o = flash_attention(qt, kt, vt, causal=True, block_q=64,
                            block_k=64, interpret=True)
        return jnp.sum(jnp.swapaxes(o, 1, 2) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_dispatcher_reference_on_cpu():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), s=64)
    out = multihead_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_ring_attention_matches_reference(cpu_mesh_devices):
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(cpu_mesh_devices).reshape(8), ("sp",))
    b, s, h, d = 2, 64, 2, 8
    key = jax.random.PRNGKey(3)
    q, k, v = _rand_qkv(key, b=b, s=s, h=h, d=d)

    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    out = ring(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grads(cpu_mesh_devices):
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(cpu_mesh_devices).reshape(8), ("sp",))
    b, s, h, d = 1, 32, 2, 8
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), b=b, s=s, h=h, d=d)

    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    g_ring = jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-4, rtol=1e-4)


def test_rms_and_layer_norm():
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 16))
    scale = jnp.ones(16) * 2.0
    y = rms_norm(x, scale)
    expected = 2.0 * x / jnp.sqrt(
        jnp.mean(x ** 2, axis=-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(expected),
                               atol=1e-6)
    y2 = layer_norm(x, jnp.ones(16), jnp.zeros(16))
    np.testing.assert_allclose(np.asarray(jnp.mean(y2, -1)),
                               np.zeros(4), atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.std(y2, -1)),
                               np.ones(4), atol=1e-2)


@pytest.mark.parametrize("layout", ["gptj", "neox"])
def test_rotary_norm_preserving(layout):
    # Rotations preserve the norm of each rotated pair.
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 4, 32))
    sin, cos = rotary_table(64, 32)
    y = apply_rotary(x, sin, cos, layout=layout)
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(y, axis=-1)),
        np.asarray(jnp.linalg.norm(x, axis=-1)), rtol=1e-5)
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]),
                               atol=1e-6)


def test_rotary_partial_dim_passthrough():
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 8, 2, 64))
    sin, cos = rotary_table(16, 16)     # rotate only first 16 dims
    y = apply_rotary(x, sin, cos)
    np.testing.assert_allclose(np.asarray(y[..., 16:]),
                               np.asarray(x[..., 16:]))


def test_cross_entropy():
    logits = jax.random.normal(jax.random.PRNGKey(8), (4, 8, 32))
    labels = jax.random.randint(jax.random.PRNGKey(9), (4, 8), 0, 32)
    loss, n = cross_entropy_loss(logits, labels)
    # compare against jax.nn reference
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    expected = -jnp.mean(
        jnp.take_along_axis(logp, labels[..., None], -1)[..., 0])
    np.testing.assert_allclose(float(loss), float(expected), rtol=1e-6)
    assert float(n) == 32.0

    mask = jnp.zeros((4, 8)).at[:, :4].set(1.0)
    loss_m, n_m = cross_entropy_loss(logits, labels, mask=mask)
    expected_m = -jnp.sum(
        jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        * mask) / 16.0
    np.testing.assert_allclose(float(loss_m), float(expected_m), rtol=1e-6)
    assert float(n_m) == 16.0


def test_flash_cross_length_causal():
    # Decode-style: sq < sk, end-aligned causality must match reference.
    key = jax.random.PRNGKey(10)
    b, h, d = 1, 2, 64
    q = jax.random.normal(key, (b, 128, h, d))
    k = jax.random.normal(jax.random.PRNGKey(11), (b, 256, h, d))
    v = jax.random.normal(jax.random.PRNGKey(12), (b, 256, h, d))
    ref = attention_reference(q, k, v, causal=True)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out = flash_attention(qt, kt, vt, causal=True, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(out, 1, 2)),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_pallas_backward_matches_reference_and_xla():
    """The Pallas dq/dk/dv kernels (P recomputed from the saved LSE)
    must match both the dense reference gradients and the lax.scan
    backward they replace, causal and not, incl. sq < sk."""
    rng = jax.random.PRNGKey(21)

    def ref_grads(q, k, v, causal, do):
        def f(q, k, v):
            return jnp.sum(attention_reference(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2), causal=causal)
                * jnp.swapaxes(do, 1, 2))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    def flash_grads(q, k, v, causal, do, backward):
        def f(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=64, block_k=64,
                interpret=True, backward=backward) * do)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for causal, (sq, sk) in [(False, (128, 128)), (True, (128, 128)),
                             (True, (64, 128))]:
        ks = jax.random.split(jax.random.fold_in(rng, sq + sk), 4)
        q = jax.random.normal(ks[0], (1, 2, sq, 64))
        k = jax.random.normal(ks[1], (1, 2, sk, 64))
        v = jax.random.normal(ks[2], (1, 2, sk, 64))
        do = jax.random.normal(ks[3], (1, 2, sq, 64))
        g_ref = ref_grads(q, k, v, causal, do)
        g_pal = flash_grads(q, k, v, causal, do, "pallas")
        g_xla = flash_grads(q, k, v, causal, do, "xla")
        for name, a, b in (("dq", g_pal[0], g_ref[0]),
                           ("dk", g_pal[1], g_ref[1]),
                           ("dv", g_pal[2], g_ref[2])):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3,
                err_msg=f"{name} causal={causal} sq={sq}")
        for name, a, b in (("dq", g_pal[0], g_xla[0]),
                           ("dk", g_pal[1], g_xla[1]),
                           ("dv", g_pal[2], g_xla[2])):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3,
                err_msg=f"{name} vs xla causal={causal}")


# ------------------------------------------------------- dispatch rules
def _last(op):
    from ray_tpu.ops.attention import dispatch_log
    return [e for e in dispatch_log() if e["op"] == op]


def test_dispatch_is_by_stated_rules_and_recorded():
    """"auto" off a TPU is the reference, with the reason on record;
    the compiled kernel off a TPU is an error, never a quiet
    interpreter; "interpret" is the explicit way to run the kernel."""
    from ray_tpu.ops import paged_attention
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), s=128, d=128)
    multihead_attention(q, k, v, causal=True)
    assert any(e["impl"] == "reference"
               and e["why"] == "platform is not tpu"
               for e in _last("flash"))
    with pytest.raises(ValueError, match="needs a TPU"):
        multihead_attention(q, k, v, causal=True, impl="flash")
    out = multihead_attention(q, k, v, causal=True, impl="interpret")
    assert any(e["impl"] == "interpret" for e in _last("flash"))
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(attention_reference(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5)
    # a forced kernel on a call it cannot compute is an error too
    mask = jnp.ones((1, 1, 128, 128), bool)
    with pytest.raises(ValueError, match="arbitrary mask"):
        multihead_attention(q, k, v, mask=mask, impl="interpret")
    kc = jnp.zeros((3, 4, 8, 128), jnp.float32)
    pq = jnp.zeros((1, 1, 4, 128), jnp.float32)
    bt = jnp.ones((1, 2), jnp.int32)
    pos = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="needs a TPU"):
        paged_attention(pq, kc, kc, bt, pos, impl="kernel")
    with pytest.raises(ValueError, match="unknown paged attention impl"):
        paged_attention(pq, kc, kc, bt, pos, impl="pallas")


def test_tpu_shape_rules_name_what_does_not_tile(monkeypatch):
    """On a TPU "auto" must decide from shapes alone and say which rule
    sent a call to the reference (nothing is compiled here: the
    reference path is what the rules pick)."""
    import ray_tpu.ops.attention as A
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), s=64, d=64)
    multihead_attention(q, k, v, causal=True)
    assert any(e["why"] == "head_dim 64 % 128 != 0"
               for e in _last("flash"))
    pq = jnp.zeros((1, 1, 4, 128), jnp.bfloat16)
    kc = jnp.zeros((3, 4, 8, 128), jnp.bfloat16)    # 8-row bf16 pages
    A.paged_attention(pq, kc, kc, jnp.ones((1, 2), jnp.int32),
                      jnp.zeros((1, 1), jnp.int32))
    assert any(e["why"] == "block_size 8 % 16 != 0 (bfloat16 pages)"
               for e in _last("paged"))
