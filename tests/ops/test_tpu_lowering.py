"""Every Pallas kernel on the chip path must pass the Pallas TPU
lowering at the shapes ``chip_smoke.py`` runs (GPT-J: 16 heads x 256,
bf16). The lowering — block shapes against the (8, 128) tiling rule,
supported ops — needs no chip:
``jit(f).trace(*shapes).lower(lowering_platforms=("tpu",))``. The Mosaic
compile proper and the numerics are the chip run's to prove."""

import functools

import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.paged_flash import paged_flash_attention

HEADS, HEAD_DIM, SEQ = 16, 256, 2048
BLOCK, WINDOW, SLOTS, CHUNK = 16, 1024, 8, 256


def _lower_for_tpu(fn, *shapes) -> str:
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


def _s(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("batch,chunk,kv_heads", [
    (SLOTS, 1, HEADS),      # decode: one row per kv head (MHA)
    (SLOTS, 1, 1),          # decode, every head on one kv head
    (1, CHUNK, HEADS),      # one prefill chunk
    (SLOTS, 5, HEADS),      # speculative verify: k+1 tokens per slot
])
def test_paged_kernel_lowers_for_tpu(batch, chunk, kv_heads):
    t = WINDOW // BLOCK
    pool = _s((1 + SLOTS * t, kv_heads, BLOCK, HEAD_DIM))
    text = _lower_for_tpu(
        paged_flash_attention,
        _s((batch, chunk, HEADS, HEAD_DIM)), pool, pool,
        _s((batch, t), jnp.int32), _s((batch, chunk), jnp.int32),
        _s((batch,), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_forward_and_backward_lower_for_tpu_at_head_dim_256():
    q = _s((1, HEADS, SEQ, HEAD_DIM))
    attn = functools.partial(flash_attention, causal=True,
                             block_q=256, block_k=512)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    assert "tpu_custom_call" in _lower_for_tpu(attn, q, q, q)
    bwd = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    # forward (for the residuals) + delta + dk/dv + dq
    assert bwd.count("tpu_custom_call") >= 4


def test_old_page_layout_would_not_lower():
    """The rule the cache layout exists for: a one-kv-head slice of a
    ``[N, block, kv_heads, D]`` pool puts kv_heads in the sublane
    position, which the TPU lowering refuses."""
    from jax.experimental import pallas as pl

    def kernel(k_ref, o_ref):
        o_ref[...] = k_ref[0, :, 0, :]

    def old_layout(k):
        return pl.pallas_call(
            kernel, grid=(1,),
            in_specs=[pl.BlockSpec((1, BLOCK, 1, HEAD_DIM),
                                   lambda i: (0, 0, 0, 0))],
            out_specs=pl.BlockSpec((BLOCK, HEAD_DIM), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((BLOCK, HEAD_DIM), k.dtype))(k)

    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        _lower_for_tpu(old_layout, _s((4, BLOCK, HEADS, HEAD_DIM)))
