"""Beside ``test_tpu_lowering.py``: what a TRAINED stack of window
layers and dropless experts adds to the chip path passes the TPU
compiler at a held-share shape, no chip attached (a described v5e): the
grouped products' two transposes (dX through the transposed matrices, dW
of each held expert from its own rows, float32), which ``jax.grad`` of
the expert layer asks of XLA:TPU, and the windowed flash kernels'
Mosaic compile at the cell's blocks."""
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import TransformerConfig
from ray_tpu.models.moe import topk_moe_mlp
from ray_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, *shapes):
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("tokens", [
    512,       # one call
    8192,      # two turns of ``_MANY_TOKENS``
])
def test_the_expert_layers_gradient_compiles_for_v5e(one_chip, tokens):
    """A held share (4 of 16 experts, top-4, published widths 2304 x
    896) under ``jax.grad``: float32 masters in, float32 dW out, the
    products themselves grouped products of bf16 operands (no float32
    array of rows among their operands)."""
    cfg = TransformerConfig(
        d_model=2304, n_layers=1, n_experts=16, experts_held=4,
        expert_first=4, experts_per_token=4, expert_width=896,
        block_style="llama", dtype=jnp.bfloat16)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    lp = {"w_router": s((2304, 16)), "we_gate": s((4, 2304, 896)),
          "we_up": s((4, 2304, 896)), "we_down": s((4, 896, 2304))}
    h = s((1, tokens, 2304), jnp.bfloat16)

    def loss(lp, h):
        y, stats = topk_moe_mlp(cfg, lp, h, stats=True)
        return jnp.sum(y.astype(jnp.float32) ** 2), stats
    compiled = _compiled(jax.grad(loss, (0, 1), has_aux=True), lp, h)
    text = compiled.as_text()
    # three products forward (recomputed in a turn's backward pass) and
    # six transposed
    assert text.count("%ragged-dot-none") >= 9
    (dlp, dh), stats = jax.eval_shape(
        jax.grad(loss, (0, 1), has_aux=True), lp, h)
    assert {k: (v.dtype, v.shape) for k, v in dlp.items()} \
        == {k: (jnp.float32, v.shape) for k, v in lp.items()}
    assert dh.dtype == jnp.bfloat16
    assert set(stats) == {"held_assignments", "load_max_over_mean",
                          "balance"}


@pytest.mark.parametrize("seq,window,blocks", [
    (8192, 1024, (512, 1024)),       # the cell's call and blocks
    (2048, 1024, (512, 1024)),       # the check's
    (4096, 512, (512, 512)),
])
def test_the_windowed_flash_kernels_compile_for_v5e(one_chip, seq, window,
                                                    blocks):
    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    q = s((1, 8, seq, 128))

    def f(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=window, block_q=blocks[0],
            block_k=blocks[1]).astype(jnp.float32))
    text = _compiled(jax.grad(f, (0, 1, 2)), q, q, q).as_text()
    for name in ("flash_window_fwd", "flash_window_bwd_dkdv",
                 "flash_window_bwd_dq", "flash_window_bwd_delta"):
        assert name in text
    # no call under an accepted kernel's name (the Python functions'
    # names, ``_flash_fwd``, are in the source locations)
    import re
    assert not re.search(r"%flash_(fwd|bwd_dkdv|bwd_dq|bwd_delta)[.\d]* = ",
                         text)
