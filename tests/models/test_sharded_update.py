"""Gradient comm-path knobs: int8 grad transport and cross-replica
sharded weight update (``make_train_step(grad_transport=,
shard_weight_update=)``) vs the fp32 replicated baseline.

Model kept tiny (1 layer, d=32) so the three compiled step programs fit
the suite's time budget.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import get_config, make_train_step
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

N_STEPS = 20


@pytest.fixture(scope="module")
def parity_runs(cpu_mesh_devices):
    cfg = dataclasses.replace(
        get_config("gptj-tiny"), d_model=32, n_layers=1, n_heads=2,
        head_dim=16, d_ff=64, vocab_size=128, max_seq_len=32)
    mesh = build_mesh(MeshSpec(dp=2, fsdp=4), cpu_mesh_devices)
    ids = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                             cfg.vocab_size)
    batch = {"input_ids": ids,
             "loss_mask": jnp.ones((8, 32), jnp.float32)}

    def run(**kw):
        bundle = make_train_step(cfg, mesh, learning_rate=1e-3,
                                 quant_block_size=64, **kw)
        state = bundle.init(seed=0)
        losses = []
        for _ in range(N_STEPS):
            state, metrics = bundle.step(state, batch)
            losses.append(float(metrics["loss"]))
        return bundle, state, losses

    return {
        "baseline": run(),
        "sharded": run(shard_weight_update=True),
        "int8_sharded": run(grad_transport="int8",
                            shard_weight_update=True),
    }


@pytest.mark.slow
def test_sharded_update_matches_replicated_exactly(parity_runs):
    # reduce-scatter + 1/N update + all-gather is the same arithmetic as
    # the replicated update, just laid out differently: losses agree to
    # float tolerance at every step
    l_base = parity_runs["baseline"][2]
    l_shard = parity_runs["sharded"][2]
    np.testing.assert_allclose(l_shard, l_base, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_int8_sharded_loss_parity_bound(parity_runs):
    # acceptance bound: int8 grad transport + sharded update stays
    # within |dloss| < 1e-2 of the fp32 replicated baseline at step 20
    l_base = parity_runs["baseline"][2]
    l_q = parity_runs["int8_sharded"][2]
    assert abs(l_q[-1] - l_base[-1]) < 1e-2
    assert l_q[-1] < l_q[0]            # still actually learning
    b = parity_runs["int8_sharded"][0]
    assert b.grad_transport == "int8" and b.shard_weight_update


@pytest.mark.slow
def test_sharded_opt_state_is_flat_and_data_sharded(parity_runs):
    bundle, state, _ = parity_runs["sharded"]
    mu = jax.tree.leaves(state["opt_state"])
    flat = [x for x in mu if hasattr(x, "ndim") and x.ndim == 1
            and x.size >= 64]
    assert flat, "expected flat 1-D optimizer moment leaves"
    specs = {str(x.sharding.spec) for x in flat}
    assert any("dp" in s and "fsdp" in s for s in specs), specs
    # flat shards pad to whole quant blocks per replica
    assert all(x.size % (64 * 8) == 0 for x in flat)
    # params keep their normal layout for eval/checkpoint paths
    p_shapes = {x.ndim for x in jax.tree.leaves(state["params"])}
    assert p_shapes - {1}, "params unexpectedly flattened"


def test_grad_transport_validation(cpu_mesh_devices):
    cfg = get_config("gptj-tiny")
    mesh = build_mesh(MeshSpec(fsdp=8), cpu_mesh_devices)
    with pytest.raises(ValueError, match="grad_transport"):
        make_train_step(cfg, mesh, grad_transport="fp8")
