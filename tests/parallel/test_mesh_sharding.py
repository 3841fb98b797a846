"""Mesh/sharding unit tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel.mesh import MeshSpec, build_mesh, chip_spec
from ray_tpu.parallel.sharding import (
    DDP_RULES,
    FSDP_RULES,
    ShardingRules,
    batch_sharding,
    infer_param_logical_axes,
    shard_params,
)


def test_mesh_spec_resolve():
    spec = MeshSpec(fsdp=-1, tp=2).resolve(8)
    assert spec.fsdp == 4 and spec.tp == 2
    with pytest.raises(ValueError):
        MeshSpec(fsdp=3).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec(fsdp=-1, tp=-1).resolve(8)


def test_build_mesh(cpu_mesh_devices):
    mesh = build_mesh(MeshSpec(fsdp=4, tp=2))
    assert mesh.shape["fsdp"] == 4
    assert mesh.shape["tp"] == 2
    assert mesh.shape["dp"] == 1


def test_chip_spec_cpu():
    spec = chip_spec()
    assert spec.name == "cpu"  # tests force the cpu platform
    assert chip_spec("v5e").bf16_flops == 197e12


def test_chip_spec_unknown_tpu_kind_raises(monkeypatch):
    """A TPU the table does not know is an error, never v5e's peak."""
    import types

    from ray_tpu.parallel.mesh import chip_spec_for_kind
    assert chip_spec_for_kind("TPU v5 lite").name == "v5e"
    assert chip_spec_for_kind("TPU v5").name == "v5p"
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        chip_spec_for_kind("TPU v9x")
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v9x")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [fake])
    with pytest.raises(ValueError, match="TPU v9x"):
        chip_spec()
    # and the block pickers no longer paper over it
    from ray_tpu.ops import default_flash_blocks, default_paged_block_r
    with pytest.raises(ValueError, match="TPU v9x"):
        default_flash_blocks(1024, 1024, 128)
    with pytest.raises(ValueError, match="TPU v9x"):
        default_paged_block_r(8, 128)


def test_sharding_rules_spec():
    rules = ShardingRules(batch=("dp", "fsdp"), embed="fsdp", mlp="tp")
    p = rules.spec_for(("batch", None, "embed"))
    assert p == jax.sharding.PartitionSpec(("dp", "fsdp"), None, "fsdp")


def test_shard_params_places_shards(cpu_mesh_devices):
    mesh = build_mesh(MeshSpec(fsdp=8))
    params = {"w": jnp.zeros((64, 16)), "b": jnp.zeros((16,))}
    axes = {"w": ("embed", "mlp"), "b": None}
    shardings = shard_params(params, axes, FSDP_RULES, mesh)
    placed = jax.device_put(params, shardings)
    # w sharded 8 ways on dim 0 (embed->fsdp), b replicated
    assert placed["w"].sharding.num_devices == 8
    assert len(placed["w"].addressable_shards) == 8
    assert placed["w"].addressable_shards[0].data.shape == (8, 16)
    assert placed["b"].addressable_shards[0].data.shape == (16,)


def test_infer_param_axes():
    params = {"big": jnp.zeros((512, 256)), "small": jnp.zeros((4, 4))}
    axes = infer_param_logical_axes(params)
    assert axes["big"] == ("embed", None)
    assert axes["small"] is None


def test_jit_fsdp_matmul_runs(cpu_mesh_devices):
    """End-to-end GSPMD: sharded param x sharded batch under jit."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
    w = jax.device_put(jnp.ones((32, 8)), NamedSharding(mesh, P("fsdp", None)))
    x = jax.device_put(jnp.ones((16, 32)),
                       NamedSharding(mesh, P(("dp", "fsdp"), None)))

    @jax.jit
    def f(x, w):
        return x @ w

    out = f(x, w)
    np.testing.assert_allclose(np.asarray(out), np.full((16, 8), 32.0))
