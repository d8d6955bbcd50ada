import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from automodel_tpu.distributed import MeshConfig
from automodel_tpu.parallel import AxisRules, logical_to_shardings, with_logical_constraint


def test_mesh_build_infer_dp():
    ctx = MeshConfig(tp=2).build()
    assert ctx.sizes["tp"] == 2
    assert ctx.sizes["dp_shard"] == 4  # inferred from 8 virtual devices
    assert ctx.num_devices == 8
    assert ctx.dp_size == 4
    assert ctx.batch_size_divisor == 4


def test_mesh_build_explicit_mismatch():
    with pytest.raises(ValueError):
        MeshConfig(tp=2, dp_shard=8).build()
    with pytest.raises(ValueError):
        MeshConfig(tp=3).build()  # 8 % 3 != 0


def test_spec_aliases():
    ctx = MeshConfig(tp=2, cp=2, dp_shard=2).build()
    spec = ctx.spec("batch", "cp", None)
    assert spec == PartitionSpec(("dp_replicate", "dp_shard", "ep"), "cp", None)
    assert ctx.axis_size("dp") == 2
    assert ctx.axis_size("dp_cp") == 4


def test_axis_rules_spec_dedup():
    ctx = MeshConfig(tp=2).build()
    rules = AxisRules()
    # embed→dp_shard, mlp→tp
    spec = rules.spec(("embed", "mlp"), ctx)
    assert spec == PartitionSpec("dp_shard", "tp")
    # two logical axes mapping to tp: second loses it
    spec2 = rules.spec(("heads", "mlp"), ctx)
    assert spec2 == PartitionSpec("tp", None)


def test_logical_to_shardings_divisibility_fallback():
    ctx = MeshConfig(tp=2, dp_shard=4).build()
    specs = {"w": ("embed", "mlp")}
    shapes = {"w": (6, 128)}  # 6 not divisible by dp_shard=4
    sh = logical_to_shardings(specs, ctx, shapes=shapes)
    assert sh["w"].spec == PartitionSpec(None, "tp")


def test_param_sharding_places_data():
    ctx = MeshConfig(tp=2, dp_shard=4).build()
    sh = logical_to_shardings({"w": ("embed", "mlp")}, ctx)
    w = jax.device_put(np.zeros((8, 16), np.float32), sh["w"])
    assert w.sharding.spec == PartitionSpec("dp_shard", "tp")
    # each device holds 1/8 of the array
    assert w.addressable_shards[0].data.shape == (2, 8)


def test_with_logical_constraint_in_jit():
    ctx = MeshConfig(dp_shard=4, tp=2).build()

    @jax.jit
    def f(x):
        return with_logical_constraint(x * 2, ("act_batch", "act_seq", None), ctx)

    x = np.zeros((8, 16, 4), np.float32)
    y = f(x)
    assert y.shape == x.shape


def test_mesh_context_manager_sets_the_ambient_mesh():
    """`with ctx:` is jax.set_mesh: a bare PartitionSpec resolves against
    the mesh inside the block."""
    import jax.numpy as jnp

    ctx = MeshConfig(tp=2).build()
    with ctx:
        y = jax.jit(
            lambda x: jax.lax.with_sharding_constraint(
                x * 2, PartitionSpec("dp_shard", "tp")
            )
        )(jnp.ones((8, 8)))
    assert y.sharding.spec == PartitionSpec("dp_shard", "tp")
    assert y.sharding.mesh.shape["tp"] == 2
