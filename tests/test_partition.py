"""Logical-axis partitioning rules and relaxation."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec

from repro.dist.partition import (
    axis_size,
    input_sharding,
    logical_to_pspec,
    relaxed_pspec,
    shard,
    sharding_ctx,
    tree_shardings,
)
from repro.launch.mesh import auto_mesh


@pytest.fixture()
def mesh1():
    return auto_mesh((1,), ("data",))


def test_no_context_is_noop():
    x = jnp.ones((4, 4))
    assert shard(x, "batch", None) is x
    assert logical_to_pspec(("batch", None)) == PartitionSpec()


def test_logical_to_pspec_rules(mesh1):
    with sharding_ctx(mesh1):
        ps = logical_to_pspec(("batch", "mlp"))
    # 'model' axis absent in this mesh -> mlp falls to replicated
    assert ps == PartitionSpec("data", None)


def test_pspec_duplicate_mesh_axis_suppressed(mesh1):
    # embed and batch both map to 'data'; an axis may appear only once
    with sharding_ctx(mesh1):
        ps = logical_to_pspec(("batch", "embed"))
    assert ps == PartitionSpec("data", None)


def test_relaxation_drops_nondividing(mesh1):
    mesh = auto_mesh((1,), ("model",))
    rules = {"mlp": ("model",)}
    ps = relaxed_pspec((7,), ("mlp",), mesh, rules)
    assert ps == PartitionSpec("model")  # 1 divides everything
    # model axis absent
    mesh2 = auto_mesh((1,), ("data",))
    ps2 = relaxed_pspec((7,), ("mlp",), mesh2, rules)
    assert ps2 == PartitionSpec(None)


def test_axis_size_defaults(mesh1):
    assert axis_size("model") == 1  # no ctx
    with sharding_ctx(mesh1):
        assert axis_size("data") == 1
        assert axis_size("model") == 1


def test_tree_shardings_structure(mesh1):
    abs_tree = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    ax_tree = {"w": ("embed", "mlp")}
    sh = tree_shardings(abs_tree, ax_tree, mesh1)
    assert sh["w"].mesh.axis_names == ("data",)


def test_input_sharding_applied(mesh1):
    sh = input_sharding((8, 8), ("batch", None), mesh1)
    x = jax.device_put(jnp.ones((8, 8)), sh)
    assert x.sharding == sh


def test_shard_constraint_inside_jit(mesh1):
    with sharding_ctx(mesh1):
        @jax.jit
        def f(x):
            return shard(x, "batch", None) * 2

        y = f(jnp.ones((4, 4)))
    assert float(y[0, 0]) == 2.0
