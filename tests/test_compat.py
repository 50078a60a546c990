"""Mesh and shard_map helpers (launch/compat.py)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.launch import compat


def test_make_mesh_works_on_this_jax():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_axis_size_matches_mesh_axis():
    """jax.lax.axis_size inside compat.shard_map returns the mesh extent."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    out = compat.shard_map(
        lambda x: x * jax.lax.axis_size("data"),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    )(jnp.ones(()))
    assert float(out) == 1.0


def test_shard_map_runs_on_this_jax():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    fn = compat.shard_map(lambda x: x + 1, mesh=mesh,
                          in_specs=P(), out_specs=P())
    np.testing.assert_array_equal(np.asarray(fn(jnp.zeros((3,)))),
                                  np.ones((3,)))
