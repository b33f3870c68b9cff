"""Elastic re-mesh check on 8 host devices (subprocess; see
test_fault_elastic.py): drop a failed host's slice, rebuild the mesh,
re-shard live state, and keep training."""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.runtime.fault import reshard_tree, shrink_mesh


def main():
    mesh = jax.make_mesh((4, 2), ("data", "model"), (AxisType.Auto,) * 2)
    sh = {
        "w": NamedSharding(mesh, P("data", "model")),
        "b": NamedSharding(mesh, P(None, "model")),
    }
    tree = {
        "w": jax.device_put(jnp.arange(64.0).reshape(8, 8), sh["w"]),
        "b": jax.device_put(jnp.ones((4, 8)), sh["b"]),
    }

    # "fail" the host holding devices of data-slice 2
    failed = [d.id for d in np.asarray(mesh.devices)[2].flatten()]
    new_mesh = shrink_mesh(mesh, failed, ("data", "model"), shrink_axis="data")
    assert dict(new_mesh.shape) == {"data": 3, "model": 2}, new_mesh.shape

    new_tree = reshard_tree(tree, sh, new_mesh)
    # values preserved exactly
    np.testing.assert_array_equal(np.asarray(new_tree["w"]), np.arange(64.0).reshape(8, 8))
    # w: 8 rows % 3 data shards != 0 → fit-or-drop replicates rows, keeps model
    spec_w = new_tree["w"].sharding.spec
    assert spec_w[1] == ("model",) or spec_w[1] == "model", spec_w
    # training continues on the shrunk mesh
    def step(t):
        return jax.tree.map(lambda x: x * 2.0, t)

    out = jax.jit(step)(new_tree)
    np.testing.assert_array_equal(np.asarray(out["b"]), 2 * np.ones((4, 8)))
    print("ELASTIC-OK")


if __name__ == "__main__":
    main()
