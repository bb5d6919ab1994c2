"""Device mesh management.

ref: the reference's HybridCommunicateGroup topology
(python/paddle/distributed/fleet/base/topology.py) carves the NCCL world
into dp/mp/pp/sharding sub-groups. TPU-native: one jax.sharding.Mesh with
named axes; every sub-group is just an axis name. auto_parallel's
ProcessMesh maps here too.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_global_mesh: Mesh | None = None


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh
    return mesh


def get_mesh() -> Mesh:
    global _global_mesh
    if _global_mesh is None:
        _global_mesh = Mesh(np.array(jax.devices()), ("dp",))
    return _global_mesh


def build_mesh(shape_dict) -> Mesh:
    """shape_dict: ordered {axis_name: size}; -1 means 'rest of devices'."""
    names = list(shape_dict)
    sizes = [shape_dict[n] for n in names]
    n_dev = len(jax.devices())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n_dev // known
    total = int(np.prod(sizes))
    assert total == n_dev, f"mesh {dict(zip(names, sizes))} != {n_dev} devices"
    devs = np.array(jax.devices()).reshape(sizes)
    return Mesh(devs, tuple(names))


class DeviceMesh:
    """ref: paddle.distributed.auto_parallel ProcessMesh-alike."""

    def __init__(self, mesh_or_shape, dim_names=None):
        if isinstance(mesh_or_shape, Mesh):
            self._mesh = mesh_or_shape
        else:
            arr = np.asarray(mesh_or_shape)
            if arr.ndim == 1 and dim_names is None:
                dim_names = ("x",)
            devs = np.array(jax.devices())[arr.reshape(-1)].reshape(arr.shape)
            self._mesh = Mesh(devs, tuple(dim_names))

    @property
    def mesh(self):
        return self._mesh

    @property
    def shape(self):
        return dict(self._mesh.shape)

    @property
    def dim_names(self):
        return list(self._mesh.axis_names)

    def get_rank_by_dim_and_process_id(self, dim, pid):
        return pid

    def __enter__(self):
        self._ctx = self._mesh.__enter__()
        return self

    def __exit__(self, *a):
        return self._mesh.__exit__(*a)


ProcessMesh = DeviceMesh
