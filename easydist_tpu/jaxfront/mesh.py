"""Device-mesh management for the auto-parallel frontend.

The reference's jax mesh holder is 1D-only (easydist/jax/device_mesh.py:28);
here the mesh is a real `jax.sharding.Mesh` of any rank, with per-axis
interconnect metadata (`MeshAxisSpec`) driving the solver cost model.
Multi-host hybrid meshes put the DCN axis outermost
(`mesh_utils.create_hybrid_device_mesh`).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from easydist_tpu.autoflow.cost_model import MeshAxisSpec

_GLOBAL_MESH = None
_GLOBAL_AXIS_SPECS: Optional[List[MeshAxisSpec]] = None


def set_device_mesh(mesh, axis_specs: Optional[Sequence[MeshAxisSpec]] = None):
    """Install `mesh` (jax.sharding.Mesh) as the global mesh.  `axis_specs`
    defaults to all-ICI axes sized from the mesh."""
    global _GLOBAL_MESH, _GLOBAL_AXIS_SPECS
    _GLOBAL_MESH = mesh
    if axis_specs is None:
        axis_specs = [MeshAxisSpec(name=str(name), size=size)
                      for name, size in zip(mesh.axis_names,
                                            mesh.devices.shape)]
    _GLOBAL_AXIS_SPECS = list(axis_specs)


def get_device_mesh():
    return _GLOBAL_MESH


def get_axis_specs(mesh=None) -> List[MeshAxisSpec]:
    """Axis specs for `mesh` — the installed specs when it is the global
    mesh, else default all-ICI specs derived from the mesh itself."""
    if mesh is None or mesh is _GLOBAL_MESH:
        if _GLOBAL_AXIS_SPECS is None:
            raise RuntimeError("device mesh not set; call set_device_mesh or "
                               "pass mesh= to easydist_compile")
        return _GLOBAL_AXIS_SPECS
    return [MeshAxisSpec(name=str(n), size=s)
            for n, s in zip(mesh.axis_names, mesh.devices.shape)]


def make_device_mesh(shape: Optional[Sequence[int]] = None,
                     axis_names: Optional[Sequence[str]] = None,
                     devices=None,
                     dcn_axes: Sequence[str] = ()):
    """Build and install a Mesh.  Default: 1D over all devices.

    `dcn_axes` marks axes that cross slice boundaries so the solver prices
    them at DCN bandwidth.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if shape is None:
        shape = (len(devices),)
    if axis_names is None:
        axis_names = tuple(f"mesh{i}" for i in range(len(shape)))
    arr = np.array(devices).reshape(tuple(shape))
    mesh = Mesh(arr, axis_names=tuple(axis_names))
    specs = [MeshAxisSpec(name=str(n), size=s,
                          kind="dcn" if n in dcn_axes else "ici")
             for n, s in zip(axis_names, shape)]
    set_device_mesh(mesh, specs)
    return mesh


def put_on_mesh(state, mesh=None):
    """`state`'s leaves committed to `mesh`, each whole on every device of
    it: what a program compiled for a mesh of ONE device declares for every
    argument, so a serving pool born here goes into its first program as
    every program hands it back, and each compiles once.  The device's own
    buffer is kept (a new sharding on it, no copy) where the leaf already
    lives there.  `mesh=None` is the mesh `compile_step` resolves for a
    program compiled without one: the global mesh, made if there is none."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        mesh = get_device_mesh()
    if mesh is None:
        mesh = make_device_mesh()
    return jax.device_put(state, NamedSharding(mesh, PartitionSpec()))
