"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The single-pod production mesh is 16x16 =
256 chips ("data", "model"); the multi-pod mesh adds a leading "pod" axis
(2 pods = 512 chips).  Batch/FSDP shard over ("pod","data"), tensor/expert
parallel over "model"; the AMG solver uses the same devices flattened to a
1-D "rank" axis (PETSc-style slabs).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_amg_mesh(ndev: int):
    """Flattened 1-D mesh for the distributed AMG row slabs."""
    from repro.dist.solver import rank_mesh
    return rank_mesh(jax.devices()[:ndev])


def data_axes(mesh) -> tuple:
    """Axes that shard the global batch (pod folds into data parallel)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
