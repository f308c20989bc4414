"""Production mesh construction (TPU v5e target).

Single pod : (16, 16)    axes ("data", "model")           = 256 chips
Multi-pod  : (2, 16, 16) axes ("pod", "data", "model")    = 512 chips

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS *before* any jax import).

Every mesh is built with ``AxisType.Auto`` axes: the model code places
arrays with ``with_sharding_constraint`` and lets the partitioner propagate
the rest, which Explicit axes (``jax.make_mesh``'s default) refuse at the
first gather whose output sharding is ambiguous (the embedding lookup).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for multi-device unit tests (run in subprocesses with
    --xla_force_host_platform_device_count)."""
    return _mesh(shape, axes)


def mesh_from_spec(spec: str):
    """Build a mesh from a CLI spec string.

    ``""``/``"none"`` -> no mesh;  ``"pod"``/``"multipod"`` -> the production
    meshes;  ``"DxM"`` / ``"PxDxM"`` (e.g. ``"4x2"``, ``"2x16x16"``) ->
    explicit shapes with axes ("data","model") / ("pod","data","model").
    """
    if not spec or spec == "none":
        return None
    if spec == "pod":
        return make_production_mesh()
    if spec == "multipod":
        return make_production_mesh(multi_pod=True)
    dims = tuple(int(d) for d in spec.split("x"))
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}.get(len(dims))
    if axes is None:
        raise ValueError(f"mesh spec '{spec}': expected 1-3 'x'-joined dims")
    return _mesh(dims, axes)


def client_axes(mesh) -> tuple:
    """Mesh axes that carry the federated client dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def num_clients(mesh) -> int:
    n = 1
    for a in client_axes(mesh):
        n *= mesh.shape[a]
    return n
