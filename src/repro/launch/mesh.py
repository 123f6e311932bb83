"""Production meshes. Defined as functions so importing this module never
touches jax device state (device count is locked at first jax init)."""
from __future__ import annotations

import jax


def _mesh_kwargs(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds a leading pod=2 axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic re-mesh use smaller shapes)."""
    return jax.make_mesh(tuple(shape), tuple(axes), **_mesh_kwargs(len(axes)))


def data_axes(mesh) -> tuple:
    """Axes that carry the batch (pod acts as extra data parallelism)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
