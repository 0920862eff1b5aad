"""Mesh construction: the production meshes and the elastic factory.

Importing this module never touches jax device state — meshes are built
inside functions only.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The assignment's production mesh.

    Single pod: (16, 16) = 256 chips, axes ("data", "model").
    Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") —
    the "pod" axis carries cross-pod data parallelism (DCN-class links).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def mesh_for(n_devices: int | None = None, model_parallel: int = 16) -> Mesh:
    """Elastic mesh factory: largest (data, model) grid for the devices
    actually present (used by train.py on restart after resize)."""
    n = n_devices or len(jax.devices())
    model = model_parallel
    while model > 1 and (n % model or (n // model) < 1):
        model //= 2
    data = n // model
    return _make_mesh((data, model), ("data", "model"))


def describe(mesh: Mesh) -> str:
    return f"mesh{dict(mesh.shape)} on {mesh.devices.size} devices"
