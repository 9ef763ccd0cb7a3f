"""Production mesh construction (single pod 16x16; two pods 2x16x16).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_serving_mesh(n_devices: int | None = None):
    """A 1-D data-parallel mesh over the local devices — the serving tier's
    default placement (`MarvelProgram.shard()` with no mesh argument)."""
    n = n_devices if n_devices is not None else len(jax.devices())
    return _make_mesh((n,), ("data",))


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the batch shards over (DP): pod+data when present."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_chips(mesh) -> int:
    return mesh.devices.size
