"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16×16 = 256 chips (data, model).
Multi-pod: 2×16×16 = 512 chips (pod, data, model) — the `pod` axis composes
with `data` for data parallelism; gradient reduction crosses pods.
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


def data_axes(mesh) -> tuple[str, ...]:
    """Every mesh axis that carries batch/data parallelism (all but `model`)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU multi-device tests (requires host device override)."""
    return _make_mesh((n_data, n_model), ("data", "model"))
