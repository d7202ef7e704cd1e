"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required so smoke tests see 1 device while the
dry-run sees 512 virtual hosts).

Axes:
  pod    pure data parallelism across pods (DCN); gradients cross pods
         once per step. Elastic: any pod count works, shardings only
         name axes.
  data   FSDP + batch within a pod (ICI).
  model  TP / EP within a pod (ICI).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small-scale runs)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (everything but `model`)."""
    return tuple(a for a in mesh.axis_names if a != "model")
