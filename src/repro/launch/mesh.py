"""Production mesh construction (assignment spec).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain 512 placeholder devices.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic fallback shapes, tests).

    Axes are ``Auto``: ``jax.make_mesh`` defaults to ``Explicit`` axes,
    whose sharding-in-types rejects the plain gathers of the embedding
    lookup and the curvature engine's slot (un)permutation."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    """All non-model axes (batch/token sharding)."""
    return tuple(a for a in mesh.axis_names if a != "model")
