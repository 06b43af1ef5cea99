"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod topology: 16x16 = 256 chips per pod; 2 pods = 512 via DCI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for unit tests (requires enough host devices)."""
    return _mesh(shape, axes)
