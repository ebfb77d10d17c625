"""Mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run pins the device count via XLA_FLAGS
before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (the sharding rules place
    activations through constraints, not explicit-axis types)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, n_pods: int = 2):
    """16×16 = 256 chips per pod; ``n_pods``×16×16 multi-pod (default 2 =
    512 chips, the assignment's production mesh; larger pod counts are used
    to size state-dominated giants)."""
    shape = (n_pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_data: int = 4, n_model: int = 2):
    """Small mesh for fake-device subprocess tests."""
    return make_mesh((n_data, n_model), ("data", "model"))
