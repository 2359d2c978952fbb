"""Production mesh builders.

A FUNCTION (not module-level constant) so importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to materialize the placeholder devices.

Every mesh is built with ``AxisType.Auto`` axes: the sharding rules pin
layouts with ``with_sharding_constraint`` and explicit jit in/out
shardings, which JAX's default ``Explicit`` axes reject.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape: Sequence[int], axes: Sequence[str], *,
                   devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (``devices`` defaults to
    ``jax.devices()``; pass described devices for an AOT compile)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for tests (requires >= prod(shape) devices; a v5e host
    has four chips)."""
    return make_auto_mesh(shape, axes)
