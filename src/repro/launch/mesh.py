"""Production meshes.

Single pod = 16 x 16 = 256 chips (TPU v5e pod), axes (data, model).
Multi-pod = 2 x 16 x 16 = 512 chips, axes (pod, data, model); 'pod' is an
extra data-parallel (or pipeline) axis whose collectives cross the DCN/ICI
pod boundary.

Defined as functions so importing this module never touches jax device
state (jax locks the device count on first backend init).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_debug_mesh"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int | None = None):
    """Small host-device mesh for tests (requires the XLA host-device flag)."""
    if pod is None:
        return _make_mesh((data, model), ("data", "model"))
    return _make_mesh((pod, data, model), ("pod", "data", "model"))
