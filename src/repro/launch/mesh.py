"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device state
(jax locks the device count on first backend init — the dry-run must set
XLA_FLAGS before any jax call).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: GSPMD propagates shardings from the constraints we place
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (TPU v5e); multi-pod adds a leading DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over host devices (tests / CPU runs)."""
    return _mesh((data, model), ("data", "model"))


def describe(mesh) -> str:
    return "x".join(f"{k}={v}" for k, v in mesh.shape.items())
