"""Production meshes (TPU v5e class).

Defined as functions, not module-level constants, so importing this module
never touches jax device state: a caller can still set
``xla_force_host_platform_device_count`` before the first mesh build,
while tests/benches see the 1-device smoke mesh.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: GSPMD propagates shardings
    and ``with_sharding_constraint`` / ``shard_map`` accept every axis
    (``jax.make_mesh`` alone now builds ``Explicit`` axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh() -> Mesh:
    """1-device mesh with production axis names, for CPU smoke tests."""
    return make_mesh((1, 1), ("data", "model"))
