"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run entry point sets
``--xla_force_host_platform_device_count`` *before* importing jax; everything
else (smoke tests, benches) sees the real single CPU device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 v5e pod (256 chips) or 2 pods = 512 chips with a "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_tiny_mesh(*, multi_pod: bool = False) -> Mesh:
    """CI-scale stand-in (8 host devices): same axis structure, tiny extents."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def mesh_for_name(name: str) -> Mesh:
    if name == "single":
        return make_production_mesh(multi_pod=False)
    if name == "multi":
        return make_production_mesh(multi_pod=True)
    if name == "tiny":
        return make_tiny_mesh(multi_pod=False)
    if name == "tiny-multi":
        return make_tiny_mesh(multi_pod=True)
    raise KeyError(f"unknown mesh {name!r}")


MESH_DEVICE_COUNT = {"single": 256, "multi": 512, "tiny": 8, "tiny-multi": 8}
