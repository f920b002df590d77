"""Production meshes.

Importing this module never touches jax device state; meshes are built
inside functions only.  The dry-run (and only the dry-run) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so these shapes are constructible on the CPU container.

Every mesh has Auto axes: the sharding rules (``dist/sharding.py``) place
arrays with ``NamedSharding`` and ``with_sharding_constraint`` and leave
propagation to GSPMD, which ``jax.make_mesh``'s default Explicit axes
refuse.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None
              ) -> Mesh:
    """Arbitrary mesh for tests/small runs, e.g. make_mesh((2, 4))."""
    if axes is None:
        axes = ("data", "model") if len(shape) == 2 else \
               ("pod", "data", "model")[-len(shape):]
    return _auto_mesh(shape, axes)


def single_device_mesh() -> Mesh:
    return _auto_mesh((1, 1), ("data", "model"))
