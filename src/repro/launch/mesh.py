"""Production mesh definitions (pure functions — importing this module
never touches jax device state)."""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def _mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    # the sharding rules place arrays with PartitionSpecs, i.e. GSPMD's
    # Auto axes (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16 data x 16 model).
    Multi-pod: 2 pods x 256 = 512 chips ((pod, data, model) = (2,16,16))."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over this host's devices: its chips on a TPU
    host, forced host devices on the CPU (tests, examples)."""
    return _mesh((data, model), ("data", "model"))
