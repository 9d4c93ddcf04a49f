"""Mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.  Every mesh in the repo comes
from :func:`make_mesh`, which marks all axes ``Auto``: the model code
places values with bare ``PartitionSpec``s in ``with_sharding_constraint``
(``distributed.sharding.maybe_shard``), which under ``jax.make_mesh``'s
default ``Explicit`` axes asserts a sharding instead of requesting one.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """(n, 1) ``("data", "model")`` mesh over the local devices (tests)."""
    return make_mesh((jax.device_count(), 1), ("data", "model"))
