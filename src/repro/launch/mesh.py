"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state -- required because the dry-run forces 512 host
devices while tests/benches must see 1.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``.  ``jax.make_mesh``
    defaults to ``Explicit`` axes, under which bare-``PartitionSpec``
    sharding constraints and implicitly-resharding gathers are rejected;
    this code base places arrays through shardings and constraints and
    lets XLA's SPMD partitioner propagate the rest."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256 chips/pod) single-pod, or 2x16x16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None):
    """Arbitrary mesh for tests/small runs (e.g. (2, 2) on 4 CPU devices)."""
    if axes is None:
        axes = ("data", "model")[: len(shape)] if len(shape) <= 2 else (
            "pod", "data", "model"
        )
    return _auto_mesh(shape, axes)


def single_device_mesh():
    return _auto_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axes_size(mesh, axes: Tuple[str, ...]) -> int:
    """Product of the mesh extents of ``axes`` (= DP replica count for the
    batch axes; = shard count for the ZeRO state layout)."""
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
