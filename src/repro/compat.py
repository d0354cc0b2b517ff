"""Mesh construction for the sharded paths.

Since jax 0.9, ``jax.make_mesh`` without ``axis_types`` builds
``Explicit`` axes, under which ``with_sharding_constraint`` in the
trainer and the lane-sharded ``jax.shard_map`` of the sweep engines
raise ``ShardingTypeError``.  Every mesh in the repo is built here with
``Auto`` axes, so the compiler keeps propagating shardings the way
those call sites expect (``launch/mesh.py``, ``train/trainer.py``,
``core/jaxplane.py`` / ``core/tcpjax.py``, tests).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "lane_mesh"]


def make_mesh(shape: Sequence[int], axis_names: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` over the local devices, every axis ``Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axis_names), axis_types=(AxisType.Auto,) * len(shape)
    )


def lane_mesh(n_shards: int) -> Mesh:
    """A 1-D ``('lanes',)`` mesh over ``n_shards`` devices: the lane axis
    the vectorized sweep engines partition over."""
    return make_mesh((n_shards,), ("lanes",))
