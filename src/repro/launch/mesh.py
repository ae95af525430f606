"""Mesh construction: the one place a ``jax.sharding.Mesh`` is made.

FUNCTIONS, not module constants — importing this module never touches
jax device state (smoke tests must see 1 CPU device; only dryrun.py sets
XLA_FLAGS for 512 host devices).

Every mesh is built with ``AxisType.Auto`` axes. Since JAX 0.7
``jax.make_mesh`` defaults to ``AxisType.Explicit``, under which
sharding becomes part of every array's type and the sharded decode paths
(written for GSPMD propagation + ``with_sharding_constraint`` pins) fail
with ``ShardingTypeError`` at their first reshape.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with Auto (GSPMD-propagated) axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (tests)."""
    return make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per-chip usable)
