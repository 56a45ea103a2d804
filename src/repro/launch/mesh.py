"""Mesh construction: every mesh in the repo is built here.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (device count is locked at first backend init, and smoke
tests must see 1 CPU device while the dry-run sees 512 placeholders).

``jax.make_mesh`` gives ``Explicit`` axes by default; the sharding layer
(``distributed/act_sharding.py``'s constraints, the GPipe ``shard_map``
pipeline, the dry-run's ``in_shardings``) is written for ``Auto`` axes,
where GSPMD propagates shardings and ``with_sharding_constraint`` is a
hint rather than a type assertion.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              axis_types: Optional[Sequence[AxisType]] = None):
    """``jax.make_mesh`` over the visible devices, all axes ``Auto`` unless
    ``axis_types`` says otherwise."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=tuple(axis_types))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256-chip v5e pod, or 2 pods = 512 chips over DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_edge_mesh(num_stages: int, data_parallel: int = 1):
    """Edge-fleet mesh for DT-FM pipeline runs: (data, stage)."""
    return make_mesh((data_parallel, num_stages), ("data", "stage"))
