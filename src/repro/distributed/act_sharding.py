"""Ambient-mesh activation sharding constraints.

``constrain(x, part0, part1, ...)`` applies ``with_sharding_constraint``
using whatever mesh axes exist in the ambient (jit-time) mesh; axes that
don't exist or don't divide the dim are silently dropped, so model code can
annotate once and run unchanged on a laptop (1 device), the edge mesh, or
the 512-chip production mesh.

The ambient mesh is the one ``jax.set_mesh`` installed; only its ``Auto``
axes carry constraints (meshes come from :mod:`repro.launch.mesh`, which
builds them Auto).
"""

from __future__ import annotations

from typing import Tuple, Union

import jax
from jax.sharding import AxisType, PartitionSpec as P

Part = Union[None, str, Tuple[str, ...]]

# canonical activation partitions
BATCH = ("pod", "data")
MODEL = ("model",)
# sentinel: force replication on this dim (plain None leaves it to GSPMD)
REPLICATED = "~replicated~"


def _auto_axis_sizes() -> dict:
    """{axis name: size} of the ambient mesh's Auto axes (empty without a
    mesh, and inside ``shard_map`` where the axes are Manual)."""
    am = jax.sharding.get_abstract_mesh()
    return {n: s for n, s, t in zip(am.axis_names, am.axis_sizes,
                                    am.axis_types)
            if t == AxisType.Auto}


def axis_extent(name: str) -> int:
    """Extent of a mesh axis in the ambient abstract mesh (1 if absent or
    not Auto) — lets model code pick sharding-dependent layouts at trace
    time without carrying the mesh around."""
    return _auto_axis_sizes().get(name, 1)


def constrain(x: jax.Array, *parts: Part) -> jax.Array:
    """Pin listed dims to mesh axes; unlisted/None dims stay UNCONSTRAINED
    so GSPMD remains free to shard them (crucial: a hard None would force
    replication and insert all-gathers against XLA's chosen layout)."""
    sizes = _auto_axis_sizes()
    if not sizes:
        return x
    used = set()
    clean = []
    pinned = False
    for i, part in enumerate(parts):
        dim = x.shape[i] if i < x.ndim else 1
        if part is None:
            clean.append(P.UNCONSTRAINED)
            continue
        if part == REPLICATED:
            clean.append(None)
            pinned = True
            continue
        names = (part,) if isinstance(part, str) else tuple(part)
        keep = []
        extent = 1
        for n in names:
            if n not in sizes or n in used:
                continue
            if dim % (extent * sizes[n]) == 0:
                keep.append(n)
                extent *= sizes[n]
        used.update(keep)
        if keep:
            pinned = True
            clean.append(tuple(keep))
        else:
            clean.append(P.UNCONSTRAINED)
    clean += [P.UNCONSTRAINED] * (x.ndim - len(clean))
    if not pinned:
        return x
    return jax.lax.with_sharding_constraint(x, P(*clean))
