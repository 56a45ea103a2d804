"""The numbers that decide ``correct``, computed the same way for the
program, the lower-precision control and the planted faults.

Training reads three things against the float32 reference; a cell
compares those its ``limits/<cell>.json`` gives a limit, and prints the
rest:

* ``loss_gap``: the largest |loss - reference loss| over the first steps;
* ``grad_norm_gap``: the gradient of the first step as the optimizer got
  it, leaf by leaf: |norm - reference norm| over the larger of the
  reference's norm of that leaf and of the median leaf;
* ``update_norm_gap``: the same measure for each leaf's change over the
  first steps.

Leaves whose reference gradient is under a thousandth of the median
leaf's (a key's bias under the softmax, whose gradient is nought but for
rounding) move under Adam by round-off alone and are left out of both
norms, by that rule and never by name.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

SMALL_GRAD = 1e-3


def norms(flat: Dict[str, object]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in flat.items()}


def counted_leaves(ref_grad_norms: Dict[str, float]) -> List[str]:
    med = float(np.median(list(ref_grad_norms.values())))
    return sorted(k for k, v in ref_grad_norms.items()
                  if v >= SMALL_GRAD * med)


def norm_gap(got: Dict[str, float], ref: Dict[str, float],
             leaves: Sequence[str]) -> float:
    med = float(np.median([ref[k] for k in leaves]))
    return max(abs(got[k] - ref[k]) / max(ref[k], med) for k in leaves)


def train_readings(losses: Sequence[float], grad_norms: Dict[str, float],
                   update_norms: Dict[str, float], ref) -> Dict[str, float]:
    """``ref`` carries the reference's ``losses``, ``grad_norms`` and
    ``update_norms``."""
    leaves = counted_leaves(ref["grad_norms"])
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, ref["losses"])),
        "grad_norm_gap": norm_gap(grad_norms, ref["grad_norms"], leaves),
        "update_norm_gap": norm_gap(update_norms, ref["update_norms"],
                                    leaves),
    }
