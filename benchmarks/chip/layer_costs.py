"""Operations and bytes that single layers of a training step require,
counted from shapes under the conventions of ``flops.py`` (a product of
(m, k) by (k, n) is 2 m k n operations; forward plus backward is 3 times
the forward's products; nothing recomputed counts; bytes are what the
layer must read and write at least, once).  Activations and the
weights' compute copies move at ``io_bytes`` each, gradients of float32
master weights at 4.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple


def mlp_cost(T: int, d: int, f: int, *, gated: bool,
             io_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one MLP sublayer's forward and backward over
    T tokens: 2 (3 if gated) matrices of d x f.  The forward reads x and
    the weights and writes y; the backward reads dy, x and the weights
    and writes dx and the weights' gradients."""
    w = (3 if gated else 2) * d * f
    flops = 3 * 2 * T * w
    nbytes = io_bytes * (2 * T * d + w) + io_bytes * (3 * T * d + w) + 4 * w
    return flops, nbytes


def lm_head_cost(T: int, d: int, V: int, *,
                 io_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of the output head and its cross entropy,
    forward and backward, over T tokens: the forward reads h, the V x d
    head and the labels and writes the loss; the backward reads h and the
    head and writes dh and the head's gradient."""
    flops = 3 * 2 * T * d * V
    nbytes = io_bytes * (2 * T * d + 2 * V * d + T * d) + 4 * T + 4 \
        + 4 * V * d
    return flops, nbytes


def adamw_cost(n_params: int) -> Tuple[float, float]:
    """(operations, bytes) of one AdamW update of ``n_params`` float32
    parameters with global-norm clipping: the clip reads each gradient
    (4 bytes); the update reads the parameter, gradient and both moments
    (16) and writes the parameter and both moments (12).  Its few
    operations per parameter are left out: the bytes bound it."""
    return 0.0, 32.0 * n_params


def param_count(family, conf: Dict[str, Any]) -> int:
    """Parameters of the configuration: every leaf of the family's
    canonical weights (``family.weights``), counted from their shapes."""
    import jax
    import jax.numpy as jnp
    from benchmarks.chip.seeded import seed_key
    shapes = jax.eval_shape(lambda k: family.weights(conf, k, jnp.float32),
                            jax.eval_shape(lambda: seed_key(0)))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
