"""The benchmark's own weights, made from the seed: what every family
shares.

Both the program under test and the plain reference receive these, so
neither takes weights from the other.  A family (``families/<family>.py``)
lays its canonical dictionary out as a tree of :data:`Spec` leaves;
:func:`tree` makes each leaf from a key of its own, derived from the seed
and the leaf's dotted name (and, for a stacked layer, the layer index),
so that the reference can make one layer at a time and get exactly the
values the program was given for that layer.

Matrices are normal with standard deviation 1/sqrt(fan-in); norm scales
are 1 + 0.05 N(0, 1); biases are 0.05 N(0, 1), so that every term of the
layer equations is exercised.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Spec = Tuple[Tuple[int, ...], str, int]     # shape, kind, fan-in


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number below 2**64 (seeds pass 32 bits)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, np.uint32(zlib.crc32(name.encode())))


def _make(spec: Spec, key: jax.Array, dtype) -> jax.Array:
    shape, kind, fan_in = spec
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        x = z / np.sqrt(fan_in)
    elif kind == "scale":
        x = 1.0 + 0.05 * z
    else:
        x = 0.05 * z
    return x.astype(dtype)


def tree(specs: Dict[str, Any], key: jax.Array, dtype, prefix: str = ""):
    """The leaves of ``specs`` (nested dicts of :data:`Spec`)."""
    out = {}
    for k, v in specs.items():
        name = prefix + k
        out[k] = tree(v, key, dtype, name + ".") if isinstance(v, dict) \
            else _make(v, leaf_key(key, name), dtype)
    return out


def flatten(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    """{dotted name: leaf} of a canonical dictionary."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, name + "."))
        else:
            out[name] = v
    return out


def unflatten(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for name, v in flat.items():
        node = out
        *path, last = name.split(".")
        for p in path:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def lookup(tree: Dict[str, Any], name: str) -> Any:
    for p in name.split("."):
        tree = tree[p]
    return tree
