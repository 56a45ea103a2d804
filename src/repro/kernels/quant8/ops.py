"""Public wrappers: quantize/dequantize arbitrary-shape arrays.

The flat input is zero-padded to whole kernel tiles (``block`` elements
per row, ``ROW_TILE`` rows per grid step), so the Pallas kernel runs for
every shape; padded rows quantize to zeros with scale 1 and are sliced
off by :func:`dequantize`.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quant8.kernel import (ROW_TILE, dequantize_blocks,
                                         quantize_blocks)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _padded_size(n: int, block: int) -> int:
    """Elements after padding ``n`` to whole rows and whole row tiles."""
    rows = -(-n // block)
    tile = min(ROW_TILE, rows)
    return -(-rows // tile) * tile * block


def quantize(x: jax.Array, block: int = 256, *, interpret=None
             ) -> Tuple[jax.Array, jax.Array, Tuple[int, ...]]:
    """Any-shape tensor -> (int8 flat, scales, original shape)."""
    if interpret is None:
        interpret = not _on_tpu()
    shape = x.shape
    flat = x.reshape(-1)
    pad = _padded_size(flat.shape[0], block) - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    q, s = quantize_blocks(flat, block=block, interpret=interpret)
    return q, s, shape


def dequantize(q: jax.Array, scale: jax.Array, shape: Tuple[int, ...],
               block: int = 256, dtype=jnp.float32, *, interpret=None
               ) -> jax.Array:
    if interpret is None:
        interpret = not _on_tpu()
    flat = dequantize_blocks(q, scale, block=block, dtype=dtype,
                             interpret=interpret)
    n = int(np.prod(shape))
    return flat[:n].reshape(shape)


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Blockwise symmetric int8 for KV-cache vectors: quant block = the
    trailing ``head_dim`` axis, one fp32 scale per (..., kv_head) vector —
    the same amax/127 scheme as :func:`quantize` with ``block = D``, kept
    shape-preserving so it can run inside the serve step's scatter (the
    flat kernel wants padded (N,) layouts).

    x: (..., D) -> (int8 (..., D), fp32 scales (...,)).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def dequantize_kv(q: jax.Array, scale: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_kv`: (..., D) int8 + (...,) scales."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)
