"""jit'd public wrapper: (B,S,H,D) layout, CPU falls back to interpret mode.

Differentiable end-to-end: ``flash_attention_bhsd`` carries a custom VJP
(fused FA-2 Pallas backward), so ``attn_impl="pallas"`` is a valid training
path, not just an inference path.  The kernels' tiles are chosen from the
shapes (``kernel.pick_tiles``), so callers pass none; tiles that causality
or the window leave without a live pair fetch nothing and do no work.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention in (B,S,H,D) layout with GQA support.

    On non-TPU backends the Pallas kernel body runs in interpret mode —
    bit-exact kernel logic, Python execution (CI / CPU validation path).
    The layout swaps sit outside the custom VJP, so jax.grad through this
    wrapper hits the fused Pallas backward kernels.
    """
    if interpret is None:
        interpret = not _on_tpu()
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               scale=scale, interpret=interpret)
    return jnp.swapaxes(out, 1, 2)
