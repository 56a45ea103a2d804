"""Plain reference of AdamW (Loshchilov & Hutter), in float32, shared by
every family's reference: the training runner replays the first steps
with it on the reference's gradients.

Written from the published description and from nothing in the program
under test.  Weights, gradients and moments are canonical dictionaries
(``seeded.py``); each leaf is updated by its dotted name.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from benchmarks.chip.seeded import flatten, lookup, unflatten

Weights = Dict[str, Any]


def adamw_schedule(opt: Dict[str, Any], step: int) -> float:
    """Linear warm-up to ``learning_rate`` over ``warmup_steps``, then a
    cosine decay to ``min_lr_ratio`` of it at ``decay_steps``."""
    warm = min(1.0, step / max(opt["warmup_steps"], 1))
    frac = min(max((step - opt["warmup_steps"])
                   / max(opt["decay_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    scale = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) \
        * 0.5 * (1 + math.cos(math.pi * frac))
    return opt["learning_rate"] * warm * scale


@functools.lru_cache(maxsize=None)
def _adamw(b1: float, b2: float, eps: float, wd: float, clip_norm: float,
           no_decay: Tuple[str, ...]):
    def step(w, g, m, v, lr, bc1, bc2):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, clip_norm / jnp.maximum(gn, 1e-9))
        out_w, out_m, out_v, out_g = {}, {}, {}, {}
        for name, p in flatten(w).items():
            gg = lookup(g, name) * clip
            mm_ = b1 * lookup(m, name) + (1 - b1) * gg
            vv = b2 * lookup(v, name) + (1 - b2) * gg * gg
            d = (mm_ / bc1) / (jnp.sqrt(vv / bc2) + eps)
            if name not in no_decay:
                d = d + wd * p
            out_w[name], out_m[name], out_v[name], out_g[name] = \
                p - lr * d, mm_, vv, gg
        return (unflatten(out_w), unflatten(out_m), unflatten(out_v),
                unflatten(out_g))
    return jax.jit(step)


def adamw_step(w: Weights, g: Weights, m: Weights, v: Weights, step: int,
               opt: Dict[str, Any], no_decay: Iterable[str]):
    """AdamW (Loshchilov & Hutter) with the gradient clipped to a global
    norm of ``clip_norm`` first; the leaves named in ``no_decay`` take no
    decoupled weight decay.  Returns (w, m, v, clipped g)."""
    b1, b2 = opt["b1"], opt["b2"]
    fn = _adamw(b1, b2, opt["eps"], opt["weight_decay"], opt["clip_norm"],
                tuple(sorted(no_decay)))
    return fn(w, g, m, v, adamw_schedule(opt, step), 1 - b1 ** step,
              1 - b2 ** step)
