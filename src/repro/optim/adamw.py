"""AdamW (decoupled weight decay) over raw pytrees — no optax dependency.

Moments default to fp32; ``moment_dtype='bfloat16'`` halves optimizer-state
HBM for the 405B/671B dry-runs (recorded in EXPERIMENTS.md §Roofline).
Update math always runs in fp32 regardless of storage dtype.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


@dataclass(frozen=True)
class OptConfig:
    learning_rate: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    moment_dtype: str = "float32"
    warmup_steps: int = 100
    decay_steps: int = 10_000
    min_lr_ratio: float = 0.1


def schedule(cfg: OptConfig, step: jax.Array) -> jax.Array:
    """Linear warmup + cosine decay to ``min_lr_ratio``."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, step / jnp.maximum(cfg.warmup_steps, 1))
    frac = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.decay_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    scale = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos
    return cfg.learning_rate * warm * scale


def init_opt_state(params: PyTree, cfg: OptConfig) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.moment_dtype)
    # zeros_like keeps each param's sharding: moments live where their
    # params live (jnp.zeros would put them all on the default device)
    zeros = lambda p: jnp.zeros_like(p, dtype=dt)
    return {"mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def global_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def apply_updates(params: PyTree, grads: PyTree, state: Dict[str, Any],
                  cfg: OptConfig) -> Tuple[PyTree, Dict[str, Any], Dict[str, jax.Array]]:
    step = state["step"] + 1
    lr = schedule(cfg, step)
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9)) \
        if cfg.clip_norm > 0 else jnp.float32(1.0)

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    mdt = jnp.dtype(cfg.moment_dtype)

    def upd(p, g, mu, nu):
        g = g.astype(jnp.float32) * clip
        mu_f = mu.astype(jnp.float32) * b1 + (1 - b1) * g
        nu_f = nu.astype(jnp.float32) * b2 + (1 - b2) * jnp.square(g)
        mu_hat = mu_f / bc1
        nu_hat = nu_f / bc2
        delta = mu_hat / (jnp.sqrt(nu_hat) + cfg.eps)
        # decoupled weight decay on >=2D weights only (skip norms/biases)
        if p.ndim >= 2:
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        new_p = p.astype(jnp.float32) - lr * delta
        return new_p.astype(p.dtype), mu_f.astype(mdt), nu_f.astype(mdt)

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_mu = jax.tree.leaves(state["mu"])
    flat_nu = jax.tree.leaves(state["nu"])
    out = [upd(p, g, mu, nu)
           for p, g, mu, nu in zip(flat_p, flat_g, flat_mu, flat_nu)]
    new_params = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_mu = jax.tree.unflatten(tdef, [o[1] for o in out])
    new_nu = jax.tree.unflatten(tdef, [o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, {"mu": new_mu, "nu": new_nu, "step": step}, metrics
