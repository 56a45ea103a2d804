"""jit-able train step: forward + backward + AdamW, one function per config.

The returned closure is pure (params, opt_state, batch) -> (params,
opt_state, metrics) and is what ``launch/dryrun.py`` lowers against the
production mesh and what the trainer loop jits for real execution.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.optim.compress import CompressConfig, compress_grads

PyTree = Any


def _split_microbatches(batch: Dict[str, jax.Array], m: int
                        ) -> Dict[str, jax.Array]:
    """Reshape every batch leaf to (m, B/m, ...); positions (3,B,S) on dim 1."""
    out = {}
    for k, v in batch.items():
        if k == "positions" and v.ndim == 3:
            out[k] = v.reshape(v.shape[0], m, v.shape[1] // m, v.shape[2]) \
                      .swapaxes(0, 1)
        else:
            out[k] = v.reshape((m, v.shape[0] // m) + v.shape[1:])
    return out


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.OptConfig, *,
                    remat: str = "none",
                    compress: Optional[CompressConfig] = None,
                    attn_impl: str = "chunked",
                    microbatches: int = 1) -> Callable:
    """Gradient-accumulation microbatching: activation memory scales with
    B/microbatches while the optimizer update stays per-global-batch —
    the standard big-model memory/throughput trade.

    ``remat`` defaults to "none" here AND in ``TrainerConfig`` (they used to
    disagree: "full" vs "none", so the trainer silently rematerialized
    nothing while dry-runs rematerialized everything).  Rematerialization is
    a memory/compute trade that only pays off at real model scale, so it is
    opt-in: the big-model launch paths (``launch/dryrun``, ``launch/train``)
    pass ``remat`` explicitly."""

    def loss_fn(p, mb):
        return M.forward_train(p, cfg, mb, remat=remat, attn_impl=attn_impl)

    def train_step(params: PyTree, opt_state: Dict[str, Any],
                   batch: Dict[str, jax.Array]
                   ) -> Tuple[PyTree, Dict[str, Any], Dict[str, jax.Array]]:
        if microbatches > 1:
            mbs = _split_microbatches(batch, microbatches)

            def body(acc, mb):
                gsum, lsum = acc
                (loss, mets), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (gsum, lsum + loss), None

            # zeros_like inherits the param sharding: the accumulator (and
            # hence the per-microbatch grad reduction) stays FSDP-sharded
            # instead of forcing a replicated all-reduce
            g0 = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32),
                              params)
            (gsum, lsum), _ = jax.lax.scan(body, (g0, jnp.float32(0.0)), mbs)
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches
            metrics: Dict[str, jax.Array] = {}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        if compress is not None and compress.method != "none":
            grads, _ = compress_grads(grads, None, compress)
        with jax.named_scope("optimizer"):
            params, opt_state, opt_metrics = adamw.apply_updates(
                params, grads, opt_state, opt_cfg)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return params, opt_state, metrics

    return train_step


def make_eval_step(cfg: ModelConfig, *, remat: str = "none",
                   attn_impl: str = "chunked") -> Callable:
    """jit'd eval step.  Takes the SAME ``remat``/``attn_impl`` knobs as
    ``make_train_step`` so evaluation runs the configuration being trained
    (it used to hardcode the forward defaults and silently diverge — e.g. a
    pallas-trained model would eval through the chunked path)."""
    @jax.jit
    def eval_step(params: PyTree, batch: Dict[str, jax.Array]):
        loss, metrics = M.forward_train(params, cfg, batch, remat=remat,
                                        attn_impl=attn_impl)
        return dict(metrics, loss=loss)
    return eval_step
