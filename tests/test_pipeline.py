"""DT-FM shard_map pipeline: correctness + learning on a simulated mesh.

The pipeline path deadlocked when dispatched eagerly (XLA CPU rendezvous —
threads reach different collectives in different orders), so the step is
jitted inside ``pipeline_train_step``; these tests pin that and the
schedule's equivalence with a plain forward pass.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.opt import opt_config
from repro.data.pipeline import make_batch_fn
from repro.distributed.pipeline import (make_pipeline_loss,
                                        pipeline_train_step,
                                        stack_for_stages, unstack_stages)
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models import params as P
from repro.optim import adamw


def _tiny_cfg():
    return dataclasses.replace(
        opt_config("opt-125m"), name="opt-pipe-test", num_layers=4,
        d_model=128, num_heads=4, num_kv_heads=4, head_dim=32, d_ff=256,
        vocab_size=512)


def test_pipeline_loss_matches_plain_forward():
    """GPipe schedule over 2 stages == unpipelined forward loss."""
    cfg = _tiny_cfg()
    mesh = make_mesh((1, 2), ("data", "stage"))
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    ref_loss, _ = M.forward_train(params, cfg, batch)

    loss_fn = make_pipeline_loss(cfg, mesh, num_microbatches=2)
    staged = stack_for_stages(cfg, params, 2)
    with jax.set_mesh(mesh):
        pipe_loss = jax.jit(loss_fn)(params, staged, batch)
    np.testing.assert_allclose(float(pipe_loss), float(ref_loss),
                               rtol=5e-3)


def test_pipeline_trains():
    cfg = _tiny_cfg()
    mesh = make_mesh((2, 2), ("data", "stage"))
    opt_cfg = adamw.OptConfig(learning_rate=1e-3, warmup_steps=5,
                              decay_steps=40)
    init_fn, step_fn = pipeline_train_step(cfg, mesh, opt_cfg,
                                           num_microbatches=2)
    with jax.set_mesh(mesh):
        rest, staged, opt = init_fn(jax.random.PRNGKey(0))
        data = make_batch_fn(cfg, 4, 32, seed=0)
        losses = []
        for _ in range(25):
            batch = {k: jnp.asarray(v) for k, v in next(data).items()}
            rest, staged, opt, metrics = step_fn(rest, staged, opt, batch)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])
    # round-trip the staging
    back = unstack_stages(cfg, staged)
    assert back["s0_attn"]["wq"].shape[0] == cfg.num_layers
