"""HLO-analysis integration: trip-count-aware FLOP/byte/collective walks
against compiled programs with known analytic counts."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import (collective_totals, compute_totals,
                                       loop_trip_counts)
from repro.launch.mesh import make_mesh


def test_scan_flops_exact():
    """A scanned matmul must count trips x per-iteration dot flops."""
    def f(x, ws):
        def body(h, w):
            return h @ w, None
        h, _ = jax.lax.scan(body, x, ws)
        return h
    x = jnp.ones((16, 128))
    ws = jnp.ones((6, 128, 128))
    hlo = jax.jit(f).lower(x, ws).compile().as_text()
    out = compute_totals(hlo)
    assert out["flops"] == pytest.approx(6 * 2 * 16 * 128 * 128)
    trips = dict(loop_trip_counts(hlo))
    assert 6 in trips.values()


def test_nested_scan_multiplies():
    def f(x, ws):
        def outer(h, _):
            def inner(g, w):
                return g @ w, None
            g, _ = jax.lax.scan(inner, h, ws)
            return g, None
        h, _ = jax.lax.scan(outer, x, jnp.arange(3))
        return h
    x = jnp.ones((8, 64))
    ws = jnp.ones((4, 64, 64))
    hlo = jax.jit(f).lower(x, ws).compile().as_text()
    out = compute_totals(hlo)
    assert out["flops"] == pytest.approx(3 * 4 * 2 * 8 * 64 * 64)


def test_collectives_counted_per_device_with_trips():
    mesh = make_mesh((8,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(x):
        def body(h, _):
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, P()))          # forces all-gather
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, P("data")))
            return h, None
        h, _ = jax.lax.scan(body, x, jnp.arange(5))
        return h.sum()

    x = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    with jax.set_mesh(mesh):
        hlo = jax.jit(
            f, in_shardings=NamedSharding(mesh, P("data"))
        ).lower(x).compile().as_text()
    coll = collective_totals(hlo)
    # at least one all-gather per loop iteration, counted 5x
    ag = coll["counts"].get("all-gather", 0)
    assert ag >= 5, coll
    assert coll["total_bytes"] > 0


def test_train_step_lowers_on_local_mesh_and_parses():
    """End-to-end: the dry-run lowering path on a tiny (2,4) local mesh —
    compile succeeds, the walk returns flops within 3x of 6·N·D, and
    collectives are present (FSDP/TP is active)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.distributed import sharding as SH
    from repro.models import params as PM
    from repro.optim import adamw
    from repro.train.step import make_train_step

    cfg = get_config("granite-3-2b").reduced(num_layers=2, d_model=256,
                                             vocab_size=512)
    mesh = make_mesh((2, 4), ("data", "model"))
    p_abs = PM.abstract_params(cfg)
    p_shard = SH.param_shardings(cfg, mesh, SH.DEFAULT_RULES)
    opt_cfg = adamw.OptConfig()
    opt_abs = jax.eval_shape(lambda p: adamw.init_opt_state(p, opt_cfg),
                             p_abs)
    opt_shard = {"mu": p_shard, "nu": p_shard,
                 "step": NamedSharding(mesh, P())}
    B, S = 8, 64
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    b_shard = SH.batch_shardings(mesh, batch)
    step = make_train_step(cfg, opt_cfg, remat="full", microbatches=2)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=(p_shard, opt_shard, b_shard),
                           out_shardings=(p_shard, opt_shard, None)
                           ).lower(p_abs, opt_abs, batch).compile()
    hlo = compiled.as_text()
    ct = compute_totals(hlo)
    coll = collective_totals(hlo)
    model = 6 * cfg.param_count() * B * S
    hlo_global = ct["flops"] * 8
    assert model / 3 < hlo_global < model * 6, (model, hlo_global)
    assert coll["total_bytes"] > 0
