"""Placement-layer tests: the plan→place→execute contract.

Pins the tentpole invariants:

* a ``PlacementSpec``'s analytic stage boundaries are exactly the layer
  slices the shard_map pipeline executes (stack/mask/unstack parity),
* a **non-uniform** pipelined model (3 stages over 8 layers) produces
  the same loss AND the same gradients as the unpipelined reference to
  fp32 tolerance — the padded scan slots are provably inert,
* topology-aware placement search never prices worse than round-robin
  on the same fleet (hypothesis property; round-robin is always in the
  candidate set),
* local-SGD maps replicas onto the placement's region groups.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.opt import opt_config
from repro.core.energy.devices import (CATALOG, CLOUD_A5000, LAPTOP_M2PRO,
                                       SMARTPHONE_SD888)
from repro.core.net import NetParams, Topology
from repro.core.placement import (PlacementSpec, StagePlacement,
                                  balanced_boundaries, ordered_placement,
                                  round_robin_placement, search_placement)
from repro.core.planner import dtfm
from repro.core.sched.carbon_aware import FleetDevice
from repro.distributed.pipeline import (make_pipeline_loss, stack_for_stages,
                                        stage_layer_mask, unstack_stages)
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models import params as P


def fleet(n, regions=("europe", "north_america"), specs=(LAPTOP_M2PRO,)):
    return [FleetDevice(spec=specs[i % len(specs)],
                        region=regions[i % len(regions)], device_id=i)
            for i in range(n)]


def _cfg8():
    cfg = dataclasses.replace(
        opt_config("opt-125m"), name="opt-place-test", num_layers=8,
        d_model=64, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=128,
        vocab_size=256)
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


# ----------------------------------------------------------------- spec shape
def test_spec_validates_contiguity_and_boundary_alignment():
    topo = Topology.from_fleet(fleet(4))
    cfg = opt_config("opt-125m")
    spec = search_placement(cfg, [LAPTOP_M2PRO] * 4,
                            topology=topo, nodes=list("0123"),
                            data_parallel=2, batch=8, seq_len=64)
    assert spec.data_parallel == 2 and spec.num_stages == 2
    assert spec.boundaries[0] == 0 and spec.boundaries[-1] == cfg.num_layers
    # a replica with shifted boundaries must be rejected
    bad = PlacementSpec(
        cfg.name, cfg.num_layers,
        [spec.pipelines[0],
         [StagePlacement(s.device, s.node,
                         range(s.layers.start + 1, s.layers.stop + 1))
          for s in spec.pipelines[1]]],
        topo)
    with pytest.raises(ValueError):
        bad.validate()


def test_balanced_boundaries_nonuniform_and_clamped():
    # 2:1 compute ratio -> laptop stages get more layers
    b = balanced_boundaries(12, [2.0, 1.0, 2.0, 1.0])
    assert b[0] == 0 and b[-1] == 12 and b == sorted(b)
    counts = [y - x for x, y in zip(b[:-1], b[1:])]
    assert counts[0] > counts[1]
    # more slots than layers: empty slots, never phantom layers
    b = balanced_boundaries(3, [1.0] * 10)
    assert b[-1] == 3 and all(y - x >= 0 for x, y in zip(b[:-1], b[1:]))


# ------------------------------------------------- spec == executed pipeline
def test_placement_boundaries_match_executed_stage_slices():
    """The analytic spec's layer slices are exactly what the executor
    stacks, masks, and un-stacks."""
    cfg = _cfg8()
    devs = [LAPTOP_M2PRO, CLOUD_A5000, LAPTOP_M2PRO]
    spec = ordered_placement(cfg, devs)
    counts = spec.layer_counts
    assert sum(counts) == cfg.num_layers and len(counts) == 3
    assert max(counts) > min(counts)          # heterogeneity -> non-uniform

    params = P.init_params(cfg, jax.random.PRNGKey(0))
    staged = stack_for_stages(cfg, params, spec)
    lmax = spec.max_stage_layers
    leaf = staged["s0_attn"]["wq"]
    assert leaf.shape[:2] == (3, lmax)
    mask = stage_layer_mask(cfg, spec)
    assert mask.shape == (3, lmax)
    assert [int(m.sum()) for m in mask] == counts
    # padded slots are zero, real slots match the source layers
    ref = params["decoder"]["g0"]["s0_attn"]["wq"]
    for i, (a, b) in enumerate(zip(spec.boundaries[:-1],
                                   spec.boundaries[1:])):
        np.testing.assert_array_equal(np.asarray(leaf[i, :b - a]),
                                      np.asarray(ref[a:b]))
        assert not np.asarray(leaf[i, b - a:]).any()
    # round-trip
    back = unstack_stages(cfg, staged, spec)
    np.testing.assert_array_equal(np.asarray(back["s0_attn"]["wq"]),
                                  np.asarray(ref))


def test_nonuniform_pipeline_matches_unpipelined_loss_and_grads():
    """3 stages over 8 layers (3|3|2): pipelined loss AND grads equal the
    plain forward to fp32 tolerance — masked padding is inert."""
    cfg = _cfg8()
    boundaries = [0, 3, 6, 8]
    mesh = make_mesh((1, 3), ("data", "stage"))
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    def ref_loss(p):
        loss, _ = M.forward_train(p, cfg, batch)
        return loss
    ref, ref_grads = jax.value_and_grad(ref_loss)(params)

    loss_fn = make_pipeline_loss(cfg, mesh, num_microbatches=2,
                                 boundaries=boundaries)
    staged = stack_for_stages(cfg, params, boundaries)

    def pipe_loss(p, st):
        return loss_fn(p, st, batch)

    with jax.set_mesh(mesh):
        pipe, (g_rest, g_staged) = jax.jit(
            jax.value_and_grad(pipe_loss, argnums=(0, 1)))(params, staged)

    np.testing.assert_allclose(float(pipe), float(ref), rtol=1e-5)
    g_decoder = unstack_stages(cfg, g_staged, boundaries)
    flat_ref = dict(jax.tree_util.tree_flatten_with_path(
        ref_grads["decoder"]["g0"])[0])
    flat_pipe = dict(jax.tree_util.tree_flatten_with_path(g_decoder)[0])
    assert flat_ref.keys() == flat_pipe.keys()
    for k in flat_ref:
        np.testing.assert_allclose(np.asarray(flat_pipe[k]),
                                   np.asarray(flat_ref[k]),
                                   rtol=2e-4, atol=1e-5, err_msg=str(k))
    # embed/head grads ride outside the pipelined region
    np.testing.assert_allclose(
        np.asarray(g_rest["embed"]["tok"]),
        np.asarray(ref_grads["embed"]["tok"]), rtol=2e-4, atol=1e-5)


def test_uniform_boundaries_keep_legacy_reshape_path():
    cfg = _cfg8()
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    a = stack_for_stages(cfg, params, 4)
    b = stack_for_stages(cfg, params, [0, 2, 4, 6, 8])
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    with pytest.raises(ValueError):
        stack_for_stages(cfg, params, 3)      # 8 % 3: needs boundaries


# -------------------------------------------------------------- planner side
def test_search_beats_round_robin_on_two_region_fleet():
    cfg = opt_config("opt-125m")
    # regions alternate per device, kinds per PAIR: the naive round-robin
    # carve-up cannot de-interleave both at once
    fl = [FleetDevice(spec=(LAPTOP_M2PRO, SMARTPHONE_SD888)[(i // 2) % 2],
                      region=("europe", "north_america")[i % 2],
                      device_id=i) for i in range(8)]
    topo = Topology.from_fleet(fl, params=NetParams(wan_bw_Bps=5e6))
    devices = [d.spec for d in fl]
    nodes = [str(d.device_id) for d in fl]
    kw = dict(batch=16, seq_len=512, microbatches=8)
    rr = dtfm.plan_placement(
        cfg, round_robin_placement(cfg, devices, topology=topo,
                                   nodes=nodes, data_parallel=2), **kw)
    ta = dtfm.plan_placement(
        cfg, search_placement(cfg, devices, topology=topo, nodes=nodes,
                              data_parallel=2, **kw), **kw)
    assert ta.step_time_s < rr.step_time_s
    assert ta.wan_bytes_per_step < rr.wan_bytes_per_step
    assert ta.placement.strategy.startswith("topology_aware")


def test_dp_regions_price_sync_without_moving_the_pipeline():
    """Legacy dp_regions semantics: it spreads the GRADIENT-SYNC replicas
    across regions while boundary activations stay priced over the real
    nodes' regions — a multi-region pipeline keeps its WAN boundary hop."""
    cfg = opt_config("opt-125m")
    topo = Topology.from_specs([LAPTOP_M2PRO, SMARTPHONE_SD888],
                               regions=["europe", "north_america"])
    kw = dict(batch=16, seq_len=512, data_parallel=2,
              topology=topo, nodes=["0", "1"],
              collective="hierarchical")
    with_regions = dtfm.plan(cfg, [LAPTOP_M2PRO, SMARTPHONE_SD888],
                             dp_regions=["europe", "north_america"], **kw)
    without = dtfm.plan(cfg, [LAPTOP_M2PRO, SMARTPHONE_SD888], **kw)
    # boundary pricing identical: dp_regions must not relocate pipelines
    assert with_regions.boundary_s_per_step == pytest.approx(
        without.boundary_s_per_step)
    assert with_regions.boundary_s_per_step > topo.p2p_time_s(
        1, "0", "0")                      # and it IS a cross-region hop
    # ... but the sync groups DO span the requested regions
    spec = with_regions.placement
    assert spec.dp_sync_nodes
    sync_regions = {topo_region
                    for g in spec.dp_sync_nodes for n in g
                    for topo_region in [spec.topology.device_region[n]]}
    assert sync_regions == {"europe", "north_america"}
    assert with_regions.dp_sync_s_per_step > without.dp_sync_s_per_step


def test_plan_placement_agrees_with_legacy_plan():
    """plan() is now a placement round-trip: pricing an ordered_placement
    directly must give the identical plan."""
    cfg = opt_config("opt-125m")
    devs = [LAPTOP_M2PRO, SMARTPHONE_SD888, CLOUD_A5000]
    kw = dict(batch=16, seq_len=256, microbatches=4)
    a = dtfm.plan(cfg, devs, **kw)
    b = dtfm.plan_placement(cfg, ordered_placement(cfg, devs), **kw)
    assert a.step_time_s == pytest.approx(b.step_time_s)
    assert a.total_energy_wh_per_step == pytest.approx(
        b.total_energy_wh_per_step)
    assert [s.layers for s in a.stages] == [s.layers for s in b.stages]


# ------------------------------------------------------- hypothesis property
def test_search_never_prices_worse_than_round_robin_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    specs_st = st.lists(st.sampled_from(
        [CATALOG["laptop-m2pro"], CATALOG["smartphone-sd888"],
         CATALOG["cloud-a5000"]]), min_size=2, max_size=8)

    @given(specs_st, st.integers(1, 2), st.integers(1, 3))
    @settings(max_examples=25, deadline=None)
    def prop(device_specs, dp, n_regions):
        if len(device_specs) < dp:
            return
        cfg = opt_config("opt-125m")
        regions = ["europe", "north_america", "asia"][:n_regions]
        fl = [FleetDevice(spec=s, region=regions[i % n_regions],
                          device_id=i)
              for i, s in enumerate(device_specs)]
        topo = Topology.from_fleet(fl)
        devices = [d.spec for d in fl]
        nodes = [str(d.device_id) for d in fl]
        kw = dict(batch=16, seq_len=128, microbatches=4)
        rr = dtfm.plan_placement(
            cfg, round_robin_placement(cfg, devices, topology=topo,
                                       nodes=nodes, data_parallel=dp),
            **kw)
        ta = dtfm.plan_placement(
            cfg, search_placement(cfg, devices, topology=topo, nodes=nodes,
                                  data_parallel=dp, **kw), **kw)
        assert ta.step_time_s <= rr.step_time_s * (1 + 1e-12)

    prop()


# ------------------------------------------------------------- local SGD map
def test_local_sgd_maps_replicas_onto_placement_region_groups():
    from repro.optim import adamw
    from repro.train.local_sgd import LocalSGDConfig, train_local_sgd
    from repro.train.trainer import TrainerConfig

    cfg = dataclasses.replace(
        opt_config("opt-125m").reduced(num_layers=2, d_model=64,
                                       vocab_size=256),
        param_dtype="float32", compute_dtype="float32")
    fl = fleet(4)
    topo = Topology.from_fleet(fl)
    spec = search_placement(cfg, [d.spec for d in fl], topology=topo,
                            nodes=[str(d.device_id) for d in fl],
                            data_parallel=2, batch=4, seq_len=32,
                            microbatches=2)
    ls = LocalSGDConfig(replicas=2, inner_steps=2)
    tc = TrainerConfig(steps=4, batch=4, seq_len=32, log_every=0, seed=0)
    opt = adamw.OptConfig(learning_rate=1e-3, warmup_steps=2, decay_steps=4)
    res = train_local_sgd(cfg, tc, ls, opt, placement=spec)
    assert len(res.replica_regions) == 2
    assert set(res.replica_regions) <= {"europe", "north_america"}
    assert res.comm_time_s_per_round > 0
    assert res.comm_time_s_per_step == pytest.approx(
        res.comm_time_s_per_round / ls.inner_steps)
    # replica-count mismatch and topology+placement double-spec both raise
    with pytest.raises(ValueError):
        train_local_sgd(cfg, tc, LocalSGDConfig(replicas=3, inner_steps=2),
                        opt, placement=spec)
    with pytest.raises(ValueError):
        train_local_sgd(cfg, tc, ls, opt, placement=spec, topology=topo)


# ------------------------------------------------------------- orchestrator
def test_orchestrator_replans_through_placement_api():
    from repro.core.sched.orchestrator import (Orchestrator, SimConfig,
                                               make_fleet)
    cfg = opt_config("opt-125m")
    fl = make_fleet({"laptop-m2pro": 4, "smartphone-sd888": 2},
                    regions=("europe", "north_america"), seed=1)
    res = Orchestrator(cfg, fl, SimConfig(total_steps=15, seed=1)).run()
    assert res.steps_done == 15
    assert res.last_placement.startswith("topology_aware")
    assert res.wan_bytes_total >= 0.0
