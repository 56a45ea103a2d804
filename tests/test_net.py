"""Net-subsystem tests: topology routing, analytic collective costs
against closed forms, compression/collective composition, planner
integration, and the local-SGD (DiLoCo-style) trainer."""

import dataclasses
import math

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.energy.devices import LAPTOP_M2PRO, SMARTPHONE_SD888
from repro.core.net import (NetParams, Topology, collective_cost,
                            hierarchical_allreduce, ring_allreduce,
                            sync_cost)
from repro.core.planner import dtfm
from repro.core.sched.carbon_aware import FleetDevice
from repro.optim.compress import CompressConfig, wire_bytes_count


def fleet(n, regions=("europe",), spec=LAPTOP_M2PRO):
    return [FleetDevice(spec=spec, region=regions[i % len(regions)],
                        device_id=i) for i in range(n)]


# ------------------------------------------------------------------ topology
def test_routing_hierarchy():
    topo = Topology.from_fleet(fleet(4, ("europe", "north_america")))
    # same region: 2 hops; cross region: 4 hops through the backbone
    assert len(topo.path("0", "2")) == 2
    assert len(topo.path("0", "1")) == 4
    assert topo.p2p_time_s(1e6, "0", "2") < topo.p2p_time_s(1e6, "0", "1")
    assert topo.path_bw_Bps("0", "2") == LAPTOP_M2PRO.net_bw_Bps


def test_wan_bottleneck_applies_cross_region_only():
    p = NetParams(wan_bw_Bps=1e6)          # WAN slower than access links
    topo = Topology.from_fleet(fleet(4, ("europe", "north_america")),
                               params=p)
    assert topo.path_bw_Bps("0", "1") == 1e6
    assert topo.path_bw_Bps("0", "2") == LAPTOP_M2PRO.net_bw_Bps


# ---------------------------------------------------------------- collectives
def test_ring_allreduce_matches_closed_form():
    p = NetParams(access_latency_s=0.005, access_jitter_s=0.002)
    topo = Topology.from_fleet(fleet(6), params=p)
    nbytes = 80e6
    c = ring_allreduce(topo, topo.devices, nbytes)
    n = 6
    bw = LAPTOP_M2PRO.net_bw_Bps
    delay = 2 * (0.005 + 0.002)            # two access hops per ring edge
    expect = 2 * (n - 1) / n * nbytes / bw + 2 * (n - 1) * delay
    assert c.time_s == pytest.approx(expect, rel=1e-12)
    # bandwidth-optimal volume: 2(N-1)/N * nbytes per device
    assert c.per_device_bytes["0"] == pytest.approx(
        2 * (n - 1) / n * nbytes)
    assert c.wan_bytes == 0.0


def test_hierarchical_beats_flat_ring_on_two_regions():
    p = NetParams(wan_bw_Bps=2e6, wan_latency_s=0.05)
    topo = Topology.from_fleet(fleet(16, ("europe", "north_america")),
                               params=p)
    nbytes = 100e6
    flat = ring_allreduce(topo, topo.devices, nbytes)
    hier = hierarchical_allreduce(topo, topo.devices, nbytes)
    assert hier.time_s < flat.time_s
    assert hier.wan_bytes < flat.wan_bytes


def test_hierarchical_degenerates_to_ring_on_one_region():
    topo = Topology.from_fleet(fleet(8))
    a = ring_allreduce(topo, topo.devices, 1e6)
    b = hierarchical_allreduce(topo, topo.devices, 1e6)
    assert b.time_s == pytest.approx(a.time_s)
    assert b.wire_bytes == pytest.approx(a.wire_bytes)


def test_collective_cost_trivial_group_and_unknown_algorithm():
    topo = Topology.from_fleet(fleet(2))
    assert collective_cost(topo, ["0"], 1e6, "ring").time_s == 0.0
    with pytest.raises(ValueError):
        collective_cost(topo, topo.devices, 1e6, "nope")


def test_sync_cost_composes_compression_and_interval():
    topo = Topology.from_fleet(fleet(4))
    n = 1_000_000
    full = sync_cost(topo, topo.devices, n, algorithm="ring",
                     compress=None, dtype_bytes=4)
    q8 = sync_cost(topo, topo.devices, n, algorithm="ring",
                   compress=CompressConfig(method="int8"), dtype_bytes=4)
    amort = sync_cost(topo, topo.devices, n, algorithm="ring",
                      compress=None, dtype_bytes=4, sync_interval=16)
    assert q8.wire_bytes < full.wire_bytes / 3     # ~4x over fp32
    assert amort.time_s == pytest.approx(full.time_s / 16)
    assert wire_bytes_count(n, None) == 4 * n


# -------------------------------------------------------------------- planner
def test_plan_rejects_oversubscribed_data_parallel():
    cfg = get_config("opt-125m")
    with pytest.raises(ValueError):
        dtfm.plan(cfg, [LAPTOP_M2PRO], batch=4, seq_len=64,
                  data_parallel=8)


def test_plan_topology_pricing_close_to_seed_model_single_region():
    """Single-region homogeneous fleets stay comparable to the seed's
    flat min-bandwidth scalar (the topology adds only latency terms)."""
    cfg = get_config("opt-125m")
    devs = [LAPTOP_M2PRO] * 3
    p = dtfm.plan(cfg, devs, batch=16, seq_len=512, microbatches=8)
    seed = dtfm.min_bw_comm_s(cfg, devs, batch=16, seq_len=512)
    assert p.comm_s_per_step >= seed                # latency can only add
    assert p.comm_s_per_step < seed * 1.5
    assert p.boundary_s_per_step > 0 and p.dp_sync_s_per_step == 0


def test_plan_local_update_amortizes_dp_sync():
    cfg = get_config("opt-125m")
    devs = [LAPTOP_M2PRO] * 2
    kw = dict(batch=16, seq_len=512, data_parallel=4,
              dp_regions=["europe", "europe", "north_america",
                          "north_america"], collective="hierarchical")
    every = dtfm.plan(cfg, devs, sync_interval=1, **kw)
    k16 = dtfm.plan(cfg, devs, sync_interval=16, **kw)
    assert k16.dp_sync_s_per_step == pytest.approx(
        every.dp_sync_s_per_step / 16)
    assert k16.step_time_s < every.step_time_s


def test_orchestrator_rebuilds_topology_and_charges_comm():
    from repro.configs.opt import opt_config
    from repro.core.sched.orchestrator import (Orchestrator, SimConfig,
                                               make_fleet)
    cfg = opt_config("opt-125m")
    fl = make_fleet({"laptop-m2pro": 4}, seed=0)
    res = Orchestrator(cfg, fl, SimConfig(total_steps=20, seed=0)).run()
    assert res.topology_rebuilds >= 1
    assert res.comm_s_total > 0
    assert 0 < res.comm_energy_wh < res.energy_wh


# ---------------------------------------------------------------- compression
def test_compress_error_state_none_without_error_feedback():
    import jax.numpy as jnp
    from repro.optim.compress import compress_grads
    g = {"w": jnp.ones((64,), jnp.float32)}
    cfgc = CompressConfig(method="int8", error_feedback=False)
    sent, err = compress_grads(g, None, cfgc)
    assert err is None
    # toggling error feedback on afterwards must not crash on shapes
    cfgc_ef = CompressConfig(method="int8", error_feedback=True)
    sent2, err2 = compress_grads(g, err, cfgc_ef)
    assert err2["w"].shape == g["w"].shape


# ------------------------------------------------------------------ local SGD
def _tiny_cfg():
    cfg = get_config("opt-125m").reduced(num_layers=2, d_model=128,
                                         vocab_size=512)
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


def test_local_sgd_k1_matches_plain_trainer():
    from repro.optim import adamw
    from repro.train.local_sgd import LocalSGDConfig, train_local_sgd
    from repro.train.trainer import TrainerConfig, train
    cfg = _tiny_cfg()
    tc = TrainerConfig(steps=6, batch=4, seq_len=32, log_every=0, seed=3)
    opt = adamw.OptConfig(learning_rate=1e-3, warmup_steps=2,
                          decay_steps=6)
    plain = train(cfg, tc, opt_cfg=opt)
    loc = train_local_sgd(
        cfg, tc, LocalSGDConfig(replicas=1, inner_steps=1, outer_lr=1.0,
                                outer_momentum=0.0, nesterov=False),
        opt_cfg=opt)
    # identical trajectory up to fp32 rounding of g - (g - l)
    np.testing.assert_allclose(plain.losses, loc.losses, rtol=1e-5,
                               atol=1e-5)


def test_local_sgd_decreases_loss_quickstart_size():
    """Integration: DiLoCo-style training (2 replicas, K=4, int8-
    compressed outer sync) learns on the quickstart-size model."""
    from repro.optim import adamw
    from repro.train.local_sgd import LocalSGDConfig, train_local_sgd
    from repro.train.trainer import TrainerConfig
    cfg = get_config("opt-125m").reduced(num_layers=4, d_model=256,
                                         vocab_size=2048)
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32")
    steps = 24
    topo = Topology.from_fleet(fleet(2, ("europe", "north_america")))
    res = train_local_sgd(
        cfg, TrainerConfig(steps=steps, batch=4, seq_len=64, log_every=0,
                           seed=0),
        # outer momentum 0.5, not DiLoCo's 0.9: that value is tuned for
        # K ~ 500; at K=4 it builds a ~7x outer step and the loss climbs
        # again after round 2, so the test passed only on init draws that
        # left round 0 high
        LocalSGDConfig(replicas=2, inner_steps=4, outer_lr=0.7,
                       outer_momentum=0.5,
                       compress=CompressConfig(method="int8")),
        adamw.OptConfig(learning_rate=3e-3, warmup_steps=2,
                        decay_steps=steps),
        topology=topo, sync_algorithm="hierarchical")
    assert res.round_losses[-1] < res.round_losses[0] * 0.9
    assert res.comm_time_s_per_step == pytest.approx(
        res.comm_time_s_per_round / 4)
    assert res.sync_wire_bytes_per_round > 0
