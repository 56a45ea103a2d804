"""Fleet telemetry layer (``repro.obs``): span tracer + Chrome-trace
export, fixed-bucket histograms, the device-resident accumulator, the
disabled-tracer overhead contract, and the wiring through trainer /
local-SGD / serving engine / orchestrator / energy monitor."""

import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (Counter, DeviceAccumulator, Gauge, Histogram,
                       MetricsRegistry, NULL_SPAN, Tracer, get_tracer,
                       set_tracer)
from repro.obs.validate import (validate_chrome_trace,
                                validate_metrics_jsonl)

from conftest import tiny


@pytest.fixture
def tracer():
    """Enabled tracer installed as the process global; always restored."""
    tr = Tracer(enabled=True, process="test")
    old = set_tracer(tr)
    yield tr
    set_tracer(old)


# --------------------------------------------------------------------------- #
# Span tracer core
# --------------------------------------------------------------------------- #

def test_span_nesting_and_chrome_roundtrip(tracer, tmp_path):
    with tracer.span("outer", "test", step=3):
        time.sleep(0.002)
        with tracer.span("inner", "test") as sp:
            sp.set(found=True)
            time.sleep(0.001)
    tracer.instant("mark", "test", note="hi")
    tracer.counter("util", 0.5)

    by_name = {e["name"]: e for e in tracer.events}
    outer, inner = by_name["outer"], by_name["inner"]
    # the inner complete event nests inside the outer on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert outer["args"] == {"step": 3}
    assert inner["args"] == {"found": True}
    assert inner["dur"] >= 1e3          # slept 1ms; ts/dur are in µs

    path = tmp_path / "trace.json"
    tracer.save_chrome_trace(str(path))
    counts = validate_chrome_trace(str(path))
    assert counts["X"] == 2 and counts["i"] == 1 and counts["C"] == 1

    data = json.loads(path.read_text())
    evs = data["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "test" for e in meta)
    x = [e for e in evs if e["ph"] == "X"]
    assert all(isinstance(e["ts"], float) and e["dur"] >= 0 for e in x)
    assert all(e["ph"] == "i" and e["s"] == "t" for e in evs
               if e["name"] == "mark")


def test_detached_spans_cross_frames(tracer):
    h1 = tracer.begin("queued", "serve", track="req:a", uid="a")
    h2 = tracer.begin("queued", "serve", track="req:b", uid="b")
    h2.end(state="admitted")
    h1.end(state="admitted")            # out of order: detached, no stack
    ends = {e["args"]["uid"]: e for e in tracer.events}
    assert ends["a"]["args"]["state"] == "admitted"
    assert ends["b"]["ts"] <= ends["a"]["ts"] + ends["a"]["dur"]
    # distinct tracks get distinct tids
    assert ends["a"]["tid"] != ends["b"]["tid"]


def test_annotate_lands_on_innermost_open_span(tracer):
    with tracer.span("phase", "test"):
        tracer.annotate(energy_j=1.5)
        with tracer.span("sub", "test"):
            tracer.annotate(carbon_g=0.2)
    by_name = {e["name"]: e for e in tracer.events}
    assert by_name["phase"]["args"]["energy_j"] == 1.5
    assert by_name["sub"]["args"]["carbon_g"] == 0.2
    tracer.annotate(lost=True)          # outside any span: no-op, no crash
    assert not any("lost" in e["args"] for e in tracer.events)


def test_explicit_timestamp_events_for_sim_clocks(tracer):
    tracer.complete("restore", ts_s=12.5, dur_s=3.0, cat="sched",
                    track="fleet", bytes_moved=100)
    tracer.instant("churn", "sched", track="fleet", ts_s=20.0)
    ev = {e["name"]: e for e in tracer.events}
    assert ev["restore"]["ts"] == 12.5e6 and ev["restore"]["dur"] == 3.0e6
    assert ev["churn"]["ts"] == 20.0e6


def test_disabled_tracer_is_shared_null_span():
    tr = Tracer(enabled=False)
    sp = tr.span("x", "y", big="attrs")
    assert sp is NULL_SPAN and tr.begin("z") is NULL_SPAN
    with sp as s:
        s.set(a=1).end(b=2)             # all no-ops
    tr.instant("i")
    tr.counter("c", 1.0)
    tr.complete("x", ts_s=0, dur_s=1)
    tr.annotate(q=1)
    assert tr.events == []


def test_disabled_tracer_overhead_under_2pct():
    """The acceptance contract: one span per iteration of a tight loop on
    a DISABLED tracer stays under 2% of a ~50µs step body — i.e. the
    net per-call cost (span construction + with-enter/exit, min over
    repeats to shed scheduler noise) must be < 1µs.  Measured directly
    rather than as a wall-clock ratio: on shared CI hosts the body's
    own run-to-run jitter exceeds the span cost by an order of
    magnitude, which would make a ratio assertion test the host, not
    the tracer."""
    import timeit
    tr = Tracer(enabled=False)

    def with_span():
        with tr.span("step", "train", metric="train/step_s"):
            pass

    def bare():
        pass

    n = 50_000
    per_call = min(timeit.repeat(with_span, number=n, repeat=7)) / n
    floor = min(timeit.repeat(bare, number=n, repeat=7)) / n
    net_s = per_call - floor
    assert net_s < 1e-6, \
        f"disabled span costs {net_s*1e9:.0f} ns/call " \
        f"({net_s/50e-6:.2%} of a 50µs step body; budget 2%)"
    assert tr.events == []


def _xplane_events(path):
    """(plane, line, name, start_ns, dur_ns) of every event of the one
    ``.xplane.pb`` that ``jax.profiler`` wrote under ``path``."""
    from jax.profiler import ProfileData
    (pb,) = list(path.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(pb))
    return [(plane.name, line.name, e.name, e.start_ns, e.duration_ns)
            for plane in pd.planes for line in plane.lines
            for e in line.events]


def test_enabled_spans_land_on_the_profiler_clock(tmp_path):
    """An enabled tracer's spans (nested and detached) are also profiler
    annotations named ``<cat>.<name>``, on the host plane and the same
    clock as the ops they enclose; the in-memory events stay as they
    were, and simulated-clock events are not bridged."""
    tr = Tracer(enabled=True)
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        h = tr.begin("request", "serve")
        with tr.span("outer", "test"):
            f(x).block_until_ready()
        tr.end(h)
        tr.complete("restore", ts_s=1.0, dur_s=0.5, cat="sim")
    finally:
        jax.profiler.stop_trace()
    ev = _xplane_events(tmp_path)
    host = {name: (s, d) for plane, _, name, s, d in ev
            if plane.startswith("/host:")}
    assert "test.outer" in host and "serve.request" in host
    assert "sim.restore" not in host and "restore" not in host
    s0, d0 = host["test.outer"]
    ops = [(s, d) for _, line, name, s, d in ev
           if line.startswith("tf_XLA") and "dot" in name]
    assert ops, "no dot op in the trace"
    assert any(s0 <= s and s + d <= s0 + d0 for s, d in ops)
    r0, rd = host["serve.request"]
    assert r0 <= s0 and s0 + d0 <= r0 + rd
    assert [e["name"] for e in tr.events] == ["outer", "request", "restore"]


def test_compile_counter_counts_new_shapes_once():
    from repro.obs import compiles
    compiles.install()
    compiles.install()                  # idempotent: one listener

    def counted_fn(x):
        return x * 3 + 1
    f = jax.jit(counted_fn)
    n0 = compiles.count()
    f(jnp.ones(7)).block_until_ready()
    new = [e for e in compiles.events()[n0:] if "counted_fn" in e.fun_name]
    assert [e.kind for e in new] == ["trace", "compile"]
    assert new[1].fun_name == "jit(counted_fn)"
    assert all(e.end_s >= e.start_s for e in new)
    n1 = compiles.count()
    f(jnp.ones(7)).block_until_ready()   # same shape: nothing new
    assert compiles.count() == n1
    f(jnp.ones(9)).block_until_ready()   # a new shape compiles once
    new = [e for e in compiles.events()[n1:] if "counted_fn" in e.fun_name]
    assert [e.kind for e in new] == ["trace", "compile"]


def test_span_metric_feeds_registry_histogram():
    reg = MetricsRegistry()
    tr = Tracer(enabled=True, registry=reg)
    for _ in range(4):
        with tr.span("step", "train", metric="train/step_s"):
            time.sleep(0.001)
    h = reg.histogram("train/step_s")
    assert h.count == 4 and h.min >= 1e-3


# --------------------------------------------------------------------------- #
# Metrics: histograms / counters / gauges / registry
# --------------------------------------------------------------------------- #

def test_histogram_percentiles_vs_numpy():
    rng = np.random.RandomState(0)
    samples = np.exp(rng.normal(-2.0, 1.5, size=5000))   # spans decades
    h = Histogram()
    for v in samples:
        h.observe(float(v))
    # default layout: 120 log buckets over [1e-7, 1e4) — bucket edge
    # ratio (1e4/1e-7)^(1/120) ≈ 1.235, so interpolation is good to
    # ~25% relative; the tests pin half that margin above it
    for q in (50, 95, 99):
        ref = float(np.percentile(samples, q))
        got = h.percentile(q)
        assert abs(got - ref) / ref < 0.35, (q, got, ref)
    assert h.count == len(samples)
    assert math.isclose(h.sum, float(samples.sum()), rel_tol=1e-9)
    assert h.min == samples.min() and h.max == samples.max()


def test_histogram_edges_and_empty():
    h = Histogram(lo=1e-3, hi=1e3, nbuckets=10)
    assert math.isnan(h.percentile(50))
    h.observe(1e-5)                     # underflow
    h.observe(1e5)                      # overflow
    assert h.percentile(0) >= 1e-5 and h.percentile(100) <= 1e5
    snap = h.snapshot()
    assert snap["count"] == 2 and "p99" in snap
    with pytest.raises(ValueError):
        Histogram(lo=0.0)


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    reg.counter("a").inc(2)
    reg.counter("a").inc(3)
    assert reg.counter("a").value == 5
    g = reg.gauge("peak")
    g.set_max(0.3)
    g.set_max(0.1)                      # high-water keeps the peak
    assert g.value == 0.3
    with pytest.raises(TypeError):
        reg.histogram("a")
    assert "a" in reg and reg.names() == ["a", "peak"]


def test_metrics_dump_jsonl_schema(tmp_path):
    reg = MetricsRegistry()
    reg.counter("serve/tokens").inc(7)
    reg.gauge("serve/kv_utilization_peak").set_max(0.4)
    reg.histogram("serve/ttft_s").observe(0.01)
    path = tmp_path / "metrics.jsonl"
    reg.dump_jsonl(str(path), meta={"arch": "opt-125m"})
    counts = validate_metrics_jsonl(str(path))
    assert counts == {"meta": 1, "metric": 3}


def test_validate_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X", "pid": 1}]}))
    with pytest.raises(ValueError):
        validate_chrome_trace(str(bad))
    empty = tmp_path / "no_spans.json"
    empty.write_text(json.dumps({"traceEvents": [
        {"ph": "M", "pid": 1, "tid": 0, "name": "process_name"}]}))
    with pytest.raises(ValueError, match="no complete"):
        validate_chrome_trace(str(empty))


def test_validate_bench_json_schema_and_claims(tmp_path):
    """Bench artifacts: dispatched by shape (meta, no traceEvents);
    provenance keys are required and embedded claim verdicts must hold."""
    from repro.obs.validate import validate, validate_bench_json
    meta = {"commit": "abc123", "timestamp_utc": "2026-01-01T00:00:00Z",
            "jax_version": "0.0", "backend": "cpu"}
    good = tmp_path / "BENCH_x.json"
    good.write_text(json.dumps({
        "meta": meta,
        "claims": [{"text": "t", "value": 1.5, "lo": 1.3,
                    "hi": float("inf"), "ok": True}]}))
    assert validate(str(good)) == {"meta": 1, "claim": 1}

    no_meta = tmp_path / "no_meta.json"
    no_meta.write_text(json.dumps({"meta": {"commit": "abc"}}))
    with pytest.raises(ValueError, match="meta missing"):
        validate_bench_json(str(no_meta))

    failed = tmp_path / "failed.json"
    failed.write_text(json.dumps({
        "meta": meta,
        "claims": [{"text": "t", "value": 1.1, "lo": 1.3, "hi": 2.0,
                    "ok": False}]}))
    with pytest.raises(ValueError, match="claim 0 FAILED"):
        validate_bench_json(str(failed))


def test_device_accumulator_matches_eager_bit_for_bit():
    """Batched drain must route EXACTLY the values eager float() would:
    one device_get at the window boundary, zero numerical difference."""
    reg_acc, reg_eager = MetricsRegistry(), MetricsRegistry()
    acc = DeviceAccumulator(reg_acc)
    xs = [jnp.float32(1.0) / (i + 3) * jnp.sin(jnp.float32(i))
          for i in range(17)]
    for i, x in enumerate(xs):
        acc.observe("loss", x)
        if i % 3 == 0:
            acc.inc("steps", jnp.int32(1))
    drained = acc.drain()
    assert len(acc) == 0 and acc.drain() == []
    k = 0
    for i, x in enumerate(xs):
        reg_eager.histogram("loss").observe(float(x))
        k += 1
        if i % 3 == 0:
            reg_eager.counter("steps").inc(float(jnp.int32(1)))
            k += 1
    assert len(drained) == k
    a, b = reg_acc.snapshot(), reg_eager.snapshot()
    assert a["loss"]["sum"] == b["loss"]["sum"]          # bit-for-bit
    assert a["loss"]["min"] == b["loss"]["min"]
    assert a["loss"]["max"] == b["loss"]["max"]
    assert a["steps"]["value"] == b["steps"]["value"]


# --------------------------------------------------------------------------- #
# Energy monitor calibration (+ span attribution)
# --------------------------------------------------------------------------- #

def _monitor():
    from repro.core.energy.devices import LAPTOP_M2PRO
    from repro.core.energy.monitor import ComponentModel, EnergyMonitor
    return EnergyMonitor(ComponentModel.for_device(LAPTOP_M2PRO))


def test_energy_calibrate_full_history():
    mon = _monitor()
    for i in range(4):
        mon.record_step(flops=1e9 * (i + 1), duration_s=0.1)
    scale = mon.calibrate(measured_j=2.0)
    assert scale == pytest.approx(2.0 / sum(mon.raw_j))
    assert mon.total_j == pytest.approx(2.0)


def test_energy_calibrate_windowed_rescales_consistently():
    """Regression: windowed calibrate must (a) derive the scale from the
    window's UNSCALED raws and (b) rescale every recorded estimate, so
    totals never mix scales and repeated calibrations don't compound."""
    mon = _monitor()
    for i in range(6):
        mon.record_step(flops=2e9, duration_s=0.05 * (i + 1))
    s1 = mon.calibrate(measured_j=3.0, window=2)
    assert s1 == pytest.approx(3.0 / sum(mon.raw_j[-2:]))
    # every entry sits on the ONE new scale — estimate_i == raw_i * s1
    for r, e in zip(mon.raw_j, mon.estimates_j):
        assert e == pytest.approx(r * s1)
    assert sum(mon.estimates_j[-2:]) == pytest.approx(3.0)
    # idempotent: same measurement, same window -> same scale (the old
    # buggy form divided by already-scaled estimates and compounded)
    assert mon.calibrate(measured_j=3.0, window=2) == pytest.approx(s1)
    # and further steps record on the calibrated scale
    e_next = mon.record_step(flops=2e9, duration_s=0.05)
    assert e_next == pytest.approx(mon.raw_j[-1] * s1)


def test_energy_calibrate_empty_is_noop():
    mon = _monitor()
    assert mon.calibrate(measured_j=5.0) == 1.0
    mon.reset()
    assert mon.scale == 1.0 and mon.raw_j == [] and mon.estimates_j == []


def test_energy_and_carbon_annotate_enclosing_span(tracer):
    from repro.core.carbon.accounting import CarbonLedger
    mon = _monitor()
    led = CarbonLedger()
    with tracer.span("engine_step", "serve"):
        mon.record_step(flops=1e9, duration_s=0.01)
        led.add_operational_kwh("serve", 1e-6)
    (ev,) = tracer.events
    assert ev["args"]["energy_j"] == pytest.approx(mon.estimates_j[0])
    assert ev["args"]["carbon_g"] == pytest.approx(
        led.operational_kg * 1000.0)


# --------------------------------------------------------------------------- #
# Trainer + local SGD wiring
# --------------------------------------------------------------------------- #

def _opt_tiny():
    from repro.configs import get_config
    return tiny(get_config("opt-125m"))


def test_trainer_emits_phase_spans_and_metrics(tracer, tmp_path):
    from repro.train.trainer import TrainerConfig, train
    reg = MetricsRegistry()
    tracer.registry = reg
    tc = TrainerConfig(steps=4, batch=2, seq_len=16, log_every=2)
    res = train(_opt_tiny(), tc, metrics=reg)
    assert res.loop_compiles == 0          # the step compiled once

    names = {e["name"] for e in tracer.events}
    assert {"step", "data", "dispatch", "metrics_drain"} <= names
    steps = [e for e in tracer.events if e["name"] == "step"]
    assert len(steps) == 4
    assert [e["args"]["step"] for e in steps] == [0, 1, 2, 3]
    # phase spans nest inside their step span on the timeline
    s0 = steps[0]
    inner = [e for e in tracer.events
             if e["name"] in ("data", "dispatch")
             and s0["ts"] <= e["ts"] <= s0["ts"] + s0["dur"]]
    assert inner, "no phase spans inside step 0"

    snap = reg.snapshot()
    assert snap["train/step_s"]["count"] == 4       # span metric= hook
    assert snap["train/loss"]["count"] == 4         # device-acc drained
    assert snap["train/grad_norm"]["count"] == 4
    assert snap["train/steps"]["value"] == 4
    assert snap["train/tokens"]["value"] == 4 * 2 * 16

    path = tmp_path / "train_trace.json"
    tracer.save_chrome_trace(str(path))
    assert validate_chrome_trace(str(path))["X"] >= 4


def test_local_sgd_round_spans_and_pseudograd_bytes(tracer, tmp_path):
    from repro.train.local_sgd import LocalSGDConfig, train_local_sgd
    from repro.train.trainer import TrainerConfig
    reg = MetricsRegistry()
    tracer.registry = reg
    tc = TrainerConfig(steps=4, batch=2, seq_len=16, log_every=0)
    ls = LocalSGDConfig(replicas=2, inner_steps=2)
    res = train_local_sgd(_opt_tiny(), tc, ls, metrics=reg)

    names = {e["name"] for e in tracer.events}
    assert {"round", "inner_step", "pseudograd", "outer_sync"} <= names
    syncs = [e for e in tracer.events if e["name"] == "outer_sync"]
    assert len(syncs) == res.rounds == 2
    assert syncs[0]["args"]["wire_bytes_per_replica"] == \
        res.sync_wire_bytes_per_round

    snap = reg.snapshot()
    assert snap["local_sgd/rounds"]["value"] == res.rounds
    # per-round wire accounting: R replicas ship one pseudo-gradient each
    assert snap["local_sgd/pseudograd_bytes"]["value"] == \
        res.sync_wire_bytes_per_round * ls.replicas * res.rounds
    assert snap["local_sgd/round_s"]["count"] == res.rounds
    assert snap["local_sgd/inner_step_s"]["count"] == \
        res.rounds * ls.replicas * ls.inner_steps

    path = tmp_path / "local_sgd_trace.json"
    tracer.save_chrome_trace(str(path))
    assert validate_chrome_trace(str(path))["X"] >= 4


# --------------------------------------------------------------------------- #
# Serving engine wiring
# --------------------------------------------------------------------------- #

def _serve_setup(tracer, *, num_blocks=40, n=4):
    import dataclasses

    from repro.configs import get_config
    from repro.models import params as P
    from repro.serve.engine import EngineConfig, Request, ServeEngine
    cfg = dataclasses.replace(tiny(get_config("qwen2-7b")), num_kv_heads=2)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    reqs = [Request(uid=f"r{i}",
                    prompt=list(np.random.RandomState(i).randint(
                        0, cfg.vocab_size, 3 + 3 * i)),
                    max_new=5 + (3 * i) % 7)
            for i in range(n)]
    eng = ServeEngine(params, cfg, EngineConfig(
        max_slots=3, block_size=4, num_blocks=num_blocks,
        max_blocks_per_seq=8))
    return eng, reqs


def test_engine_request_lifecycle_spans_and_ttft(tracer, tmp_path):
    eng, reqs = _serve_setup(tracer)
    out = eng.run(reqs)
    assert set(out) == {r.uid for r in reqs}

    # every request's track tells queued -> prefill -> decode(finished)
    for r in reqs:
        track_tid = tracer._tracks[f"req:{r.uid}"]
        phases = [e for e in tracer.events
                  if e["tid"] == track_tid and e["ph"] == "X"]
        seq = [(e["name"], e["args"].get("state")) for e in phases]
        assert ("queued", "admitted") in seq
        assert ("prefill", "prefilled") in seq
        assert ("decode", "finished") in seq
        fin = next(e for e in phases if e["args"].get("state") == "finished")
        assert fin["args"]["tokens"] == len(out[r.uid].tokens)

    s = eng.stats()
    assert 0 < s["ttft_p50_s"] <= s["ttft_p99_s"]
    assert s["req_tokens_per_s_p50"] > 0
    snap = eng.metrics.snapshot()
    assert snap["serve/ttft_s"]["count"] == len(reqs)
    assert snap["serve/tokens_per_s"]["count"] == len(reqs)
    assert snap["serve/requests_finished"]["value"] == len(reqs)
    assert snap["serve/tokens"]["value"] == sum(
        len(c.tokens) for c in out.values())
    # the engine_step metric= hook feeds the TRACER's attached registry
    # (unset here), not the engine's own — the windows stay separable
    assert "serve/step_s" not in snap

    path = tmp_path / "serve_trace.json"
    tracer.save_chrome_trace(str(path))
    counts = validate_chrome_trace(str(path))
    assert counts["X"] >= 3 * len(reqs) and counts.get("C", 0) > 0


def test_engine_kv_peak_survives_drain(tracer):
    """Satellite: per-step high-water KV stats from the registry stay
    nonzero AFTER every request finished and all blocks were freed —
    the instantaneous kv.stats() read zero by then."""
    eng, reqs = _serve_setup(tracer)
    eng.run(reqs)
    assert eng.kv.stats()["utilization"] == 0.0     # all evicted
    s = eng.stats()
    assert s["utilization_peak"] > 0.0
    assert eng.metrics.gauge("serve/kv_utilization_peak").value > 0.0
    assert eng.metrics.histogram("serve/kv_utilization",
                                 lo=1e-4, hi=2.0).count == eng.steps


def test_engine_preemption_keeps_ttft_clock(tracer):
    """TTFT is submit -> first EVER token: preempted-then-requeued
    requests must not reset the clock or double-observe."""
    eng, reqs = _serve_setup(tracer, num_blocks=9)
    out = eng.run(reqs)
    assert sum(c.preemptions for c in out.values()) > 0
    assert eng.metrics.counter("serve/preemptions").value > 0
    assert eng.metrics.histogram("serve/ttft_s").count == len(reqs)
    # a preempted phase span closed with state=preempted, then requeued
    states = [e["args"].get("state") for e in tracer.events
              if e["ph"] == "X"]
    assert "preempted" in states
    assert any(e["name"] == "queued" and e["args"].get("requeued")
               for e in tracer.events)
    assert any(e["name"] == "preempt" and e["ph"] == "i"
               for e in tracer.events)


def test_engine_without_tracer_still_serves():
    """Default (disabled) tracer: no events, but registry metrics and
    stats still work — telemetry is opt-in, never load-bearing."""
    assert not get_tracer().enabled
    eng, reqs = _serve_setup(get_tracer())
    out = eng.run(reqs)
    assert len(out) == len(reqs)
    assert get_tracer().events == []
    assert eng.stats()["ttft_p50_s"] > 0


# --------------------------------------------------------------------------- #
# Orchestrator fleet events
# --------------------------------------------------------------------------- #

def test_orchestrator_fleet_timeline_on_sim_clock(tracer, tmp_path):
    from repro.configs.opt import opt_config
    from repro.core.sched.orchestrator import (Orchestrator, SimConfig,
                                               make_fleet)
    cfg = opt_config("opt-125m")
    fleet = make_fleet({"laptop-m2pro": 4, "smartphone-sd888": 6},
                       regions=("europe", "north_america"), seed=2)
    r = Orchestrator(cfg, fleet, SimConfig(
        total_steps=60, seed=5, checkpoint_interval=20)).run()

    names = [e["name"] for e in tracer.events]
    assert names.count("step") == r.steps_done
    assert "replan" in names and "ckpt_write" in names
    if r.membership_changes:
        assert "churn" in names
    if r.restores:
        assert "restore" in names
    # events ride the SIMULATED clock: monotone non-decreasing sim time,
    # total span ~ the sim's wall result (µs = s * 1e6)
    steps = [e for e in tracer.events if e["name"] == "step"]
    ts = [e["ts"] for e in steps]
    assert ts == sorted(ts)
    assert steps[-1]["ts"] + steps[-1]["dur"] <= r.wall_time_s * 1e6 + 1
    assert all("energy_wh" in e["args"] for e in steps)
    samples = [e for e in tracer.events if e["name"] == "fleet.active"]
    assert len(samples) == r.steps_done
    assert all(e["ph"] == "C" for e in samples)

    path = tmp_path / "fleet_trace.json"
    tracer.save_chrome_trace(str(path))
    assert validate_chrome_trace(str(path))["X"] >= r.steps_done
