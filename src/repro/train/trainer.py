"""Trainer loop: jit'd train_step + data pipeline + checkpointing + the
paper's energy monitor wired per step.

Runs on whatever mesh is ambient — a laptop (1 device), the edge mesh, or
the production pod.  ``examples/quickstart.py`` and the integration tests
drive a ~100M-param model through a few hundred steps with decreasing loss.

The hot loop is zero-sync by default (``benchmarks/bench_train_step.py``
records the step-time deltas):

* **donation** — ``donate_argnums=(params, opt_state)``: XLA updates the
  parameter and optimizer buffers in place instead of allocating + copying
  a full model's worth of HBM every step.  Only requested on backends that
  implement donation (TPU/GPU) — see :func:`donation_supported`;
* **async metrics** — per-step metrics stay on device; the loop keeps the
  uncopied device scalars and fetches with a single ``jax.device_get``
  every ``log_every`` steps (and one bulk fetch at the end), instead of a
  blocking ``float(...)`` round-trip per step that drains the dispatch
  pipeline;
* **prefetch** — the next batch is staged host→device with
  ``jax.device_put`` right after the step is dispatched, overlapping input
  transfer with device compute (double buffering).

Passing an ``EnergyMonitor`` opts back into per-step host sync: energy
accounting needs true per-step wall-clock, which only exists at a sync
point.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.core import flops as F
from repro.core.energy.monitor import ComponentModel, EnergyMonitor
from repro.data.pipeline import make_batch_fn
from repro.obs import compiles
from repro.obs.metrics import DeviceAccumulator, MetricsRegistry
from repro.obs.trace import get_tracer
from repro.models import params as PM
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.train.step import make_train_step

PyTree = Any


@dataclass
class TrainerConfig:
    steps: int = 100
    batch: int = 8
    seq_len: int = 256
    log_every: int = 10
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_placement: Any = None   # PlacementSpec/CheckpointSpec: save
                                       # layer-sliced by stage (elastic)
    checkpoint_replication: int = 0    # §5 neighbour shard copies
    resume: bool = False        # restore the newest complete checkpoint
                                # (any layout/boundaries) before training
    remat: str = "none"         # matches the make_train_step default
    attn_impl: str = "chunked"  # "naive" | "chunked" | "pallas"
    microbatches: int = 1
    donate: bool = True         # donate (params, opt_state) into the jit
    async_metrics: bool = True  # no per-step host sync; bulk-fetch metrics
    prefetch: bool = True       # double-buffer host->device batch transfer
    seed: int = 0


@dataclass
class TrainerResult:
    losses: List[float] = field(default_factory=list)
    steps_per_s: float = 0.0            # includes the compile step
    steady_steps_per_s: float = 0.0     # excludes the compile step
    compile_time_s: float = 0.0         # first-step (trace+compile+run) time
    energy_wh: float = 0.0
    final_loss: float = float("nan")
    resumed_from_step: int = 0          # 0 when starting fresh
    loop_compiles: int = 0              # JAX traces + compiles after the
                                        # first step (``repro.obs.compiles``)


def donation_supported() -> bool:
    """Buffer donation lands on TPU/GPU; XLA's CPU backend ignores it and
    jax still pays per-call donation bookkeeping for nothing (measured ~7%
    step-time overhead on the bench config), so the trainer only requests
    donation where it can actually reuse buffers."""
    return jax.default_backend() != "cpu"


def effective_donate(tc: TrainerConfig) -> bool:
    return tc.donate and donation_supported()


def make_jit_train_step(cfg: ModelConfig, tc: TrainerConfig,
                        opt_cfg: adamw.OptConfig) -> Callable:
    """The trainer's jit: (params, opt_state) donated per
    ``effective_donate`` — requested donation ∧ backend support.

    Installs the process-wide compile counter (``repro.obs.compiles``),
    so that whoever drives the step can tell whether it compiled again
    inside its loop."""
    compiles.install()
    return jax.jit(
        make_train_step(cfg, opt_cfg, remat=tc.remat,
                        attn_impl=tc.attn_impl,
                        microbatches=tc.microbatches),
        donate_argnums=(0, 1) if effective_donate(tc) else ())


def train(cfg: ModelConfig, tc: TrainerConfig,
          opt_cfg: Optional[adamw.OptConfig] = None,
          monitor: Optional[EnergyMonitor] = None,
          metrics: Optional[MetricsRegistry] = None,
          health=None) -> TrainerResult:
    """``metrics`` opts into per-phase step-time histograms + loss /
    grad-norm distributions WITHOUT extra host syncs: device scalars
    batch in a :class:`DeviceAccumulator` and drain at the same
    log-window boundaries the async-metrics loop already uses.  Span
    tracing rides the process-global tracer (``repro.obs``): a disabled
    tracer (the default) reduces every ``span`` call to one attribute
    check, keeping the zero-sync loop inside the
    ``bench_train_step.py`` regression gate.

    ``health`` (a :class:`repro.obs.HealthMonitor`) receives every loss
    the loop fetches — at the same sync points, never adding one — so
    its loss-spike / divergence detector watches the run live."""
    opt_cfg = opt_cfg or adamw.OptConfig(
        learning_rate=3e-4, warmup_steps=max(10, tc.steps // 20),
        decay_steps=tc.steps)
    rng = jax.random.PRNGKey(tc.seed)
    params = PM.init_params(cfg, rng)
    opt_state = adamw.init_opt_state(params, opt_cfg)
    start_step = 0
    if tc.resume and tc.checkpoint_dir:
        # elastic resume: the checkpoint may have been written by ANY
        # placement (layer-sliced with different stage boundaries, or
        # leaf-modulo) — restore re-slices via its manifest either way,
        # so a changed fleet picks up exactly where the old one stopped
        found = ckpt.latest_complete_step(tc.checkpoint_dir)
        if found is not None:
            state = ckpt.restore(tc.checkpoint_dir,
                                 {"params": params, "opt": opt_state},
                                 step=found)
            params, opt_state = state["params"], state["opt"]
            start_step = found
            print(f"[trainer] resumed from step {found} "
                  f"({tc.checkpoint_dir})")
    step_fn = make_jit_train_step(cfg, tc, opt_cfg)
    data = make_batch_fn(cfg, tc.batch, tc.seq_len, tc.seed)

    step_flops = F.train_flops(cfg, tc.batch, tc.seq_len,
                               remat=tc.remat != "none")
    # the monitor needs true per-step wall clock -> forces the sync path
    sync_every_step = (not tc.async_metrics) or monitor is not None
    result = TrainerResult()
    pending: List[Dict[str, jax.Array]] = []   # device-resident metrics
    tr = get_tracer()
    acc = DeviceAccumulator(metrics) if metrics is not None else None

    batch = jax.device_put(next(data)) if tc.prefetch else None
    compiled_by_step0 = compiles.count()
    t0 = time.time()
    t_prev = t0
    for step in range(tc.steps):
        step_span = tr.span("step", "train", metric="train/step_s",
                            step=start_step + step)
        step_span.__enter__()
        if not tc.prefetch:
            with tr.span("data", "train", metric="train/data_s"):
                batch = jax.device_put(next(data))
        # forward+backward+optimizer are one fused jit; the span times
        # host-side dispatch under the async loop and true step time
        # under the sync loop (monitor present / async_metrics off)
        with tr.span("dispatch", "train", metric="train/dispatch_s"):
            params, opt_state, mx = step_fn(params, opt_state, batch)
        if tc.prefetch and step + 1 < tc.steps:
            # step is dispatched but not complete: stage the next batch now
            # so generation + transfer overlap with device compute
            with tr.span("data", "train", metric="train/data_s"):
                batch = jax.device_put(next(data))

        host: Optional[Dict[str, Any]] = None
        if sync_every_step:
            host = jax.device_get(mx)               # one sync per step
            result.losses.append(float(host["loss"]))
            if health is not None:
                health.observe_loss(float(host["loss"]))
            if metrics is not None:
                metrics.histogram("train/loss", lo=1e-4, hi=1e4) \
                    .observe(float(host["loss"]))
                metrics.histogram("train/grad_norm", lo=1e-4, hi=1e4) \
                    .observe(float(host["grad_norm"]))
        else:
            pending.append(mx)                      # no sync
            if acc is not None:
                # device scalars only — drained with ONE device_get at
                # the log-window boundary below (zero extra syncs)
                acc.observe("train/loss", mx["loss"])
                acc.observe("train/grad_norm", mx["grad_norm"])
        if step == 0:
            if host is None:
                jax.block_until_ready(mx["loss"])
            result.compile_time_s = time.time() - t0
            compiled_by_step0 = compiles.count()
        if monitor is not None:
            t_now = time.time()
            monitor.record_step(flops=step_flops,
                                duration_s=t_now - t_prev)
            t_prev = t_now
        if tc.log_every and step % tc.log_every == 0:
            if host is None:
                # drain the whole window in ONE device_get: bounds the
                # device-resident metrics backlog at log_every entries
                with tr.span("metrics_drain", "train"):
                    fetched = jax.device_get(pending)
                    if acc is not None:
                        acc.drain()
                result.losses.extend(float(m["loss"]) for m in fetched)
                if health is not None:
                    for m in fetched:
                        health.observe_loss(float(m["loss"]))
                host = fetched[-1]
                pending.clear()
            print(f"step {step:5d}  loss {float(host['loss']):.4f}  "
                  f"gnorm {float(host['grad_norm']):.3f}  "
                  f"lr {float(host['lr']):.2e}")
        if tc.checkpoint_every and tc.checkpoint_dir \
                and (step + 1) % tc.checkpoint_every == 0:
            with tr.span("checkpoint", "train",
                         metric="train/checkpoint_s",
                         step=start_step + step + 1):
                state = {"params": params, "opt": opt_state}
                if tc.checkpoint_placement is not None:
                    ckpt.save_for_placement(
                        tc.checkpoint_dir, start_step + step + 1, state,
                        tc.checkpoint_placement,
                        replication=tc.checkpoint_replication)
                else:
                    ckpt.save(tc.checkpoint_dir, start_step + step + 1,
                              state)
                ckpt.prune(tc.checkpoint_dir)
        step_span.__exit__(None, None, None)
    if pending:
        with tr.span("metrics_drain", "train"):
            fetched = jax.device_get(pending)       # one bulk sync at exit
        result.losses.extend(float(m["loss"]) for m in fetched)
        if health is not None:
            for m in fetched:
                health.observe_loss(float(m["loss"]))
    if acc is not None:
        acc.drain()
    if metrics is not None:
        metrics.counter("train/steps").inc(tc.steps)
        metrics.counter("train/tokens").inc(
            tc.steps * tc.batch * tc.seq_len)
    wall = time.time() - t0
    result.loop_compiles = compiles.count() - compiled_by_step0
    result.steps_per_s = tc.steps / wall
    if tc.steps > 1 and wall > result.compile_time_s:
        result.steady_steps_per_s = (tc.steps - 1) / (wall -
                                                      result.compile_time_s)
    else:
        result.steady_steps_per_s = result.steps_per_s
    result.final_loss = result.losses[-1]
    result.resumed_from_step = start_step
    if monitor is not None:
        result.energy_wh = monitor.total_wh
    return result
