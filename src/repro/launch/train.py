"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the trainer loop for any registered architecture on whatever devices
exist.  ``--reduced`` (default on CPU) trains the smoke variant;
``--mesh data,model`` builds a local mesh from the visible devices so the
same entrypoint drives a laptop, an edge mesh simulation
(``--host-devices N``), or a real pod slice.  ``--local-sgd`` switches to
the DiLoCo-style local-update loop (``--replicas`` × ``--inner-steps``);
``--async`` upgrades it to bounded-staleness async outer updates
(``--quorum`` / ``--staleness-bound``), and ``--straggler-frac`` /
``--crash-prob`` / ``--link-flap-prob`` inject a deterministic,
seed-replayable fault plan (``--fault-seed``).

Telemetry: ``--trace-out trace.json`` captures a Chrome-trace /
Perfetto timeline of every step phase (data / dispatch / outer-sync /
checkpoint, with J + gCO2e attached); ``--metrics-out metrics.jsonl``
writes the metrics registry (per-phase step-time histograms with
p50/p95/p99, loss/grad-norm distributions, byte counters).  Validate
either with ``python -m repro.obs.validate <file>``.

Health: ``--health-out health.jsonl`` attaches the streaming detectors
(stragglers / link degradation / loss spikes — the async quorum then
excludes *detected* stragglers) and writes their alert record;
``--slo tokens_per_s=500,gco2e=5`` adds burn-rate-monitored SLOs, with
a one-line verdict summary at the end of the run.

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --steps 100
    PYTHONPATH=src python -m repro.launch.train --arch opt-125m \
        --local-sgd --replicas 2 --inner-steps 8 --steps 32 \
        --trace-out trace.json --metrics-out metrics.jsonl
"""

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-125m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=["none", "full", "dots", "dots_no_batch"])
    ap.add_argument("--full", action="store_true",
                    help="train the FULL config (needs real accelerators)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="fake host device count (CPU simulation)")
    ap.add_argument("--mesh", default="",
                    help="comma dims for a (data, model) mesh")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest complete checkpoint in "
                         "--checkpoint-dir before training; the "
                         "checkpoint may come from a DIFFERENT fleet "
                         "placement (layer-sliced shards are re-sliced "
                         "onto whatever runs now)")
    ap.add_argument("--device", default="laptop-m2pro",
                    help="energy-model device for the carbon ledger")
    ap.add_argument("--local-sgd", action="store_true",
                    help="run the DiLoCo local-update loop instead of "
                         "the plain trainer")
    ap.add_argument("--replicas", type=int, default=2,
                    help="local-SGD replica count")
    ap.add_argument("--inner-steps", type=int, default=8,
                    help="local-SGD inner steps per sync round (K)")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="bounded-staleness async outer updates instead "
                         "of the barrier sync round (implies --local-sgd)")
    ap.add_argument("--quorum", type=int, default=0,
                    help="async: replicas required before an outer "
                         "update fires (0 = all replicas)")
    ap.add_argument("--staleness-bound", type=int, default=0,
                    help="async: drop + resync replicas more than S "
                         "outer versions stale (0 = lockstep)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed the deterministic fault-injection plan")
    ap.add_argument("--straggler-frac", type=float, default=0.0,
                    help="fraction of replicas slowed 4-8x by the plan")
    ap.add_argument("--crash-prob", type=float, default=0.0,
                    help="per-round crash probability per replica")
    ap.add_argument("--link-flap-prob", type=float, default=0.0,
                    help="per-round link flap probability per replica")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace/Perfetto JSON timeline")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics registry as JSONL")
    ap.add_argument("--slo", default=None, metavar="K=V[,K=V...]",
                    help="monitor train SLOs and print end-of-run "
                         "verdicts; keys: tokens_per_s=<floor>, "
                         "staleness=<bound>, gco2e=<budget>, "
                         "horizon=<s> (e.g. --slo tokens_per_s=500,"
                         "gco2e=5)")
    ap.add_argument("--health-out", default=None,
                    help="attach the streaming health detectors "
                         "(straggler / link / loss-spike) and write "
                         "their alert record + SLO verdicts as JSONL")
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.core.carbon.accounting import CarbonLedger
    from repro.core.energy.devices import get_device
    from repro.core.energy.monitor import ComponentModel, EnergyMonitor
    from repro.launch.mesh import make_mesh
    from repro.obs import MetricsRegistry, Tracer, set_tracer
    from repro.train.trainer import TrainerConfig, train

    use_compile_cache()
    cfg = get_config(args.arch if args.full else args.arch + "-smoke")
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params on "
          f"{jax.device_count()} device(s)")

    registry = None
    if args.trace_out or args.metrics_out:
        # tracing on: span durations feed the registry's histograms, so
        # --metrics-out alone still gets per-phase step-time summaries
        registry = MetricsRegistry()
        set_tracer(Tracer(enabled=True, registry=registry,
                          process=f"train:{cfg.name}"))

    monitor = EnergyMonitor(ComponentModel.for_device(
        get_device(args.device)))
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume needs --checkpoint-dir")
    tc = TrainerConfig(steps=args.steps, batch=args.batch, seq_len=args.seq,
                       microbatches=args.microbatches, remat=args.remat,
                       checkpoint_dir=args.checkpoint_dir,
                       checkpoint_every=args.checkpoint_every,
                       resume=args.resume)

    fault_plan = None
    if args.straggler_frac or args.crash_prob or args.link_flap_prob:
        from repro.core.faultinject import FaultPlan
        fault_plan = FaultPlan(seed=args.fault_seed,
                               straggler_frac=args.straggler_frac,
                               crash_prob=args.crash_prob,
                               link_flap_prob=args.link_flap_prob)

    health = slo = None
    if args.health_out is not None or args.slo is not None:
        from repro.obs import HealthMonitor, SLOMonitor, train_slos
        health = HealthMonitor(registry=registry)
        if args.slo is not None:
            kv = dict(p.split("=", 1) for p in args.slo.split(",") if p)
            slo = SLOMonitor(train_slos(
                tokens_per_s_floor=float(kv.get("tokens_per_s", 0)),
                staleness_bound=float(kv.get("staleness", 0)),
                gco2e_budget=float(kv.get("gco2e", 0)),
                horizon_s=float(kv.get("horizon", 3600.0))),
                registry=health.registry)

    def _run():
        if args.local_sgd or args.async_mode:
            from repro.train.local_sgd import (LocalSGDConfig,
                                               train_local_sgd)
            ls = LocalSGDConfig(replicas=args.replicas,
                                inner_steps=args.inner_steps,
                                checkpoint_dir=args.checkpoint_dir,
                                checkpoint_every_rounds=args.checkpoint_every,
                                resume=args.resume,
                                async_mode=args.async_mode,
                                quorum=args.quorum or None,
                                staleness_bound=args.staleness_bound)
            return train_local_sgd(
                cfg, tc, ls,
                monitor=None if args.async_mode else monitor,
                metrics=registry, fault_plan=fault_plan, health=health)
        return train(cfg, tc, monitor=monitor, metrics=registry,
                     health=health)

    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(dims, ("data", "model")[: len(dims)])
        with jax.set_mesh(mesh):
            res = _run()
    else:
        res = _run()

    led = CarbonLedger()
    led.add_operational_wh("train", res.energy_wh)
    rate = res.steps_per_s
    print(f"[train] final loss {res.final_loss:.4f}  "
          f"{rate:.2f} steps/s  "
          f"{res.energy_wh:.3f} Wh modelled  "
          f"{led.operational_kg*1000:.3f} gCO2e")
    if getattr(res, "mode", "") == "async":
        print(f"[train] async: {res.outer_updates} outer updates, "
              f"{res.dropped_stale} dropped stale, {res.resyncs} resyncs, "
              f"{res.crashes} crashes, "
              f"{res.virtual_tokens_per_s:.0f} virtual tok/s")
        if res.fault_counts:
            faults = " ".join(f"{k}={v}"
                              for k, v in sorted(res.fault_counts.items()))
            print(f"[train] faults: {faults}")

    if slo is not None:
        tok_s = getattr(res, "virtual_tokens_per_s", 0.0) \
            or rate * args.batch * args.seq
        slo.observe("train_tokens_per_s", tok_s)
        elapsed = getattr(res, "virtual_time_s", 0.0) \
            or args.steps / max(rate, 1e-9)
        slo.observe("train_gco2e", led.operational_kg * 1000, t=0.0)
        slo.observe("train_gco2e", 0.0, t=elapsed)
    if health is not None:
        print(f"[train] health: {health.summary_line()}")
    if slo is not None:
        print(f"[train] {slo.summary_line()}")
    if args.health_out:
        health.dump_jsonl(args.health_out, slo=slo,
                          meta={"arch": cfg.name, "steps": args.steps,
                                "local_sgd": bool(args.local_sgd
                                                  or args.async_mode)})
        print(f"[train] health record: {args.health_out}")

    if args.trace_out:
        from repro.obs import get_tracer
        get_tracer().save_chrome_trace(args.trace_out)
        print(f"[train] trace: {args.trace_out} "
              f"({len(get_tracer().events)} events — open in "
              "https://ui.perfetto.dev)")
    if args.metrics_out:
        registry.dump_jsonl(args.metrics_out,
                            meta={"arch": cfg.name, "steps": args.steps,
                                  "local_sgd": args.local_sgd,
                                  "backend": jax.default_backend()})
        print(f"[train] metrics: {args.metrics_out} "
              f"({len(registry.names())} metrics)")


if __name__ == "__main__":
    main()
