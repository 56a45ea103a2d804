"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the continuous-batching paged-KV engine (``repro.serve.engine``) over
a mixed-length request set for any registered architecture (reduced
variant by default — CPU-runnable), prints tokens/s plus the per-token
energy/carbon estimate, and falls back to the dense ``greedy_generate``
path for architectures whose caches are not token-paged (SSM / MLA /
encoder-decoder).  A warmup generation runs before the timing window so
compile time never pollutes the tokens/s measurement.
"""

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--batch", type=int, default=8,
                    help="number of requests in the mixed-length set")
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="max prompt length (requests vary 4..prompt-len)")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--device", default="tpu-v5e",
                    help="energy/carbon profile from core.energy.devices "
                         "(smartphone-sd888 | laptop-m2pro | cloud-a5000 | "
                         "cloud-h100 | tpu-v5e)")
    ap.add_argument("--attn-impl", default="gather",
                    choices=["gather", "pallas"],
                    help="paged decode attention: XLA gather or the Pallas "
                         "flash-decode kernel (interpret mode off-TPU)")
    ap.add_argument("--kv-dtype", default="bfloat16",
                    choices=["bfloat16", "float32", "int8"],
                    help="paged KV pool dtype; int8 stores quantized pages "
                         "with per-vector fp32 scales (~0.53x the bf16 "
                         "bytes) and dequantizes inside the attention "
                         "gather")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt tokens fed per engine step, shared across "
                         "prefilling slots (1 = token-by-token)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable the prefix-cache index (every request "
                         "recomputes its full prompt)")
    ap.add_argument("--legacy", action="store_true",
                    help="force the dense greedy_generate path")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace/Perfetto JSON timeline "
                         "(per-request queued/prefill/decode lifecycle "
                         "spans + engine steps + KV counters)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine metrics registry as JSONL "
                         "(TTFT p50/p99, per-request tokens/s, KV "
                         "utilization histograms)")
    ap.add_argument("--slo", default=None, metavar="K=V[,K=V...]",
                    help="monitor serve SLOs (the engine defers "
                         "admissions while the TTFT SLO burns); keys: "
                         "ttft=<p99 s>, itl=<inter-token p99 s>, "
                         "gco2e=<budget>, horizon=<s> (e.g. "
                         "--slo ttft=0.5,gco2e=2)")
    ap.add_argument("--health-out", default=None,
                    help="write the SLO verdicts + alert record as "
                         "JSONL")
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.core.energy.devices import get_device
    from repro.models import model as M
    from repro.models import params as P

    use_compile_cache()
    device = get_device(args.device)
    cfg = get_config(args.arch if args.full else args.arch + "-smoke")
    print(f"[serve] {cfg.name}: {cfg.param_count()/1e6:.1f}M params "
          f"(energy profile: {device.name})")
    params = P.init_params(cfg, jax.random.PRNGKey(0))

    if args.trace_out or args.metrics_out:
        from repro.obs import Tracer, set_tracer
        set_tracer(Tracer(enabled=True, process=f"serve:{cfg.name}"))

    slo = health = None
    if args.slo is not None or args.health_out is not None:
        from repro.obs import HealthMonitor, SLOMonitor, serve_slos
        health = HealthMonitor()
        kv = dict(p.split("=", 1)
                  for p in (args.slo or "").split(",") if p)
        slo = SLOMonitor(serve_slos(
            ttft_p99_s=float(kv.get("ttft", 0.5)),
            inter_token_p99_s=float(kv.get("itl", 0.2)),
            gco2e_budget=float(kv.get("gco2e", 0)),
            horizon_s=float(kv.get("horizon", 3600.0))),
            registry=health.registry)

    if not args.legacy and M.paged_decode_supported(cfg):
        _run_engine(args, cfg, params, device, slo=slo, health=health)
    else:
        _run_legacy(args, cfg, params, device)
        if slo is not None:
            print(f"[serve] {slo.summary_line()}")

    if args.health_out and health is not None:
        health.dump_jsonl(args.health_out, slo=slo,
                          meta={"arch": cfg.name,
                                "requests": args.batch})
        print(f"[serve] health record: {args.health_out}")

    if args.trace_out:
        from repro.obs import get_tracer
        get_tracer().save_chrome_trace(args.trace_out)
        print(f"[serve] trace: {args.trace_out} "
              f"({len(get_tracer().events)} events — open in "
              "https://ui.perfetto.dev)")


def _mixed_requests(args, cfg, tag: str):
    import jax
    from repro.serve.engine import Request
    lens = [4 + (7 * i) % max(args.prompt_len - 3, 1)
            for i in range(args.batch)]
    reqs = []
    for i, L in enumerate(lens):
        toks = jax.random.randint(jax.random.PRNGKey(100 + i), (L,), 0,
                                  cfg.vocab_size)
        reqs.append(Request(uid=f"{tag}{i}", prompt=list(map(int, toks)),
                            max_new=args.max_new))
    return reqs


def _run_engine(args, cfg, params, device, slo=None,
                health=None) -> None:
    from repro.serve.engine import EngineConfig, ServeEngine
    from repro.serve.paged_cache import blocks_for

    block = 16
    per_seq = blocks_for(args.prompt_len + args.max_new, block) + 1
    ecfg = EngineConfig(max_slots=min(args.batch, 8), block_size=block,
                        num_blocks=per_seq * min(args.batch, 8) + 2,
                        max_blocks_per_seq=per_seq,
                        attn_impl=args.attn_impl,
                        cache_dtype=args.kv_dtype,
                        prefill_chunk=args.prefill_chunk,
                        prefix_sharing=not args.no_prefix_sharing)
    engine = ServeEngine(params, cfg, ecfg, device=device, slo=slo)
    # warmup compiles BOTH step shapes (C=1 decode + C=chunk mixed) and
    # the sampler; reset_stats() then zeroes the EnergyMonitor so the
    # reported J/token prices serving, not XLA compilation
    engine.warmup()
    engine.reset_stats()

    engine.run(_mixed_requests(args, cfg, "r"))
    s = engine.stats()
    print(f"[serve] engine: {int(s['tokens_generated'])} tokens in "
          f"{engine.wall_s:.2f}s ({s['tokens_per_s']:.1f} tok/s, "
          f"{int(s['steps'])} steps, {ecfg.max_slots} slots)")
    if "ttft_p50_s" in s:
        print(f"[serve] TTFT p50 {s['ttft_p50_s']*1e3:.1f} ms / "
              f"p99 {s['ttft_p99_s']*1e3:.1f} ms")
    if args.metrics_out:
        import jax
        engine.metrics.dump_jsonl(
            args.metrics_out,
            meta={"arch": cfg.name, "requests": args.batch,
                  "max_new": args.max_new, "attn_impl": args.attn_impl,
                  "backend": jax.default_backend()})
        print(f"[serve] metrics: {args.metrics_out} "
              f"({len(engine.metrics.names())} metrics)")
    print(f"[serve] paged KV: peak {s['peak_cache_bytes']/1e6:.2f} MB of "
          f"{s['pool_bytes']/1e6:.2f} MB pool "
          f"(peak frag {s['frag_tokens_peak']:.0f} tokens, "
          f"peak util {100*s['utilization_peak']:.0f}%)")
    print(f"[serve] fast path: prefix hit rate "
          f"{100*s['prefix_hit_rate']:.0f}% "
          f"({int(s['prefix_hit_tokens'])} tokens), "
          f"{int(s['cow_forks_total'])} CoW forks, "
          f"{s['kv_bytes_saved']/1e6:.2f} MB KV saved "
          f"(chunk={ecfg.prefill_chunk}, kv={ecfg.cache_dtype})")
    print(f"[serve] energy ({device.name}): {s['energy_j']:.2f} J "
          f"({s['j_per_token']:.3f} J/token, {s['carbon_g']:.4f} gCO2e)")
    if slo is not None:
        # carbon spend paces against the budget over the serving window
        slo.observe("serve_gco2e", s["carbon_g"], t=0.0)
        slo.observe("serve_gco2e", 0.0, t=max(engine.wall_s, 1e-9))
        deferred = int(engine.metrics.counter(
            "serve/admission_deferred").value)
        print(f"[serve] {slo.summary_line()} | admissions deferred "
              f"under burn: {deferred}")
    if health is not None:
        print(f"[serve] health: {health.summary_line()}")


def _run_legacy(args, cfg, params, device) -> None:
    import time

    import jax
    import jax.numpy as jnp
    from repro.core import flops as F
    from repro.models import model as M

    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    enc = None
    if cfg.is_encoder_decoder:
        frames = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
        enc = M.encoder_forward(params, cfg, frames, {})

    from repro.serve.step import greedy_generate
    # warmup: same shapes, compile outside the timing window (the cached
    # jitted step makes the timed run reuse this compile)
    greedy_generate(params, cfg, prompt, max_new=2,
                    cache_len=args.prompt_len + args.max_new,
                    enc=enc).block_until_ready()

    from repro.obs import get_tracer
    t0 = time.time()
    with get_tracer().span("greedy_generate", "serve", batch=args.batch,
                           max_new=args.max_new):
        out = greedy_generate(params, cfg, prompt, max_new=args.max_new,
                              enc=enc)
        out.block_until_ready()
    wall = time.time() - t0
    n_new = args.batch * args.max_new
    dec_flops = sum(
        F.decode_flops(cfg, args.batch, args.prompt_len + i)
        for i in range(args.max_new))
    print(f"[serve] legacy dense: {n_new} tokens in {wall:.2f}s "
          f"({n_new/wall:.1f} tok/s); analytic decode "
          f"{dec_flops/1e9:.2f} GFLOP "
          f"({device.name} roofline: "
          f"{dec_flops/device.peak_flops*1e3:.3f} ms compute-bound)")
    print(f"[serve] sample: {list(map(int, out[0, -10:]))}")
    if args.metrics_out:
        from repro.obs import MetricsRegistry
        reg = MetricsRegistry()
        reg.counter("serve/tokens").inc(n_new)
        reg.histogram("serve/tokens_per_s", lo=1e-3, hi=1e6) \
            .observe(n_new / wall)
        reg.dump_jsonl(args.metrics_out,
                       meta={"arch": cfg.name, "path": "legacy"})


if __name__ == "__main__":
    main()
