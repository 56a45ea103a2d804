"""Chip smoke test: the train and serve paths, once, on a TPU.

    python chip_smoke.py             # one chip: train, local SGD, serve
    python chip_smoke.py --chips 4   # four chips: the GPipe pipeline only

One process holds the chip and starts no other that touches JAX.  Every
phase goes through the library's own entry points with random weights
made from a seed, and checks what comes out; a failed check exits
non-zero.  There is no CPU fallback: without a TPU the script exits
non-zero before any phase runs.  The last line of stdout is the JSON
verdict ``{"ok": true, "device": {...}}``; everything else comes before
it.

Phases (one chip):

* train — opt-125m at its published widths (12 x 768, 12 heads, d_ff
  3072, vocab 50272), ``attn_impl="pallas"`` (FA-2 forward and backward
  compiled), batch 8 x 1024, donation on.  The losses must be finite and
  fall, the first-step loss must match the XLA ``chunked`` attention on
  the same batch, and the compiled step must hold a ``tpu_custom_call``.
* local SGD — the same model, 2 replicas x K=2 x 2 rounds at batch
  4 x 1024 with the int8-compressed outer sync (the quant8 kernel,
  compiled).
* serve — qwen2-7b widths (d 3584, 28/4 heads of 128, d_ff 18944, vocab
  152064), depth cut to 12 of 28 layers to fit one 16 GB chip next to a
  ~2 GB bf16 KV pool.  First-step logits of the Pallas flash-decode path
  must match the XLA gather path; then ``ServeEngine`` (4 slots) serves
  8 requests (prompts 64-512 tokens, half sharing a prefix, 32 new
  tokens each) once per path.

Four chips: opt-125m on a (1, 4) data x stage mesh, the uniform 3/3/3/3
split and the non-uniform split ``ordered_placement`` gives three
laptops and a smartphone; pipelined loss and gradients must match the
unpipelined model in the same process, and every parameter must live
on the mesh.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# first-step loss, pallas vs chunked attention, on a ~10.8-nat loss: both
# attend in fp32 from bf16 inputs and differ only in summation order and
# where bf16 rounds the attention output (2^-8 relative), averaged over
# 8k tokens
TRAIN_LOSS_TOL = 1e-2
# first-step logits, pallas flash-decode vs XLA gather, max |diff| over
# max |logit|: both attend in fp32 over the same bf16 pages; the bf16
# residual stream (2^-8 relative per rounding) carries any flipped
# rounding through 12 layers
SERVE_LOGIT_RTOL = 2e-2
# pipeline vs unpipelined, fp32 under "highest" matmul precision: only
# the summation order differs (microbatched, staged)
PIPE_LOSS_RTOL = 1e-5
PIPE_GRAD_RTOL = 1e-3

SERVE_LAYERS = 12          # of qwen2-7b's 28
SERVE_BLOCKS = 5120        # x 16 tokens x 12 layers x 32 KiB = 1.9 GiB


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"[smoke] FAIL: {what}")


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def finite(xs) -> bool:
    import numpy as np
    return bool(np.all(np.isfinite(np.asarray(xs, np.float64))))


# --------------------------------------------------------------------- train

def train_phase(cfg, *, batch: int, seq: int, steps: int) -> None:
    import jax
    from repro.data.pipeline import make_batch_fn
    from repro.models import model as M
    from repro.models import params as PM
    from repro.optim import adamw
    from repro.train.trainer import TrainerConfig, make_jit_train_step, train

    tc = TrainerConfig(steps=steps, batch=batch, seq_len=seq, log_every=0,
                       attn_impl="pallas", donate=True, seed=0)
    opt_cfg = adamw.OptConfig(learning_rate=1e-3, warmup_steps=2,
                              decay_steps=steps)
    t0 = time.time()
    res = train(cfg, tc, opt_cfg)
    losses = res.losses
    log(f"train: {steps} steps, {time.time() - t0:.1f}s with eager param "
        f"init (first step incl. compile {res.compile_time_s:.1f}s); losses "
        + " ".join(f"{x:.4f}" for x in losses))
    check(len(losses) == steps and finite(losses), "train losses finite")
    check(losses[-1] < losses[0], "train loss falls")

    # the trainer's first loss is the forward loss of the seed's params on
    # the seed's first batch: recompute it with the XLA chunked attention
    params = PM.init_params(cfg, jax.random.PRNGKey(tc.seed))
    first = next(make_batch_fn(cfg, batch, seq, tc.seed))
    ref = float(jax.jit(lambda p, b: M.forward_train(
        p, cfg, b, attn_impl="chunked")[0])(params, first))
    gap = abs(losses[0] - ref)
    log(f"train: first-step loss pallas {losses[0]:.6f} chunked {ref:.6f} "
        f"|gap| {gap:.2e} (tol {TRAIN_LOSS_TOL:g})")
    check(gap <= TRAIN_LOSS_TOL, "first-step loss, pallas vs chunked")

    opt_state = adamw.init_opt_state(params, opt_cfg)
    compiled = make_jit_train_step(cfg, tc, opt_cfg).lower(
        params, opt_state, first).compile()
    kernel = has_kernel(compiled)
    log(f"train: compiled step holds tpu_custom_call: {kernel}")
    check(kernel, "train step runs the Pallas kernels compiled")


def local_sgd_phase(cfg, *, batch: int, seq: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.optim import adamw
    from repro.optim.compress import CompressConfig, compress_grads
    from repro.train.local_sgd import LocalSGDConfig, train_local_sgd
    from repro.train.trainer import TrainerConfig

    K, rounds = 2, 2
    comp = CompressConfig(method="int8")
    tc = TrainerConfig(steps=K * rounds, batch=batch, seq_len=seq,
                       log_every=0, attn_impl="pallas", seed=1)
    t0 = time.time()
    # 3e-4: at the trainer's 1e-3 the first steps overshoot (the loss
    # climbs at step 3 before it falls), and 4 inner steps end mid-climb
    res = train_local_sgd(
        cfg, tc, LocalSGDConfig(replicas=2, inner_steps=K, compress=comp),
        adamw.OptConfig(learning_rate=3e-4, warmup_steps=1,
                        decay_steps=K * rounds))
    log(f"local_sgd: {res.rounds} rounds x 2 replicas x K={K} in "
        f"{time.time() - t0:.1f}s; first inner step {res.losses[0]:.4f}, "
        "round losses " + " ".join(f"{x:.4f}" for x in res.round_losses)
        + f"; {res.sync_wire_bytes_per_round} int8 wire bytes/replica")
    check(res.rounds == rounds and finite(res.round_losses)
          and finite(res.losses), "local SGD losses finite")
    check(res.round_losses[-1] < res.losses[0],
          "local SGD: last round's fleet loss below the first step's")
    # the outer sync's compressor, as it runs there
    leaf = jax.ShapeDtypeStruct((cfg.d_model, cfg.d_ff), jnp.float32)
    compiled = jax.jit(lambda g: compress_grads({"w": g}, None, comp)[0]
                       ).lower(leaf).compile()
    kernel = has_kernel(compiled)
    log(f"local_sgd: int8 outer-sync compressor holds tpu_custom_call: "
        f"{kernel}")
    check(kernel, "quant8 runs compiled in the outer sync")


# --------------------------------------------------------------------- serve

def make_requests(vocab: int, lengths, *, shared_prefix: int, max_new: int,
                  seed: int):
    """Prompts of the given lengths; the even-numbered ones start with one
    shared system prompt of ``shared_prefix`` tokens."""
    import numpy as np
    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, shared_prefix).tolist()
    reqs = []
    for i, n in enumerate(lengths):
        if i % 2 == 0:
            prompt = system + rng.integers(0, vocab, n - shared_prefix).tolist()
        else:
            prompt = rng.integers(0, vocab, n).tolist()
        reqs.append(Request(uid=f"r{i}", prompt=prompt, max_new=max_new))
    return reqs


def first_step_logits(params, cfg, impl: str, reqs, *, num_blocks: int,
                      block_size: int, chunk: int):
    """Logits of one chunked-prefill step (each request's first ``chunk``
    tokens) and of the decode step after it, through the paged model step
    the engine jits, plus the compiled chunk step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M

    n = len(reqs)
    nb = -(-(chunk + 1) // block_size)
    bt = jnp.asarray(1 + np.arange(n * nb, dtype=np.int32).reshape(n, nb))
    cache = M.init_paged_cache(cfg, num_blocks, block_size, jnp.bfloat16)
    toks = jnp.asarray([r.prompt[:chunk] for r in reqs], jnp.int32)
    nfeed = jnp.full((n,), chunk, jnp.int32)
    chunk_fn = jax.jit(lambda p, c, t, nf, b, s: M.decode_step_paged(
        p, cfg, c, t, b, s, num_feed=nf, attn_impl=impl))
    step_fn = jax.jit(lambda p, c, t, b, s: M.decode_step_paged(
        p, cfg, c, t, b, s, attn_impl=impl))
    sl0 = jnp.zeros((n,), jnp.int32)
    compiled = chunk_fn.lower(params, cache, toks, nfeed, bt, sl0).compile()
    lg_chunk, cache = chunk_fn(params, cache, toks, nfeed, bt, sl0)
    nxt = jnp.asarray([[r.prompt[chunk]] for r in reqs], jnp.int32)
    lg_dec, _ = step_fn(params, cache, nxt, bt, sl0 + chunk)
    return (np.asarray(lg_chunk, np.float32), np.asarray(lg_dec, np.float32),
            compiled)


def serve_phase(cfg, *, num_blocks: int, lengths, shared_prefix: int,
                max_new: int, max_slots: int, prefill_chunk: int = 8
                ) -> None:
    """``max_slots`` below the request count: later requests are admitted
    after earlier ones have published their prompt blocks, which is when
    the shared prefix can be served from the prefix cache."""
    import gc

    import jax
    import numpy as np
    from repro.models import params as PM
    from repro.serve.engine import EngineConfig, ServeEngine

    params = PM.init_params(cfg, jax.random.PRNGKey(0))
    reqs = make_requests(cfg.vocab_size, lengths,
                         shared_prefix=shared_prefix, max_new=max_new,
                         seed=0)
    block_size = 16
    lg = {}
    for impl in ("pallas", "gather"):
        lg[impl] = first_step_logits(params, cfg, impl, reqs,
                                     num_blocks=num_blocks,
                                     block_size=block_size,
                                     chunk=prefill_chunk)
    kernel = has_kernel(lg["pallas"][2])
    log(f"serve: compiled pallas step holds tpu_custom_call: {kernel}")
    check(kernel, "serve step runs flash-decode compiled")
    for i, name in enumerate(("prefill chunk", "decode")):
        a, b = lg["pallas"][i], lg["gather"][i]
        check(finite(a) and finite(b), f"serve {name} logits finite")
        rel = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        agree = float(np.mean(a.argmax(-1) == b.argmax(-1)))
        log(f"serve: first {name} step logits pallas vs gather: "
            f"max|diff|/max|logit| {rel:.2e} (tol {SERVE_LOGIT_RTOL:g}), "
            f"argmax agreement {agree:.3f}")
        check(rel <= SERVE_LOGIT_RTOL, f"serve {name} logits, pallas vs "
              "gather")
    del lg

    max_blocks = -(-(max(lengths) + max_new) // block_size)
    tokens = {}
    for impl in ("pallas", "gather"):
        eng = ServeEngine(params, cfg, EngineConfig(
            max_slots=max_slots, block_size=block_size,
            num_blocks=num_blocks, max_blocks_per_seq=max_blocks,
            attn_impl=impl, prefill_chunk=prefill_chunk))
        t0 = time.time()
        done = eng.run(list(reqs))
        st = eng.stats()
        log(f"serve[{impl}]: {len(done)} requests, "
            f"{int(st['tokens_generated'])} tokens in {int(st['steps'])} "
            f"steps, {time.time() - t0:.1f}s incl. compile; prefix hit "
            f"rate {st['prefix_hit_rate']:.3f}; peak KV "
            f"{st['peak_cache_bytes'] / 2**20:.1f} MiB of "
            f"{st['pool_bytes'] / 2**30:.2f} GiB pool; failed "
            f"{int(st['requests_failed'])}")
        check(len(done) == len(reqs) and st["requests_failed"] == 0,
              f"serve[{impl}]: every request completes")
        check(all(len(c.tokens) == max_new for c in done.values()),
              f"serve[{impl}]: every request gets {max_new} tokens")
        check(st["tokens_generated"] == len(reqs) * max_new,
              f"serve[{impl}]: token count")
        check(st["prefix_hit_rate"] > 0, f"serve[{impl}]: shared prefix "
              "served from the prefix cache")
        check(0 < st["peak_cache_bytes"] <= st["pool_bytes"],
              f"serve[{impl}]: KV use within the pool")
        tokens[impl] = {u: c.tokens for u, c in done.items()}
        del eng, done
        gc.collect()
    same = np.mean([a == b for u in tokens["pallas"]
                    for a, b in zip(tokens["pallas"][u], tokens["gather"][u])])
    log(f"serve: greedy tokens identical across paths: {same:.3f} "
        "(informational: bf16 near-ties may flip)")


# ------------------------------------------------------------------ pipeline

def pipeline_phase(cfg, *, batch: int, seq: int, microbatches: int,
                   stages: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.energy.devices import LAPTOP_M2PRO, SMARTPHONE_SD888
    from repro.core.placement import ordered_placement
    from repro.data.pipeline import make_batch_fn
    from repro.distributed.pipeline import (make_pipeline_loss,
                                            pipeline_train_step,
                                            resolve_boundaries,
                                            stack_for_stages, unstack_stages)
    from repro.launch.mesh import make_edge_mesh
    from repro.models import model as M
    from repro.models import params as PM
    from repro.optim import adamw

    mesh = make_edge_mesh(stages)
    # laptops and one smartphone: the phone's stage gets fewer layers
    spec = ordered_placement(cfg, [LAPTOP_M2PRO] * (stages - 1)
                             + [SMARTPHONE_SD888])
    splits = {"uniform": stages, "ordered_placement": spec.boundaries}
    check(len(set(spec.layer_counts)) > 1 and len(spec.layer_counts) == stages,
          f"ordered_placement gives a non-uniform {stages}-stage split")
    params = PM.init_params(cfg, jax.random.PRNGKey(0))
    batch_np = next(make_batch_fn(cfg, batch, seq, seed=0))
    with jax.default_matmul_precision("highest"):
        ref, ref_g = jax.jit(jax.value_and_grad(
            lambda p: M.forward_train(p, cfg, batch_np)[0]))(params)
        ref = float(ref)
        ref_dec = ref_g["decoder"]["g0"]
        ref_rest = {k: v for k, v in ref_g.items() if k != "decoder"}
        rest = jax.device_put({k: v for k, v in params.items()
                               if k != "decoder"}, NamedSharding(mesh, P()))
        with jax.set_mesh(mesh):
            for name, bounds in splits.items():
                loss_fn = make_pipeline_loss(cfg, mesh,
                                             num_microbatches=microbatches,
                                             boundaries=bounds)
                staged = jax.device_put(
                    stack_for_stages(cfg, params, bounds),
                    NamedSharding(mesh, P("stage")))
                pipe, (g_rest, g_staged) = jax.jit(jax.value_and_grad(
                    lambda r, s: loss_fn(r, s, batch_np), argnums=(0, 1)))(
                        rest, staged)
                g_dec = unstack_stages(cfg, g_staged, bounds)
                loss_rel = abs(float(pipe) - ref) / abs(ref)

                def rel(a, b):
                    num = sum(float(np.sum((np.asarray(x, np.float64)
                                            - np.asarray(y, np.float64)) ** 2))
                              for x, y in zip(jax.tree.leaves(a),
                                              jax.tree.leaves(b)))
                    den = sum(float(np.sum(np.asarray(y, np.float64) ** 2))
                              for y in jax.tree.leaves(b))
                    return (num / den) ** 0.5
                g_rel = max(rel(g_dec, ref_dec), rel(g_rest, ref_rest))
                log(f"pipeline[{name}] boundaries "
                    f"{resolve_boundaries(cfg, bounds)}: loss {float(pipe):.6f} vs {ref:.6f} (rel "
                    f"{loss_rel:.2e}, tol {PIPE_LOSS_RTOL:g}); grad rel "
                    f"L2 {g_rel:.2e} (tol {PIPE_GRAD_RTOL:g})")
                check(loss_rel <= PIPE_LOSS_RTOL, f"pipeline[{name}] loss")
                check(g_rel <= PIPE_GRAD_RTOL, f"pipeline[{name}] grads")

            # the training entry point: every parameter and optimizer leaf
            # lives on all of the mesh's devices, and a step runs
            init_fn, step_fn = pipeline_train_step(
                cfg, mesh, adamw.OptConfig(warmup_steps=1, decay_steps=2),
                num_microbatches=microbatches, boundaries=spec)
            state = init_fn(jax.random.PRNGKey(0))
            n_dev = len(mesh.devices.flat)
            on_mesh = all(len(x.sharding.device_set) == n_dev
                          for x in jax.tree.leaves(state))
            log(f"pipeline: every param/opt leaf on all {n_dev} devices: "
                f"{on_mesh}")
            check(on_mesh, "pipeline state placed on the whole mesh")
            *_, mets = step_fn(*state, batch_np)
            step_loss = float(mets["loss"])
            log(f"pipeline: pipeline_train_step loss {step_loss:.4f}")
            check(finite([step_loss]), "pipeline train step loss finite")


# ---------------------------------------------------------------------- main

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the GPipe pipeline on a (1, 4) mesh")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    d0 = devs[0]
    log(f"device: platform {d0.platform}, kind {d0.device_kind}, "
        f"count {len(devs)}")
    check(d0.platform == "tpu", "no TPU found (this script has no CPU "
          "fallback)")
    check(len(devs) >= args.chips, f"--chips {args.chips} needs "
          f"{args.chips} devices, found {len(devs)}")

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")

    t0 = time.time()
    opt = get_config("opt-125m")
    if args.chips == 4:
        f32 = dataclasses.replace(opt, param_dtype="float32",
                                  compute_dtype="float32")
        log(f"pipeline: {opt.name} {opt.num_layers}x{opt.d_model}, "
            f"vocab {opt.vocab_size}, fp32 under 'highest' matmul precision")
        pipeline_phase(f32, batch=8, seq=1024, microbatches=4, stages=4)
    else:
        log(f"train: {opt.name} {opt.num_layers}x{opt.d_model}, "
            f"{opt.num_heads} heads, d_ff {opt.d_ff}, vocab "
            f"{opt.vocab_size} ({opt.param_count() / 1e6:.1f}M params)")
        train_phase(opt, batch=8, seq=1024, steps=8)
        # batch 4: two replicas' params, moments, error feedback and the
        # outer state sit beside the step's ~10 GiB of batch-8 temporaries
        local_sgd_phase(opt, batch=4, seq=1024)
        qwen = get_config("qwen2-7b")
        cut = dataclasses.replace(qwen, num_layers=SERVE_LAYERS)
        log(f"serve: {qwen.name} d {qwen.d_model}, {qwen.num_heads}/"
            f"{qwen.num_kv_heads} heads of {qwen.head_dim}, d_ff "
            f"{qwen.d_ff}, vocab {qwen.vocab_size}; depth cut "
            f"{qwen.num_layers} -> {SERVE_LAYERS} layers "
            f"({cut.param_count() / 1e9:.2f}B params), KV pool "
            f"{SERVE_BLOCKS} blocks x 16 tokens")
        serve_phase(cut, num_blocks=SERVE_BLOCKS,
                    lengths=(64, 96, 128, 192, 256, 320, 384, 512),
                    shared_prefix=48, max_new=32, max_slots=4)
    log(f"all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
