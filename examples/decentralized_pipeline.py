"""End-to-end driver: DT-FM hybrid data+pipeline training on a simulated
edge mesh — the paper's §5 "distributed training methods for the edge",
executed for real with shard_map + ppermute.

    PYTHONPATH=src python examples/decentralized_pipeline.py \
        [--stages 4] [--data 2] [--steps 300] [--params-m 100]

Builds a (data x stage) mesh from CPU placeholder devices (each device =
one edge participant), splits an OPT-style decoder into pipeline stages,
and trains with GPipe microbatching.  Loss must decrease; the script also
prints the DT-FM analytic plan (step time, bubble, per-device energy) for
the same fleet so the executed schedule can be compared with the paper's
Table-2 model.

Default geometry is a ~14M-param model for a quick run; --params-m 100
trains a ~100M-param model for a few hundred steps (the deliverable's
end-to-end driver; allow ~30-60 min on CPU).
"""

import os

DATA = int(os.environ.get("EX_DATA", "2"))
STAGES = int(os.environ.get("EX_STAGES", "4"))
os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                           f"{DATA*STAGES} "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp   # noqa: E402
import numpy as np   # noqa: E402

from repro.configs.opt import opt_config            # noqa: E402
from repro.core.energy.devices import LAPTOP_M2PRO  # noqa: E402
from repro.core.planner import dtfm                 # noqa: E402
from repro.data.pipeline import make_batch_fn       # noqa: E402
from repro.distributed.pipeline import (            # noqa: E402
    make_pipeline_loss, pipeline_train_step, stack_for_stages,
    unstack_stages)
from repro.launch.mesh import make_edge_mesh       # noqa: E402
from repro.optim import adamw                       # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--params-m", type=int, default=14,
                    help="~model size in millions (14 quick | 100 full)")
    args = ap.parse_args()

    import dataclasses
    base = opt_config("opt-125m")
    if args.params_m >= 100:
        # ~100M params: the full OPT-125m geometry with a smaller vocab
        cfg = dataclasses.replace(base, name="opt-100m-pipe",
                                  vocab_size=8192)
    else:
        cfg = dataclasses.replace(base, name="opt-14m-pipe",
                                  num_layers=8, d_model=384, num_heads=8,
                                  num_kv_heads=8, head_dim=48, d_ff=1536,
                                  vocab_size=4096)
    print(f"model: {cfg.param_count()/1e6:.1f}M params, "
          f"{cfg.num_layers} layers")

    mesh = make_edge_mesh(STAGES, DATA)
    print(f"mesh: {DATA} data x {STAGES} stages "
          f"({DATA*STAGES} simulated edge devices)")

    opt_cfg = adamw.OptConfig(learning_rate=3e-4, warmup_steps=20,
                              decay_steps=args.steps)
    init_fn, step_fn = pipeline_train_step(
        cfg, mesh, opt_cfg, num_microbatches=args.microbatches)

    with jax.set_mesh(mesh):
        rest, staged, opt = init_fn(jax.random.PRNGKey(0))
        data = make_batch_fn(cfg, args.batch, args.seq, seed=0)
        losses = []
        t0 = time.time()
        for step in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(data).items()}
            rest, staged, opt, metrics = step_fn(rest, staged, opt, batch)
            losses.append(float(metrics["loss"]))
            if step % 20 == 0:
                print(f"step {step:4d}  loss {losses[-1]:.4f}")
        wall = time.time() - t0

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"\nloss {first:.3f} -> {last:.3f} "
          f"({args.steps} steps, {wall:.0f}s, "
          f"{args.steps/wall:.2f} steps/s)")
    assert last < first - 0.3, "pipeline training failed to learn"

    # analytic DT-FM plan for the equivalent edge fleet (paper Table 2 model)
    plan = dtfm.plan(cfg, [LAPTOP_M2PRO] * STAGES, batch=args.batch,
                     seq_len=args.seq, microbatches=args.microbatches,
                     data_parallel=DATA)
    print(f"\nDT-FM analytic plan ({STAGES} laptops x {DATA} pipelines):")
    print(f"  step time {plan.step_time_s:.2f}s  "
          f"bubble {plan.bubble_fraction:.2f}  "
          f"comm {plan.comm_s_per_step:.2f}s/step  "
          f"energy {plan.total_energy_wh_per_step*1000:.2f} mWh/step")

    # the same contract, heterogeneous: a smartphone joins, the placement
    # search hands it fewer layers, and the SAME executor runs that
    # non-uniform split (boundaries flow spec -> pipeline)
    from repro.core.energy.devices import SMARTPHONE_SD888   # noqa: E402
    from repro.core.placement import ordered_placement       # noqa: E402
    hetero = [LAPTOP_M2PRO] * (STAGES - 1) + [SMARTPHONE_SD888]
    spec = ordered_placement(cfg, hetero)
    print(f"\nheterogeneous placement (1 phone joins):\n{spec.describe()}")
    if len(spec.boundaries) - 1 == STAGES:
        loss_fn = make_pipeline_loss(cfg, mesh,
                                     num_microbatches=args.microbatches,
                                     boundaries=spec)
        from repro.models import params as PM                    # noqa: E402
        p = PM.init_params(cfg, jax.random.PRNGKey(1))
        st = stack_for_stages(cfg, p, spec)
        with jax.set_mesh(mesh):
            b = {k: jnp.asarray(v) for k, v in next(data).items()}
            nl = jax.jit(loss_fn)(p, st, b)
        print(f"  non-uniform split {spec.layer_counts} executes: "
              f"loss {float(nl):.4f}")


if __name__ == "__main__":
    main()
