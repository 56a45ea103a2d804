"""Attribute collective bytes to source ops (trip-count-aware).

    PYTHONPATH=src python -m repro.launch.coll_attr --arch X --shape Y [...]

Buckets every collective's result bytes by the jax op_name metadata on its
HLO line — the §Perf microscope for "which op is moving these bytes".
"""

import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import re                # noqa: E402
from collections import defaultdict  # noqa: E402

from repro.launch import hlo_analysis as HA  # noqa: E402

_NAME_RE = re.compile(r'op_name="([^"]*)"')


def attribute(hlo: str, top: int = 25):
    comps, entry = HA.parse_computations(hlo)
    buckets = defaultdict(float)
    ops = defaultdict(float)

    coll_re = re.compile(
        r"^(?:ROOT\s+)?%[\w\.\-]+\s*=\s*(\S.*?)\s+"
        r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(")

    def walk(name, mult, stack=()):
        c = comps.get(name)
        if c is None or name in stack:
            return
        for ln in c.lines:
            m = coll_re.match(ln)
            if not m:
                continue
            b = HA._shape_bytes(m.group(1)) * mult
            tag = _NAME_RE.search(ln)
            tag = tag.group(1) if tag else "(untagged)"
            # strip trailing op ids, keep the semantic path
            tag = re.sub(r"\[[^\]]*\]", "", tag)
            buckets[f"{m.group(2)} :: {tag[:110]}"] += b
            ops[m.group(2)] += b
        for cond, body in c.whiles:
            trips = c.trip_hint.get(body) or HA._trip_count(comps.get(cond))
            walk(body, mult * trips, stack + (name,))
        for cal in c.plain_calls:
            walk(cal, mult, stack + (name,))

    walk(entry, 1.0)
    print("== by op ==")
    for k, v in sorted(ops.items(), key=lambda kv: -kv[1]):
        print(f"  {v/2**40:8.2f} TiB  {k}")
    print("== top sources ==")
    for k, v in sorted(buckets.items(), key=lambda kv: -kv[1])[:top]:
        print(f"  {v/2**40:8.2f} TiB  {k}")


def main():
    from repro.launch import dryrun as DR
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--moment-dtype", default="float32")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    # reuse dryrun's lowering, but grab the HLO text
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.registry import get_config, input_shape
    from repro.launch.mesh import make_production_mesh
    from repro.distributed import sharding as SH
    from repro.launch import specs as SP
    from repro.models import params as PM, model as M
    from repro.optim import adamw
    from repro.train.step import make_train_step
    from repro.serve.step import make_serve_step

    cfg = get_config(args.arch)
    shape = input_shape(args.shape)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    p_abs = PM.abstract_params(cfg)
    p_shard = SH.param_shardings(cfg, mesh, SH.DEFAULT_RULES)
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = adamw.OptConfig(moment_dtype=args.moment_dtype)
            opt_abs = jax.eval_shape(
                lambda p: adamw.init_opt_state(p, opt_cfg), p_abs)
            opt_shard = {"mu": p_shard, "nu": p_shard,
                         "step": NamedSharding(mesh, P())}
            batch = SP.input_specs(cfg, shape)
            b_shard = SH.batch_shardings(mesh, batch)
            step = make_train_step(cfg, opt_cfg, remat=args.remat,
                                   microbatches=args.microbatches)
            hlo = jax.jit(step, in_shardings=(p_shard, opt_shard, b_shard),
                          out_shardings=(p_shard, opt_shard, None),
                          donate_argnums=(0, 1)).lower(
                p_abs, opt_abs, batch).compile().as_text()
        elif shape.kind == "prefill":
            batch = SP.input_specs(cfg, shape)
            batch.pop("labels", None)
            b_shard = SH.batch_shardings(mesh, batch)
            fn = lambda p, b: M.forward_logits(p, cfg, b)  # noqa: E731
            hlo = jax.jit(fn, in_shardings=(p_shard, b_shard)).lower(
                p_abs, batch).compile().as_text()
        else:
            raise SystemExit("decode attribution not wired; use train/prefill")
    attribute(hlo, args.top)


if __name__ == "__main__":
    main()
