"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

MUST set the placeholder-device flag before any other import (jax locks the
device count on first backend init).
"""

import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse       # noqa: E402
import json           # noqa: E402
import re             # noqa: E402
import time           # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.registry import (INPUT_SHAPES, get_config, input_shape,  # noqa: E402
                                    list_archs, shape_applicable)
from repro.launch.hlo_analysis import collective_totals, compute_totals  # noqa: E402
from repro.distributed import sharding as SH  # noqa: E402
from repro.launch import specs as SP  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import params as PM  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.serve.step import make_serve_step  # noqa: E402
from repro.train.step import make_train_step  # noqa: E402

RESULT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(.+?)\s+(all-reduce-start|all-gather-start|all-reduce|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def collective_bytes(hlo: str) -> Dict[str, Any]:
    """Sum result bytes of every collective op in (per-device) HLO text."""
    per_op: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    for line in hlo.splitlines():
        mm = _COLL_RE.search(line)
        if not mm:
            continue
        shapes_str, op = mm.groups()
        op = op.replace("-start", "")
        total = 0
        for dt, dims in _SHAPE_RE.findall(shapes_str):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for x in dims.split(","):
                if x:
                    n *= int(x)
            total += n * _DTYPE_BYTES[dt]
        per_op[op] = per_op.get(op, 0) + total
        counts[op] = counts.get(op, 0) + 1
    return {"bytes_by_op": per_op, "counts": counts,
            "total_bytes": sum(per_op.values())}


def _opt_shardings(p_shard, mesh):
    return {"mu": p_shard, "nu": p_shard,
            "step": NamedSharding(mesh, P())}


def lower_case(arch: str, shape_name: str, *, multi_pod: bool = False,
               remat: str = "full", moment_dtype: str = "float32",
               rules_name: str = "default", microbatches: int = 8,
               donate: bool = True, moe_layout: str = "") -> Dict[str, Any]:
    import dataclasses
    cfg = get_config(arch)
    if moe_layout and cfg.moe.enabled:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, layout=moe_layout))
    shape = input_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = {"default": SH.DEFAULT_RULES, "tp_only": SH.TP_ONLY_RULES}[rules_name]

    p_abs = PM.abstract_params(cfg)
    p_shard = SH.param_shardings(cfg, mesh, rules)

    with jax.set_mesh(mesh):
        return _lower_compile_record(cfg, shape, mesh, rules, arch,
                                     shape_name, multi_pod, remat,
                                     moment_dtype, rules_name, donate,
                                     p_abs, p_shard, microbatches)


def _lower_compile_record(cfg, shape, mesh, rules, arch, shape_name,
                          multi_pod, remat, moment_dtype, rules_name,
                          donate, p_abs, p_shard, microbatches):
    t0 = time.time()
    if shape.kind in ("train",):
        opt_cfg = adamw.OptConfig(moment_dtype=moment_dtype)
        opt_abs = jax.eval_shape(lambda p: adamw.init_opt_state(p, opt_cfg),
                                 p_abs)
        opt_shard = _opt_shardings(p_shard, mesh)
        batch = SP.input_specs(cfg, shape)
        b_shard = SH.batch_shardings(mesh, batch)
        step = make_train_step(cfg, opt_cfg, remat=remat,
                               microbatches=microbatches)
        jitted = jax.jit(step,
                         in_shardings=(p_shard, opt_shard, b_shard),
                         out_shardings=(p_shard, opt_shard, None),
                         donate_argnums=(0, 1) if donate else ())
        lowered = jitted.lower(p_abs, opt_abs, batch)
    elif shape.kind == "prefill":
        batch = SP.input_specs(cfg, shape)
        batch.pop("labels", None)
        b_shard = SH.batch_shardings(mesh, batch)
        # chunked (flash) attention for prefill: the naive path materializes
        # the full S x S score tensor — 120 TiB/dev of exp/div/add at 32k
        # (EXPERIMENTS.md §Perf A2)
        fn = lambda p, b: M.forward_logits(p, cfg, b,   # noqa: E731
                                           attn_impl="chunked")
        jitted = jax.jit(fn, in_shardings=(p_shard, b_shard))
        lowered = jitted.lower(p_abs, batch)
    else:  # decode
        T = SP.cache_len(cfg, shape)
        cache_abs = M.abstract_cache(cfg, shape.global_batch, T)
        c_shard = SH.cache_shardings(cfg, mesh, cache_abs, shape.global_batch)
        dspec = SP.decode_specs(cfg, shape)
        serve = make_serve_step(cfg)
        b = shape.global_batch
        tok_shard = NamedSharding(mesh, SH.batch_spec(mesh, b))
        # enc-dec: cross-KV lives in the (pre-warmed) cache, so serve_step
        # never touches the raw encoder output (§Perf beyond-paper #6)
        args = [p_abs, cache_abs, dspec["tokens"], dspec["index"]]
        in_sh = [p_shard, c_shard,
                 NamedSharding(mesh, P(*SH.batch_spec(mesh, b), None)),
                 NamedSharding(mesh, P())]
        fn = serve
        jitted = jax.jit(fn, in_shardings=tuple(in_sh),
                         out_shardings=(None, c_shard),
                         donate_argnums=(1,) if donate else ())
        lowered = jitted.lower(*args)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_totals(hlo)          # trip-count-aware (hlo_analysis)
    ct = compute_totals(hlo)               # trip-count-aware flops/bytes

    n_chips = mesh.devices.size
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(n_chips),
        "kind": shape.kind,
        "remat": remat, "moment_dtype": moment_dtype, "rules": rules_name,
        "microbatches": microbatches if shape.kind == "train" else None,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
        # trip-count-aware walk of the per-device HLO (hlo_analysis):
        # cost_analysis() counts while bodies once, these do not
        "hlo_flops_per_device": ct["flops"],
        "hlo_bytes_per_device": ct["bytes_accessed"],
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "collectives": coll,
        "params_total": PM.count_params(cfg),
        "params_active": PM.count_params(cfg, active_only=True),
    }
    return rec


def run_and_save(arch: str, shape_name: str, tag: str = "", **kw
                 ) -> Dict[str, Any]:
    rec = lower_case(arch, shape_name, **kw)
    out_dir = RESULT_DIR if not tag else RESULT_DIR.parent / "perf"
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "multipod" if kw.get("multi_pod") else "pod"
    extra = f"_{tag}" if tag else ""
    if not tag and (kw.get("remat", "full") != "full"
                    or kw.get("moment_dtype", "float32") != "float32"
                    or kw.get("rules_name", "default") != "default"):
        extra = f"_{kw.get('remat','full')}_{kw.get('moment_dtype','float32')}" \
                f"_{kw.get('rules_name','default')}"
    rec["tag"] = tag
    out = out_dir / f"{arch}_{shape_name}_{suffix}{extra}.json"
    out.write_text(json.dumps(rec, indent=1))
    print(f"[dryrun] {arch} x {shape_name} ({rec['mesh']}): "
          f"compile {rec['compile_s']}s, "
          f"flops/dev {rec['hlo_flops_per_device']:.3e}, "
          f"mem temp {rec['memory']['temp_bytes']/2**30:.2f} GiB, "
          f"coll {rec['collectives']['total_bytes']/2**30:.3f} GiB -> {out.name}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch x shape)")
    ap.add_argument("--skip-existing", action="store_true",
                    help="with --all: skip combos whose record already exists")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--moment-dtype", default="float32")
    ap.add_argument("--rules", default="default")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--tag", default="",
                    help="perf-iteration tag: save under experiments/perf/")
    ap.add_argument("--moe-layout", default="",
                    choices=["", "auto", "ep_full", "unconstrained"])
    args = ap.parse_args()

    kw = dict(multi_pod=args.multi_pod, remat=args.remat,
              moment_dtype=args.moment_dtype, rules_name=args.rules,
              microbatches=args.microbatches, tag=args.tag,
              moe_layout=args.moe_layout)
    if args.all:
        for arch in list_archs(assigned_only=True):
            for shape_name in INPUT_SHAPES:
                if not shape_applicable(arch, shape_name):
                    print(f"[dryrun] SKIP {arch} x {shape_name} "
                          f"(sub-quadratic attention required; see DESIGN.md)")
                    continue
                if args.skip_existing:
                    suffix = "multipod" if args.multi_pod else "pod"
                    if (RESULT_DIR / f"{arch}_{shape_name}_{suffix}.json").exists():
                        continue
                run_and_save(arch, shape_name, **kw)
    else:
        assert args.arch and args.shape
        if not shape_applicable(args.arch, args.shape):
            print(f"[dryrun] SKIP {args.arch} x {args.shape} (see DESIGN.md)")
            return
        run_and_save(args.arch, args.shape, **kw)


if __name__ == "__main__":
    main()
