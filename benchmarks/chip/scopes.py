"""The training step's device time split by the program's named scopes.

The program names its step's work with ``jax.named_scope``: ``embed``,
``attention`` (holding ``attention_core``), ``mlp``, ``lm_head`` and
``optimizer``.  XLA keeps each name as a component of the compiled
instruction's ``op_name`` metadata, bare or wrapped in ``jvp(...)`` and
``transpose(...)`` (``jit(train_step)/transpose(jvp())/while/body/
closed_call/mlp/dot_general``); a fusion carries the ``op_name`` of its
root.  The device trace names each op by its instruction
(``%fusion.463 = bf16[...] fusion(...)``), so the step's compiled HLO
text maps each instruction to its ``op_name``.  That text comes from
lowering the runner's step once more on the shapes of the window's
arguments and compiling it (a hit in the persistent compilation cache
that the run itself filled).

The rule: an op belongs to the innermost of :data:`SCOPES` that appears
as a component of its ``op_name``; a ``transpose(`` component marks it
as backward.  Time is self time (a loop's event less the ops nested in
it), summed over the whole ``jit_train_step`` modules that lie inside
the traced slice and divided by their number.  Scoped plus unscoped
time is the step's device time by construction.

A program without the scopes (no op under any of them) reads nothing,
and so does a map that does not cover the traced ops (under 99 % of the
step's device time found in it): a stale map would put time under the
wrong names.
"""

from __future__ import annotations

import re
import sys
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.chip import harness, tracing
from benchmarks.chip.metrics._attention import STEP

SCOPES = ("embed", "attention", "attention_core", "mlp", "lm_head",
          "optimizer")
MIN_MAPPED = 0.99     # least share of the step's device time in the map

_WRAP = re.compile(r"^(?:jvp|transpose)\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=\{?%?([\w.\-]+)")


def scope_of(op_name: str) -> Tuple[Optional[str], bool]:
    """(innermost scope or None, whether the op is backward)."""
    scope, backward = None, False
    for part in op_name.split("/"):
        if part.startswith("transpose("):
            backward = True
        while True:
            m = _WRAP.match(part)
            if not m:
                break
            part = m.group(1)
        if part in SCOPES:
            scope = part
    return scope, backward


def hlo_op_names(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` over a compiled module's HLO text,
    every instruction included.  One without an ``op_name`` (a fusion XLA
    made) takes its called computation's: that of the root, else the
    first found there; else it maps to ``""``."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    root: Dict[str, str] = {}
    first: Dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name, rest = m.groups()
        own.setdefault(name, "")
        op = _OP_NAME.search(rest)
        if op:
            own[name] = op.group(1)
            if comp is not None:
                first.setdefault(comp, op.group(1))
                if line.lstrip().startswith("ROOT"):
                    root[comp] = op.group(1)
        else:
            called = _CALLS.search(rest)
            if called:
                calls[name] = called.group(1)
    for name, comp in calls.items():
        own[name] = root.get(comp) or first.get(comp) or ""
    return own


def compiled_step_text(obs: Dict[str, Any]) -> str:
    """The optimized HLO text of the runner's training step, compiled for
    the first chip on the shapes of the window's arguments."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.chip import program
    from benchmarks.chip import weights as W
    from repro.data.pipeline import make_batch_fn
    from repro.optim import adamw
    from repro.train.trainer import TrainerConfig, make_jit_train_step
    conf, job = obs["config"], obs["traffic"]
    runner = harness.load_module(harness.HERE / "runners"
                                 / f"{job['runner']}.py")
    cfg = program.model_config(conf)
    oc = runner._opt_config(job["optimizer"])
    tc = TrainerConfig(batch=job["batch"], seq_len=job["seq_len"],
                       attn_impl=job["attn_impl"])
    dtype = jnp.dtype(conf["param_dtype"])
    params = jax.eval_shape(lambda k: program.to_program(
        conf, W.all_weights(conf, k, dtype), cfg), W.seed_key(0))
    opt = jax.eval_shape(lambda p: adamw.init_opt_state(p, oc), params)
    batch = next(make_batch_fn(cfg, job["batch"], job["seq_len"], 0))
    one = SingleDeviceSharding(jax.devices()[0])
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one,
                                       weak_type=getattr(x, "weak_type",
                                                         False)),
        (params, opt, batch))
    return make_jit_train_step(cfg, tc, oc).lower(*args).compile().as_text()


def split(trace: tracing.Trace, t0: float, t1: float,
          op_names: Dict[str, str]) -> Optional[Dict[str, Any]]:
    """Per-step device seconds by scope and direction over the whole
    steps inside [t0, t1] (ns), averaged over the chips."""
    per_chip: List[Dict[str, Any]] = []
    for chip, events in trace.devices.items():
        steps = [(a, a + d) for name, a, d in trace.modules.get(chip, [])
                 if STEP.search(name) and a >= t0 and a + d <= t1]
        if not steps:
            continue
        acc: Dict[str, float] = {"total": 0.0, "unscoped": 0.0,
                                 "unmapped": 0.0}
        for lo, hi in steps:
            for key, ns in tracing.self_times(
                    tracing.clip(events, lo, hi)).items():
                instr = key.split(" ")[0]
                acc["total"] += ns
                op = op_names.get(instr)
                if op is None:
                    acc["unmapped"] += ns
                scope, backward = scope_of(op or "")
                k = "unscoped" if scope is None else \
                    f"{scope}.{'bwd' if backward else 'fwd'}"
                acc[k] = acc.get(k, 0.0) + ns
        per_chip.append({k: v / len(steps) / 1e9 for k, v in acc.items()}
                        | {"steps": len(steps)})
    if not per_chip:
        return None
    keys = set().union(*per_chip)
    return {k: sum(c.get(k, 0.0) for c in per_chip) / len(per_chip)
            for k in keys}


def scope_s(s: Dict[str, Any], scope: str) -> float:
    """Forward plus backward seconds per step under ``scope``."""
    return s.get(f"{scope}.fwd", 0.0) + s.get(f"{scope}.bwd", 0.0)


def table(s: Dict[str, Any]) -> str:
    cells = [f"{sc} {1e3 * s.get(sc + '.fwd', 0.0):.3f}/"
             f"{1e3 * s.get(sc + '.bwd', 0.0):.3f}" for sc in SCOPES]
    scoped = sum(scope_s(s, sc) for sc in SCOPES)
    return (f"device ms per step by scope (forward/backward) over "
            f"{s['steps']:g} steps: {'; '.join(cells)}; unscoped "
            f"{1e3 * s['unscoped']:.3f}; scoped + unscoped "
            f"{1e3 * (scoped + s['unscoped']):.3f} of the step's "
            f"{1e3 * s['total']:.3f} (not in the map "
            f"{1e3 * s['unmapped']:.3f})")


def read(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The split of the traced slice's steps, computed once per run and
    kept in ``obs``; None where the program has no scopes or the map does
    not cover the trace."""
    if "scopes" in obs:
        return obs["scopes"]
    obs["scopes"] = None
    trace, summary = obs.get("trace"), obs.get("trace_summary")
    if trace is None or summary is None:
        return None
    t = time.time()
    try:
        text = compiled_step_text(obs)
    except Exception:             # a reader never ends the run
        print("[scopes] no op_name map:", file=sys.stderr, flush=True)
        traceback.print_exc()
        return None
    finally:
        obs["scope_map_span_s"] = (t, time.time())
    s = split(trace, summary["t0_ns"], summary["t1_ns"], hlo_op_names(text))
    if s is None:
        return None
    print(f"[scopes] op_name map built in "
          f"{obs['scope_map_span_s'][1] - t:.2f}s; {table(s)}", flush=True)
    if s["total"] <= 0 or s["unmapped"] > (1 - MIN_MAPPED) * s["total"] \
            or not any(scope_s(s, sc) > 0 for sc in SCOPES):
        return None
    obs["scopes"] = s
    return s
