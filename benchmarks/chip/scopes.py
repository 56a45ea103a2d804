"""A step's device time split by the program's named scopes.

The program names its step's work with ``jax.named_scope``; the cell's
family lists the names (``SCOPES``; the decoder's are ``embed``,
``attention`` holding ``attention_core``, ``mlp``, ``lm_head`` and
``optimizer``).  XLA keeps each name as a component of the compiled
instruction's ``op_name`` metadata, bare or wrapped in ``jvp(...)`` and
``transpose(...)`` (``jit(train_step)/transpose(jvp())/while/body/
closed_call/mlp/dot_general``); a fusion carries the ``op_name`` of its
root.  The device trace names each op by its instruction
(``%fusion.463 = bf16[...] fusion(...)``), so the step's compiled HLO
text maps each instruction to its ``op_name``.  The runner hands that
text over in ``obs``: ``step_hlo``, a call that compiles the step it
timed on the shapes of the window's arguments (once per checkout, then
from the persistent compilation cache), made only when a reader asks,
after the traced slice; and ``step_module``, the name the step's modules
carry in the trace.

The rule: an op belongs to the innermost of the family's scopes that
appears as a component of its ``op_name``; a ``transpose(`` component
marks it as backward.  Time is self time (a loop's event less the ops
nested in it), summed over the whole step modules that lie inside the
traced slice and divided by their number.  Scoped plus unscoped time is
the step's device time by construction.

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
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import tracing

MIN_MAPPED = 0.99     # least share of the step's device time in the map

_WRAP = re.compile(r"^(?:jvp|transpose)\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=\{?%?([\w.\-]+)")


def backward(op_name: str) -> bool:
    """Whether the op is backward work: a ``transpose(`` component."""
    return any(part.startswith("transpose(") for part in op_name.split("/"))


def scope_of(op_name: str,
             scopes: Sequence[str]) -> Tuple[Optional[str], bool]:
    """(innermost of ``scopes`` or None, whether the op is backward)."""
    scope = None
    for part in op_name.split("/"):
        while True:
            m = _WRAP.match(part)
            if not m:
                break
            part = m.group(1)
        if part in scopes:
            scope = part
    return scope, backward(op_name)


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


def op_names(obs: Dict[str, Any]) -> Optional[Dict[str, str]]:
    """Instruction name -> ``op_name`` of the step the runner timed, from
    the HLO text its ``obs["step_hlo"]`` gives; built once per run and
    kept in ``obs`` (the compile's wall span too, ``scope_map_span_s``).
    None where a run traced nothing or the text could not be had."""
    if "op_names" in obs:
        return obs["op_names"]
    obs["op_names"] = None
    make = obs.get("step_hlo")
    if obs.get("trace") is None or make is None:
        return None
    t = time.time()
    try:
        text = make()
    except Exception:             # a reader never ends the run
        print("[scopes] no op_name map:", file=sys.stderr, flush=True)
        traceback.print_exc()
        return None
    finally:
        obs["scope_map_span_s"] = (t, time.time())
    obs["op_names"] = hlo_op_names(text)
    return obs["op_names"]


def split(trace: tracing.Trace, t0: float, t1: float,
          op_names: Dict[str, str], step_module: str,
          scopes: Sequence[str]) -> Optional[Dict[str, Any]]:
    """Per-step device seconds by scope and direction over the whole
    ``step_module`` modules inside [t0, t1] (ns), averaged over the
    chips."""
    per_chip: List[Dict[str, Any]] = []
    for chip, events in trace.devices.items():
        steps = tracing.module_spans(trace.modules.get(chip, []),
                                     step_module, t0, t1)
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
                scope, backward = scope_of(op or "", scopes)
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


def table(s: Dict[str, Any], scopes: Sequence[str]) -> str:
    cells = [f"{sc} {1e3 * s.get(sc + '.fwd', 0.0):.3f}/"
             f"{1e3 * s.get(sc + '.bwd', 0.0):.3f}" for sc in scopes]
    scoped = sum(scope_s(s, sc) for sc in scopes)
    return (f"device ms per step by scope (forward/backward) over "
            f"{s['steps']:g} steps: {'; '.join(cells)}; unscoped "
            f"{1e3 * s['unscoped']:.3f}; scoped + unscoped "
            f"{1e3 * (scoped + s['unscoped']):.3f} of the step's "
            f"{1e3 * s['total']:.3f} (not in the map "
            f"{1e3 * s['unmapped']:.3f})")


def read(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The split of the traced slice's steps by the cell's family's
    scopes, computed once per run and kept in ``obs``; None where the
    program has no scopes or the map does not cover the trace."""
    if "scopes" in obs:
        return obs["scopes"]
    obs["scopes"] = None
    summary = obs.get("trace_summary")
    names = op_names(obs)
    if summary is None or names is None:
        return None
    scopes = obs["family"].SCOPES
    s = split(obs["trace"], summary["t0_ns"], summary["t1_ns"], names,
              obs["step_module"], scopes)
    if s is None:
        return None
    lo, hi = obs["scope_map_span_s"]
    print(f"[scopes] op_name map built in {hi - lo:.2f}s; "
          f"{table(s, scopes)}", flush=True)
    if s["total"] <= 0 or s["unmapped"] > (1 - MIN_MAPPED) * s["total"] \
            or not any(scope_s(s, sc) > 0 for sc in scopes):
        return None
    obs["scopes"] = s
    return s
