"""Shared by the attention kernels' roofline readers: the kernels' device
time per training step, against the least time the attention the step
requires could take on the chip (``flops.attention_cost``, once per
layer, on the sizes of the cell's family, ``dims``).  Only steps that lie
wholly inside the traced slice count (the modules the runner names,
``obs["step_module"]``), and only the kernel time inside them.

A kernel's instructions are found by name (:data:`PATTERN`), and their
direction by the scope map of the step the runner timed
(``scopes.op_names``): a ``transpose(`` component of the ``op_name``
marks the backward, whatever the kernel writes."""

import re

from benchmarks.chip import flops, peaks, scopes, tracing

PATTERN = r"^%?\S*flash_attention\S* = "   # the FA-2 kernels' instructions


def kernel_ns(events, names, pattern: str, backward: bool, t0: float,
              t1: float) -> float:
    """Device time in [t0, t1] (ns) of the ops matching ``pattern`` whose
    ``op_name`` (``names``: instruction -> op_name) is of the direction
    asked; an op not in the map counts for neither."""
    rx = re.compile(pattern)
    total = 0.0
    for event, _, d in tracing.clip(events, t0, t1):
        instr = tracing.short_name(event).split(" ")[0]
        if rx.search(event) and instr in names \
                and scopes.backward(names[instr]) == backward:
            total += d
    return total


def share(obs, pattern: str, backward: bool):
    trace, s = obs.get("trace"), obs.get("trace_summary")
    if trace is None:
        return None
    names = scopes.op_names(obs)
    if names is None:
        return None
    n = obs["family"].dims(obs["config"])
    job = obs["traffic"]
    fl, by = flops.attention_cost(job["batch"], job["seq_len"], n["H"],
                                  n["K"], n["hd"], backward=backward)
    need = n["L"] * flops.roofline_s(fl, by, peaks.lookup(obs["device_kind"]))
    shares = []
    for chip, events in trace.devices.items():
        steps = tracing.module_spans(trace.modules.get(chip, []),
                                     obs["step_module"], s["t0_ns"],
                                     s["t1_ns"])
        ns = sum(kernel_ns(events, names, pattern, backward, lo, hi)
                 for lo, hi in steps)
        if steps and ns > 0:
            shares.append(need / (ns / 1e9 / len(steps)))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
