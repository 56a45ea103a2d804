"""Shared by the attention kernels' roofline readers: the kernels' device
time per training step, against the least time the attention the step
requires could take on the chip (``flops.attention_cost``, once per
layer).  Only steps that lie wholly inside the traced slice count, and
only the kernel time inside them."""

import re

from benchmarks.chip import flops, peaks, tracing
from benchmarks.chip.weights import dims

STEP = re.compile(r"^jit_train_step\b")   # the step, as XLA Modules names it


def share(obs, pattern: str, backward: bool):
    trace, s = obs.get("trace"), obs.get("trace_summary")
    if trace is None:
        return None
    n = dims(obs["config"])
    job = obs["traffic"]
    fl, by = flops.attention_cost(job["batch"], job["seq_len"], n["H"],
                                  n["K"], n["hd"], backward=backward)
    need = n["L"] * flops.roofline_s(fl, by, peaks.lookup(obs["device_kind"]))
    shares = []
    for chip, events in trace.devices.items():
        steps = [(a, a + d) for name, a, d in trace.modules.get(chip, [])
                 if STEP.search(name) and a >= s["t0_ns"]
                 and a + d <= s["t1_ns"]]
        ns = sum(tracing.op_ns(events, pattern, lo, hi)[0]
                 for lo, hi in steps)
        if steps and ns > 0:
            shares.append(need / (ns / 1e9 / len(steps)))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
