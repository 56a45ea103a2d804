"""Reduction of a JAX profiler trace to device busy time, op time and
idle gaps.

A traced run records its window under ``jax.profiler`` and reads the
``.xplane.pb`` back with ``jax.profiler.ProfileData``.  On a TPU each chip
is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
operation run, nested: a loop's event spans the events of its body.  The
host plane ``/host:CPU`` holds the benchmark's own
``jax.profiler.TraceAnnotation`` spans, named ``bench.<what>``, on the same
clock.  Everything below works on plain (name, start_ns, dur_ns) tuples,
so the tests can feed it a small recorded trace.
"""

from __future__ import annotations

import contextlib
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]         # name, start_ns, dur_ns
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


@dataclass
class Trace:
    devices: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


@contextlib.contextmanager
def capture(path: Path):
    import jax
    jax.profiler.start_trace(str(path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(Path(path).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(str(files[-1]))
    out = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dest = out.devices if line.name == OPS_LINE else out.modules
                dest[int(m.group(1))] = [
                    (e.name, e.start_ns, e.duration_ns) for e in line.events]
            elif plane.name.startswith("/host:"):
                out.host.extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX))
    return out


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to the window [t0, t1] (ns); those outside dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Sequence[Event], t0: float, t1: float) -> float:
    """Length of the union of the operations' intervals in the window."""
    return sum(b - a for a, b in union(
        (s, s + d) for _, s, d in clip(events, t0, t1)))


def short_name(name: str) -> str:
    """``%fusion.12 = f32[6,2048]{...} fusion(...)`` -> ``fusion.12
    f32[6,2048]``: the instruction and its result's leading shape."""
    m = re.match(r"%?([\w.\-]+)\s*=\s*\(?(\w+\[[\d,]*\])?", name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(2) or ''}".strip()


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Time of each operation less the time of the operations nested in
    it (a loop's body), summed by :func:`short_name`."""
    out: Dict[str, float] = {}
    stack: List[List] = []          # [name, end, child_ns, dur_ns]

    def close(entry):
        name, _, child, dur = entry
        key = short_name(name)
        out[key] = out.get(key, 0.0) + max(dur - child, 0.0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            close(stack.pop())
        if stack:
            stack[-1][2] += d
        stack.append([name, s + d, 0.0, d])
    while stack:
        close(stack.pop())
    return out


def idle_gaps(events: Sequence[Event], host: Sequence[Event], t0: float,
              t1: float, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest stretches of the window with no operation on the
    device, each named by the innermost benchmark span open at its
    midpoint (``-`` where none is).  ``host`` should not hold the span
    that defines the window."""
    busy = union((s, s + d) for _, s, d in clip(events, t0, t1))
    gaps, prev = [], t0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if t1 > prev:
        gaps.append((prev, t1))
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        open_spans = [(s, -d, name) for name, s, d in host
                      if s <= mid <= s + d]
        label = max(open_spans)[2] if open_spans else "-"
        labelled.append((label, (b - a) / 1e9))
    return labelled


def module_spans(modules: Sequence[Event], name: str, t0: float,
                 t1: float) -> List[Tuple[float, float]]:
    """(start, end) in ns of the modules named ``name`` (the ``XLA
    Modules`` line names a jitted program ``name(<id>)``) that lie wholly
    inside [t0, t1]."""
    rx = re.compile(rf"^{re.escape(name)}\b")
    return [(a, a + d) for n, a, d in modules
            if rx.search(n) and a >= t0 and a + d <= t1]


def window(host: Sequence[Event], name: str) -> Optional[Tuple[float, float]]:
    """(start, end) in ns of the host span ``name`` (the traced window)."""
    for n, s, d in host:
        if n == name:
            return s, s + d
    return None


def summarize(trace: Trace, span: str, top: int = 10):
    """Busy and window seconds averaged over the chips, and the
    ``breakdown`` of the result line, over the host span ``span``."""
    t0, t1 = window(trace.host, span)
    chips = sorted(trace.devices)
    busy = [busy_ns(trace.devices[c], t0, t1) for c in chips]
    ops: Dict[str, float] = {}
    for c in chips:
        for k, v in self_times(clip(trace.devices[c], t0, t1)).items():
            ops[k] = ops.get(k, 0.0) + v / len(chips)
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = idle_gaps(trace.devices[chips[0]],
                     [h for h in trace.host if h[0] != span], t0, t1, top)
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (t1 - t0) / 1e9,
            "t0_ns": t0, "t1_ns": t1,
            "breakdown": {"device_ops": [[k, v / 1e9] for k, v in device_ops],
                          "idle_gaps": [[k, v] for k, v in gaps]}}
