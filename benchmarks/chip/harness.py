"""What every run shares: finding a cell's files by name, the chip's
memory peak, and the result line.

Nothing here imports JAX (a family it loads may).  A cell
(``BENCHMARK.json`` ``workloads``) names a configuration and a traffic
mix; the harness finds

* the configuration at the ``file`` its ``configs`` entry gives,
* the configuration's model family, which its ``family`` key names, at
  ``families/<family>.py`` (what it gives: ``families/decoder.py``),
* the traffic mix at ``traffic/<traffic>.json``, whose ``runner`` key
  names the runner that drives the program, ``runners/<runner>.py``,
  which holds ``run`` (one run of the cell) and ``calibrate`` (the
  readings its limits are set from),
* each per-layer metric's reader at ``metrics/<metric>.py``,

so a new cell, configuration, family, mix, job or metric is new files
and a new entry, and no edit.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(ROOT / "src") not in sys.path:       # the program under test
    sys.path.insert(0, str(ROOT / "src"))


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    family: Any                 # the module families/<config's family>.py
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


@dataclass
class Outcome:
    """What a runner hands back: the verdict, the end-to-end readings,
    and whatever the per-layer readers read (``obs``)."""
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Dict[str, Any]]
    memory_peak_bytes: int
    obs: Dict[str, Any] = field(default_factory=dict)
    breakdown: Optional[Dict[str, Any]] = None


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def find_cell(bench: Dict[str, Any], name: str, root: Path = ROOT,
              here: Path = HERE) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / confs[w["config"]]["file"])
    family = load_module(here / "families" / f"{config['family']}.py")
    traffic = load_json(here / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name, w["chips"], config, family, traffic, e2e, per_layer)


def load_module(path: Path):
    """Import a file by its path (names may hold '.' and '-')."""
    path = path.resolve()
    rel = path.relative_to(HERE) if path.is_relative_to(HERE) else path
    mod_name = "chipbench_" + "".join(
        ch if ch.isalnum() else "_" for ch in str(rel.with_suffix("")))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def runner(cell: Cell, here: Path = HERE):
    return load_module(here / "runners" / f"{cell.traffic['runner']}.py")


def metric_reader(name: str, here: Path = HERE):
    return load_module(here / "metrics" / f"{name}.py")


# ----------------------------------------------------------------- memory

def memory_peak_bytes(devices) -> int:
    """The fullest chip's peak: the buffers' peak (``peak_bytes_in_use``)
    plus the peak the runtime reserved for compiled programs' scratch
    (``peak_bytes_reserved``), which the first leaves out.  0 where the
    platform keeps no statistics."""
    best = 0
    for d in devices:
        s = d.memory_stats() or {}
        best = max(best, s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0))
    return best


# ----------------------------------------------------------------- the result

def check(name: str, value: float, limit: float) -> Dict[str, Any]:
    """One compared number: it passes when it does not exceed ``limit``."""
    return {"name": name, "value": value, "limit": limit,
            "ok": bool(value <= limit)}


def result_line(out: Outcome, cell: Cell, device: Dict[str, Any],
                trace: bool) -> Dict[str, Any]:
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"]).read(out.obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out.end_to_end[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    line: Dict[str, Any] = {"correct": out.correct,
                            "attempted": out.attempted,
                            "failed": out.failed, "metrics": metrics,
                            "device": device}
    if trace and out.breakdown is not None:
        line["breakdown"] = out.breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in out.checks}
    return line


def print_checks(checks: List[Dict[str, Any]]) -> None:
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr,
              flush=True)
