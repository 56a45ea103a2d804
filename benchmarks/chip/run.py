"""Runs one cell of the chip benchmark once.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Loads and warms up, measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON object as
the last line of standard output (the numbers compared, each beside its
limit, also go last to standard error).  ``--trace 1`` adds a traced
slice after the window and reports the cell's per-layer metrics in place
of its end-to-end ones.  Without a TPU, or with fewer chips than the cell
asks for, it exits with code 2 before any phase and prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import harness, peaks  # noqa: E402

CACHE_DIR = harness.ROOT / ".jax_cache"


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        print(f"[run] needs {n} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr, flush=True)
        sys.exit(2)
    peaks.lookup(devices[0].device_kind)      # an unknown chip is an error
    return devices[:n]


def use_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    devices = require_chips(cell.chips)
    use_cache()
    limits = harness.load_json(harness.HERE / "limits" / f"{cell.name}.json")
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        out = harness.runner(cell).run(cell, args.seed, args.seconds,
                                       bool(args.trace), Path(tmp), devices,
                                       limits)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    if args.trace:
        s = out.obs["trace_summary"]
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
    line = harness.result_line(out, cell, device, bool(args.trace))
    harness.print_checks(out.checks)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
