"""Readings that the limits in ``limits/<cell>.json`` are set from, taken
on the chip at the cell's own size.  Not part of a benchmark run.

    python benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,... [--control 11,12,13] [--faults 11,12,13]

The cell's runner (``runners/<runner>.py``) reads the numbers compared
for the program on every seed of ``--seeds``, for the control (the
float32 reference with every matrix product rounded to float8, one scale
per tensor, in the program's place) on every seed of ``--control``, and
for the faults it plants on every seed of ``--faults``.  One JSON line
per reading.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.run import require_chips, use_cache  # noqa: E402


def seeds(text: str):
    return [int(x) for x in text.split(",") if x]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    args = ap.parse_args()
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    devices = require_chips(cell.chips)
    use_cache()
    for reading in harness.runner(cell).calibrate(
            cell, seeds(args.seeds), seeds(args.control), seeds(args.faults),
            devices):
        print("[calibrate] " + json.dumps(reading), flush=True)


if __name__ == "__main__":
    main()
