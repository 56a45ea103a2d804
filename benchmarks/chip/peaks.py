"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

A kind that is not in the table is an error, never a default: a share of
a peak read against the wrong chip's peak is a wrong number.
"""

from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
# of chip-to-chip interconnect.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def lookup(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; KeyError for a chip not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
