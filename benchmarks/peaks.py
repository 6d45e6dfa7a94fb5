"""Peaks of the chips the benchmark may run on, keyed by `device_kind`.

Copied from `mmlspark_tpu/obs/perf.PEAKS` (PR 21) so that the yardstick does
not move when the program does. Source: Google Cloud TPU documentation,
"TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB per chip. A device that is
not listed is an error, never a default.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        # not a LookupError: a reader must not take this for "nothing to read"
        raise ValueError(f"no peaks listed for device kind {device_kind!r}")
    return PEAKS[device_kind]


def least_seconds(flops: float, bytes_moved: float, device_kind: str):
    """Roofline floor of one call: (seconds, which bound binds)."""
    p = peaks_for(device_kind)
    by_flops = flops / p["flops_per_s"]
    by_bytes = bytes_moved / p["bytes_per_s"]
    return max(by_flops, by_bytes), "compute" if by_flops >= by_bytes else "memory"
