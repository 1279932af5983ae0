"""Published peaks of each chip the benchmark knows, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s per chip. Copied from the program's
``launch/hlo_analysis.PEAKS`` so that the yardstick cannot move with it. A
kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # FLOP/s per chip
        "int8_ops": 393e12,        # OP/s per chip
        "hbm_bytes_per_s": 819e9,  # bytes/s per chip
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return dict(PEAKS[device_kind])


def roofline_seconds(ops: float, nbytes: float, device_kind: str):
    """Least time the chip could take for this work, and which term bounds
    it: ``(seconds, "compute" | "memory")``. Operations are held to the
    bf16 peak, the chip's highest floating-point rate."""
    p = peaks_for(device_kind)
    compute = ops / p["bf16_flops"]
    memory = nbytes / p["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
