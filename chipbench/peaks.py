"""Published per-chip peaks, keyed by JAX's ``device_kind`` — the ONE table
every utilization figure in this repository divides by.

A device that is not in the table is an error, never a default: an MFU
computed against another chip's peak is a wrong number under a right name.

Sources: Google Cloud TPU documentation, the system-architecture page of
each generation ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per
chip; "TPU v4": 275 TFLOP/s, 1,228 GB/s; "TPU v5p": 459 TFLOP/s,
2,765 GB/s; "TPU v6e": 918 TFLOP/s, 1,640 GB/s). JAX spells the e-series
``lite`` in ``device_kind`` ("TPU v5 lite"); both spellings are listed.
"""

from __future__ import annotations

__all__ = ["PEAKS", "peak_bf16_flops"]

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_sec": 819e9}
_V6E = {"bf16_flops": 918e12, "hbm_bytes_per_sec": 1640e9}

PEAKS = {
    "TPU v4": {"bf16_flops": 275e12, "hbm_bytes_per_sec": 1228e9},
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
    "TPU v5p": {"bf16_flops": 459e12, "hbm_bytes_per_sec": 2765e9},
    "TPU v6 lite": _V6E,
    "TPU v6e": _V6E,
}


def peak_bf16_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s of one chip of ``device_kind``; raises ``KeyError``
    naming the kind when it is not in :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]["bf16_flops"]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} in "
            f"benchmarks/peaks.py (known: {sorted(PEAKS)}); add the chip "
            f"with its source instead of assuming another chip's peak"
        ) from None
