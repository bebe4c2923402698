"""The one table of device peaks the benchmark divides by.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
819 GB/s per chip). Keyed by ``device_kind`` as JAX reports it. A device that
is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"benchmark/lib/peaks.py with its source") from None
