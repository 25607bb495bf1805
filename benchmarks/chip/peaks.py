"""Published peaks of each accelerator the benchmark may run on, keyed by
JAX's ``device_kind``. A kind that is not in the table is an error, never
a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to peaks.py with their "
                       f"source") from None
