"""Published peaks of one chip, keyed by the family of ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 819 GB/s HBM bandwidth,
16 GB HBM per chip. A device that is not in the table is an error.
"""
from __future__ import annotations

CHIP_PEAKS = {
    "v5e": {"flops": 197e12, "bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def family(device_kind: str) -> str:
    k = device_kind.lower().replace("_", " ")
    if "v5" in k and ("lite" in k or "v5e" in k):
        return "v5e"
    raise KeyError("no peak-table row for device_kind=%r" % (device_kind,))


def peaks(device_kind: str) -> dict:
    return CHIP_PEAKS[family(device_kind)]
