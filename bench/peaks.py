"""Published peaks of each chip, keyed by the ``device_kind`` JAX reports.

A device that is not in the table is an error, not a default: a share of
a peak is only meaningful against that chip's own peak.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
    # 197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip.
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud, TPU v5e"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {', '.join(PEAKS)}") from None
