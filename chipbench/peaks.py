"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.  No
float32 peak is published; the models here run float32, and their
utilisation is stated against the bf16 peak.  A device that is not in the
table is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float      # FLOP/s
    hbm_bytes: float       # bytes/s
    hbm_capacity: float    # bytes
    source: str


V5E = Peak(197e12, 819e9, 16e9, "Google Cloud documentation, TPU v5e")

PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"add its row to chipbench/peaks.py") from None
