"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s
HBM, 16 GB).  A kind that is not here is an error, never a default: a
roofline against another chip's peaks is a wrong number.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops_bf16: float        # FLOP/s
    hbm_bw: float            # bytes/s
    hbm_bytes: float         # bytes of device memory


CHIP_PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    if device_kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}")
    return CHIP_PEAKS[device_kind]
