"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports. A device that is not listed is an error:
a share of an unknown peak means nothing.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float   # FLOP/s per chip
    hbm_bytes: float    # bytes/s per chip
    hbm_capacity: float  # bytes per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes=819e9,
                         hbm_capacity=16e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def for_kind(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
