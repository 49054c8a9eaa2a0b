"""Published peaks of each device the benchmark runs on, keyed by
``device_kind`` as JAX reports it.  A device that is not here is an error.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" system
architecture page: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s
    hbm_bytes: float  # bytes/s
    hbm_capacity: float  # bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9, "Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
