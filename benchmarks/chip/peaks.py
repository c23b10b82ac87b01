"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A kind that is not here is an error.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float      # bfloat16 FLOP/s
    hbm_bw: float     # HBM bytes/s
    hbm_bytes: float  # HBM capacity


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
