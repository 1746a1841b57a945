"""Published peaks of one chip, keyed by `device_kind` as JAX reports it.

A device that is not in the table is an error, never a default: a share of
a peak is only as good as the peak it is divided by.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float           # FLOP/s, dense bf16 matrix units
    hbm_bytes_per_s: float      # HBM bandwidth
    hbm_bytes: float            # HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; the table has {sorted(PEAKS)}"
                       ) from None
