"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

A device kind that is not in the table is an error, never a default."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float        # dense bf16 FLOP/s of one chip
    hbm_bw: float       # HBM bytes/s of one chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table knows {sorted(PEAKS)}") from None
