"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16 and 16 GB of HBM at 819 GB/s per chip.
A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s, dense bf16 matrix unit
    hbm_bytes_per_s: float


PEAKS = {
    # what JAX reports as a v5e's ``device_kind``
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
