"""Published peaks of the chips this benchmark may run on, keyed by the
``device_kind`` JAX reports. The benchmark keeps its own copy (the program's
is ``flops.CHIP_PEAKS``) so that no later PR can move the yardstick by
editing the program. A device that is not in the table is an error, never a
default."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s, dense bf16 on the MXU
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 2**30,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM2e at 819 GB/s per chip",
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.py "
            f"(known: {sorted(PEAKS)}); add its published peaks with their "
            "source, do not guess"
        ) from None
