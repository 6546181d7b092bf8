"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

A device that is not in the table is an error: a roofline share divided by
a guessed peak would be a number nobody can check.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops_per_s: float  # dense bf16 matrix peak
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


TABLE = {
    "TPU v5 lite": Peaks(
        flops_per_s=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM2 at 819 GB/s per chip",
    ),
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(TABLE)}") from None
