"""Peaks of the chips the benchmark runs on, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float           # bf16 FLOP/s per chip
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
                        source="cloud.google.com/tpu/docs/v5e"),
}


class UnknownDevice(KeyError):
    """The device kind has no entry in :data:`PEAKS`."""


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} has no peaks in chipbench/peaks.py "
            f"(known: {sorted(PEAKS)})") from None
