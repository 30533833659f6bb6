"""Per-chip peaks keyed by device kind, and roofline terms against them."""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class HW:
    """Published peaks of one chip."""
    peak_flops: float              # bf16 FLOP/s per chip
    hbm_bw: float                  # B/s per chip
    ici_bw: float                  # B/s per link
    hbm_bytes: float               # HBM capacity per chip


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
# 1,600 Gbit/s of chip-to-chip interconnect over 4 links.
CHIPS: Dict[str, HW] = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9,
                      ici_bw=1600e9 / 8 / 4, hbm_bytes=16e9),
}

# The chip the CPU dry-runs and tests model (their devices have no peaks).
V5E = CHIPS["TPU v5 lite"]


def chip_spec(device) -> HW:
    """Peaks of the chip ``device`` is.  A TPU kind missing from
    :data:`CHIPS` is an error, never a default; a non-TPU device (the CPU
    backend of tests and dry-runs) models :data:`V5E`."""
    if device.platform != "tpu":
        return V5E
    try:
        return CHIPS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device.device_kind!r}; "
            f"add them to repro.roofline.terms.CHIPS with their source "
            f"(known: {sorted(CHIPS)})") from None


def roofline_terms(*, flops_global: float, hbm_bytes_global: float,
                   collective_bytes_per_device: float, n_chips: int,
                   model_flops: float, hw: HW = V5E) -> Dict[str, float]:
    compute_s = flops_global / (n_chips * hw.peak_flops)
    memory_s = hbm_bytes_global / (n_chips * hw.hbm_bw)
    collective_s = collective_bytes_per_device / hw.ici_bw
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", collective_s), key=lambda kv: kv[1])
    step_s = max(compute_s, memory_s, collective_s)
    ideal_s = model_flops / (n_chips * hw.peak_flops)
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant[0],
        "model_flops": model_flops,
        "useful_flop_ratio": model_flops / max(flops_global, 1.0),
        "roofline_fraction": ideal_s / max(step_s, 1e-12),
        "step_time_lower_bound_s": step_s,
    }
