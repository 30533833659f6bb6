"""Arithmetic the metric readers share (each metric keeps its own file)."""
from __future__ import annotations

import re
from statistics import fmean
from typing import Optional

from chipbench import counts

TRAIN_STEP_PROGRAM = "jit_train_step"
# a fit's initial draw: jax.random.choice(..., replace=False) permutes
# every point index in this program
KMEANS_INIT_PROGRAM = "jit__shuffle"


def in_window(rec, name: str):
    return rec.spans.durations(name, since=rec.window_start)


def mean_ms(values) -> Optional[float]:
    values = list(values)
    return 1e3 * fmean(values) if values else None


def idle_pct(rec) -> Optional[float]:
    t = rec.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def program_ms(rec, prefix: str) -> Optional[float]:
    """Device time per execution of the programs named ``prefix...``."""
    if rec.trace is None:
        return None
    s, n = rec.trace.module_time(lambda name: name.startswith(prefix))
    return 1e3 * s / n if n else None


def program_s(rec, prefix: str) -> float:
    """Device time of all the programs named ``prefix...`` (0 if none)."""
    return rec.trace.module_time(lambda name: name.startswith(prefix))[0]


def train_flops(rec) -> float:
    seq = int(rec.traffic["train"]["seq"])
    return counts.train_flops_per_token(rec.config, seq) * \
        rec.counters.get("train_tokens", 0)


def kmeans_flops(rec) -> float:
    c = rec.counters
    if not c.get("kmeans_iters"):
        return 0.0
    return counts.kmeans_iter_flops(c["kmeans_points"], c["kmeans_k"],
                                    c["kmeans_d"]) * c["kmeans_iters"]


def share_of_peak_pct(rec, flops: float) -> Optional[float]:
    if not rec.window_s or not flops:
        return None
    return 100.0 * flops / (rec.window_s * rec.chips * rec.peak.flops)


def kernel_s(rec, match) -> Optional[float]:
    if rec.trace is None:
        return None
    s = rec.trace.op_time(match)
    return s if s > 0 else None


# the assignment kernel's custom call: (idx s32[n], min f32[n]) out
_KMEANS_KERNEL = re.compile(
    r"^%\S+ = \(s32\[\d+\]\{[^}]*\}, f32\[\d+\]\{[^}]*\}\) custom-call\(")


def is_kmeans_kernel(op_name: str) -> bool:
    """The Pallas assignment kernel's operation in the device trace."""
    return bool(_KMEANS_KERNEL.match(op_name))
