"""The numbers that decide ``correct``: gaps between what the timed path
produced and the plain reference, each held to a limit that the
configuration file states (``limits``), set from measured readings.

Training (the first three steps of the very Trainer the window drives):

* ``loss_gap``  the largest |loss - ref| / |ref| over the steps;
* ``grad_gap``  over leaves, the largest gap between the norm of the first
  clipped gradient and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf;
* ``delta_gap`` the same for the norm of the parameters' change after
  the steps.

Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of both gap norms.

K-Means (a fit's answer):

* ``centroid_gap`` the largest |c - ref| over the centroids, over the
  largest |ref| coordinate;
* ``cost_gap``     |cost - ref| / ref.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

NOUGHT = 1e-3      # share of the median leaf's gradient norm


def limits(cj: Dict) -> Dict[str, float]:
    """The limit of each compared number, as the configuration states it."""
    return {k: float(v) for k, v in cj["limits"].items()}


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep: Iterable[str]) -> float:
    keep = list(keep)
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keep)


def kept_leaves(ref_g: Dict[str, float]):
    med = float(np.median(list(ref_g.values())))
    return [k for k, v in ref_g.items() if v >= NOUGHT * med]


def train_gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    keep = kept_leaves(ref["g_norms"])
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = float("inf")
    return {"loss_gap": float(loss_gap),
            "grad_gap": _leaf_gap(prog["g_norms"], ref["g_norms"], keep),
            "delta_gap": _leaf_gap(prog["d_norms"], ref["d_norms"], keep)}


def kmeans_gaps(centroids, cost: float, ref_centroids,
                ref_cost: float) -> Dict[str, float]:
    c = np.asarray(centroids, np.float64)
    r = np.asarray(ref_centroids, np.float64)
    if c.shape != r.shape or not np.all(np.isfinite(c)):
        return {"centroid_gap": float("inf"), "cost_gap": float("inf")}
    return {"centroid_gap": float(np.max(np.abs(c - r)) / np.max(np.abs(r))),
            "cost_gap": float(abs(cost - ref_cost) / abs(ref_cost))}
