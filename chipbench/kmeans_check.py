"""The K-Means comparison both K-Means paths share: a fit's answer
against the reference on the same points from the same seed."""
from __future__ import annotations

import os
from typing import Any, Dict

import numpy as np

from chipbench import checks, data, harness


def config(root: str, traffic: Dict[str, Any]) -> Dict[str, Any]:
    """The K-Means configuration file a traffic mix names."""
    bench = harness.load_benchmark(root)
    name = traffic["kmeans"]["config"]
    conf = next(c for c in bench["configs"] if c["name"] == name)
    return harness.load_json(os.path.join(root, conf["file"]))


def gaps(points, k: int, iters: int, seed: int, centroids,
         cost: float) -> Dict[str, float]:
    from chipbench.reference import kmeans as ref
    rc, rcost = ref.fit(points, k, iters, seed)
    return checks.kmeans_gaps(centroids, cost, rc, rcost)


def readings(devices, seed: int, n: int, d: int, mixture: int, k: int,
             iters: int, control: bool) -> Dict[str, Any]:
    """Calibration: one program fit (compiled kernel, local path) on ``n``
    points made from ``seed`` against the reference; with ``control``
    also the bf16 control against the reference."""
    from chipbench.reference import kmeans as ref
    from repro import compat
    from repro.analytics import kmeans as km
    from repro.analytics.engine import AnalyticsEngine
    engine = AnalyticsEngine(compat.make_mesh((1, 1), ("data", "model"),
                                              devices=devices[:1]))
    pts = data.mixture(data.key(seed, 3), n, d, mixture,
                       engine.block_sharding())
    engine.put("p", pts)
    init = int(np.random.default_rng((seed, 5)).integers(0, 2 ** 31))
    c, cost = km.kmeans_fit(engine, "p", k, iters=iters, use_kernel=True,
                            data_path="local", seed=init)
    rc, rcost = ref.fit(pts, k, iters, init)
    row = {"n": n, "program": checks.kmeans_gaps(np.asarray(c), cost, rc,
                                                 rcost)}
    if control:
        cc, ccost = ref.fit(pts, k, iters, init, bf16=True)
        row["control_bf16"] = checks.kmeans_gaps(cc, ccost, rc, rcost)
    return row
