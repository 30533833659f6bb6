"""Fig-6 analogue: K-Means time-to-completion across the paper's scenarios.

Paper setup: 3 scenarios with constant points x clusters product
(10k x 5k, 100k x 500, 1M x 50), d=3, 2 iterations; RP (Lustre path) vs
RP-YARN (local-disk path) on 8/16/32 tasks. Finding: the data-local path
averaged ~13% faster, with better speedup at higher task counts.

Here: identical scenarios (scaled by --scale for the CPU container),
'tasks' = engine shards, local vs global data path, wall-clock measured.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax

from repro import compat
from repro.analytics import kmeans as km
from repro.analytics.engine import AnalyticsEngine
from repro.core.pilot_data import PilotDataRegistry

SCALE = 16  # divide paper scenario sizes by this on the CPU container


def run(scale: int = SCALE, use_kernel: bool = False) -> List[Dict]:
    rows = []
    mesh = compat.make_mesh((len(jax.devices()), 1), ("data", "model"))
    for scen, (n_pts, n_clu) in km.PAPER_SCENARIOS.items():
        n = max(256, n_pts // scale)
        k = max(4, n_clu // scale)
        eng = AnalyticsEngine(mesh, PilotDataRegistry())
        eng.put("pts", km.make_dataset(n, km.PAPER_DIM, n_clusters=8, seed=0))
        # warm-up both paths (compile) then interleave 5 measured reps each
        for path in ("local", "global"):
            km.kmeans_fit(eng, "pts", k, iters=1, data_path=path,
                          use_kernel=use_kernel)
        times = {"local": [], "global": []}
        cost = 0.0
        for _ in range(5):
            for path in ("local", "global"):
                t0 = time.monotonic()
                _, cost = km.kmeans_fit(eng, "pts", k, iters=km.PAPER_ITERS,
                                        data_path=path, use_kernel=use_kernel)
                times[path].append(time.monotonic() - t0)
        for path in ("local", "global"):
            dt = sorted(times[path])[len(times[path]) // 2]  # median
            rows.append({
                "name": f"fig6/{scen}/{path}",
                "us_per_call": float(dt * 1e6),
                "derived": (f"n={n} k={k} cost={cost:.1f} "
                            f"moved_MB={eng.moved_bytes/1e6:.1f}")})
    # the paper's headline: local vs global ratio
    loc = [r for r in rows if r["name"].endswith("/local")]
    glo = [r for r in rows if r["name"].endswith("/global")]
    speedups = [g["us_per_call"] / l["us_per_call"] for l, g in zip(loc, glo)]
    rows.append({"name": "fig6/local_vs_global_speedup",
                 "us_per_call": 0.0,
                 "derived": f"mean_speedup={sum(speedups)/len(speedups):.3f}x"})
    return rows
