"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.

  fig5      — Pilot/CU startup overheads (paper Fig 5) + AppMaster reuse
  fig6      — K-Means scenarios, local vs global data path (paper Fig 6)
  fig8      — Session placement sweep: locality vs movement cost crossover
  elastic   — static split vs ControlPlane rebalancing (makespan, moved B)
  fairshare — 3 tenants at 6:1:1 load: FIFO vs DRF vs Capacity policies
  dispatch  — Raptor overlay vs per-CU scheduler dispatch throughput
  staging   — async prefetch + replica cache vs synchronous staging
  serve     — disaggregated prefill/decode serving vs static engine
  roofline_placement — roofline-aware vs byte-only placement split
  roofline  — per-(arch x shape x mesh) roofline terms from the dry-run
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "fig5", "fig6", "fig8", "elastic",
                             "fairshare", "dispatch", "staging", "serve",
                             "roofline_placement", "roofline", "chaos"])
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_chaos, bench_dispatch, bench_elastic,
                            bench_fairshare, bench_roofline_placement,
                            bench_session_placement,
                            bench_serve_scale, bench_staging,
                            fig5_overheads, fig6_kmeans,
                            roofline_table)
    sections = {
        "fig5": fig5_overheads.run,
        "fig6": fig6_kmeans.run,
        "fig8": bench_session_placement.run,
        "elastic": bench_elastic.run,
        "fairshare": bench_fairshare.run,
        "dispatch": bench_dispatch.run,
        "staging": bench_staging.run,
        "serve": bench_serve_scale.run,
        "roofline_placement": bench_roofline_placement.run,
        "roofline": roofline_table.run,
        "chaos": bench_chaos.run,
    }
    print("name,us_per_call,derived")
    for name, fn in sections.items():
        if args.only and name != args.only:
            continue
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001 — report, keep benching
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}")
            continue
        for r in rows:
            derived = str(r["derived"]).replace(",", ";")
            print(f"{r['name']},{r['us_per_call']:.1f},{derived}")


if __name__ == "__main__":
    main()
