"""Roofline-aware placement benchmark.

Two HPC pilots advertise contrasting rooflines ("bigflops": high peak
FLOP/s, thin HBM; "bigmem": the reverse).  A compute-bound and a
memory-bound stage consume the SAME dataset (equal bytes), so the
byte-only placer co-locates them wherever the data landed; the
roofline-aware placer splits them by modeled est_runtime, and the modeled
makespan drops.

    PYTHONPATH=src python benchmarks/bench_roofline_placement.py [--smoke] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
from typing import Any, Dict, List

import numpy as np


# ---------------------------------------------------------- placement arm
# contrasting advertised rooflines (per chip)
BIGFLOPS = {"peak_flops_per_chip": 100e12, "hbm_bw_per_chip": 100e9}
BIGMEM = {"peak_flops_per_chip": 10e12, "hbm_bw_per_chip": 1000e9}

# equal input bytes, opposite roofline profiles
COMPUTE_COST = {"flops": 1000e12, "hbm_bytes": 10e9}    # intensity 1e5
MEMORY_COST = {"flops": 10e12, "hbm_bytes": 1000e9}     # intensity 1e4


def _modeled_makespan(assign: Dict[str, str]) -> float:
    """Per-pilot sum of roofline est times under an assignment
    {stage: pilot} — the modeled (not slept) step-time metric."""
    from repro.roofline.placement import StageCost, est_runtime
    hw = {"bigflops": BIGFLOPS, "bigmem": BIGMEM}
    costs = {"compute_stage": StageCost(**COMPUTE_COST),
             "memory_stage": StageCost(**MEMORY_COST)}
    per_pilot: Dict[str, float] = {}
    for stage, pilot in assign.items():
        rt = est_runtime(costs[stage], n_chips=1,
                         peak_flops=hw[pilot]["peak_flops_per_chip"],
                         hbm_bw=hw[pilot]["hbm_bw_per_chip"])
        per_pilot[pilot] = per_pilot.get(pilot, 0.0) + rt["est_s"]
    return max(per_pilot.values())


def placement_one(roofline: bool) -> Dict[str, Any]:
    import jax
    from repro.core import (PilotDescription, ResourceManager, Session,
                            StageCost, TransferCostModel, hpc_stage)

    rm = ResourceManager(devices=jax.devices() * 2)
    session = Session(
        rm, cost_model=TransferCostModel(dcn_cost_per_byte=1e-9),
        roofline_placement=roofline)
    session.add_pilot(PilotDescription(n_chips=1, name="bigflops",
                                       runtime="hpc", **BIGFLOPS))
    session.add_pilot(PilotDescription(n_chips=1, name="bigmem",
                                       runtime="hpc", **BIGMEM))

    def gen(**kw):
        return {"x": np.zeros(1024, np.float32)}

    def work(**kw):
        return {}

    session.run([
        hpc_stage("gen", gen, outputs=("x",)),
        hpc_stage("compute_stage", work, inputs=("x",),
                  cost=StageCost(**COMPUTE_COST)),
        hpc_stage("memory_stage", work, inputs=("x",),
                  cost=StageCost(**MEMORY_COST)),
    ])
    pc = session.placements["compute_stage"]
    pm = session.placements["memory_stage"]
    assign = {"compute_stage": pc["pilot"], "memory_stage": pm["pilot"]}
    row = {
        "roofline_placement": roofline,
        "compute_on": pc["pilot"],
        "memory_on": pm["pilot"],
        "split": pc["pilot"] != pm["pilot"],
        "modeled_makespan_s": _modeled_makespan(assign),
        # est terms ride the placement record when roofline is on
        "compute_est_runtime_s": pc["chosen"].get("est_runtime"),
        "memory_est_runtime_s": pm["chosen"].get("est_runtime"),
        "compute_bound": pc["chosen"].get("bound"),
        "memory_bound": pm["chosen"].get("bound"),
        "est_error_ratio": pc.get("est_error_ratio"),
    }
    # the estimate-vs-actual cross-check rides pilot heartbeats
    row["heartbeat_est_drift"] = {
        snap["name"]: snap.get("est_drift")
        for snap in session.control_plane.poll().values()}
    session.shutdown()
    return row


def placement_arm() -> List[Dict[str, Any]]:
    return [placement_one(roofline=False), placement_one(roofline=True)]


# ----------------------------------------------------------------- driver
def run() -> List[Dict]:
    """Rows for benchmarks/run.py, section 'roofline_placement'."""
    rows = []
    for r in placement_arm():
        tag = "roofline" if r["roofline_placement"] else "bytes_only"
        rows.append({"name": f"roofline_placement/{tag}",
                     "us_per_call": r["modeled_makespan_s"] * 1e6,
                     "derived": (f"compute_on={r['compute_on']} "
                                 f"memory_on={r['memory_on']} "
                                 f"split={r['split']}")})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: writes --json, enforces the placement "
                         "split")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write results as JSON (default "
                         "BENCH_roofline_placement.json with --smoke)")
    args = ap.parse_args()

    placement = placement_arm()
    out = {"placement": placement}
    json_path = args.json or ("BENCH_roofline_placement.json" if args.smoke
                              else None)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {json_path}")

    for r in placement:
        tag = "roofline" if r["roofline_placement"] else "bytes-only"
        print(f"placement[{tag:>10}]: compute->{r['compute_on']:<9} "
              f"memory->{r['memory_on']:<9} split={r['split']} "
              f"modeled_makespan={r['modeled_makespan_s']:.1f}s")

    off, on = placement
    print(f"roofline split makespan {on['modeled_makespan_s']:.1f}s vs "
          f"byte-only {off['modeled_makespan_s']:.1f}s")
    if args.smoke:
        if not on["split"] or off["split"]:
            raise SystemExit(
                "placement check failed: expected byte-only co-location "
                f"(got split={off['split']}) and roofline split "
                f"(got split={on['split']})")
        if not on["modeled_makespan_s"] < off["modeled_makespan_s"]:
            raise SystemExit("placement check failed: roofline makespan "
                             "not below byte-only")
        print("smoke checks passed")


if __name__ == "__main__":
    main()
