"""Coupled rounds over two 2-chip pilots through the unchanged coupled
driver, at smoke size on four host devices (a subprocess, since the
device count is fixed when JAX starts): training on a (2, 1) data mesh,
the frame moved to the other pilot's chips, K-Means over two; the run is
``correct`` and reads the coupled metrics.  This is the traffic of the
four-chip coupled cell that PERF.md lists as not built yet."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from chipbench import harness
from chipbench.test_chipbench_harness import (SMOKE_KMEANS, SMOKE_MODEL,
                                              SMOKE_TRAFFIC)

PILOTS = {"hpc": {"runtime": "hpc", "chips": 2},
          "ana": {"runtime": "analytics", "chips": 2}}
RUN = """
import json, sys
from chipbench import harness
code, res = harness.run("smoke.coupled4", 2 ** 33 + 41, 1.0, True,
                        root=sys.argv[1], require_tpu=False)
print("RESULT " + json.dumps(res))
"""


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def test_four_chip_coupled_traffic_runs_correct(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_benchmark(harness.ROOT)
    for cj in (SMOKE_MODEL, SMOKE_KMEANS):
        rel = f"chipbench/configs/{cj['name']}.json"
        _dump(os.path.join(root, rel), cj)
        bench["configs"].append({"name": cj["name"], "source": "test",
                                 "file": rel, "reduced": [], "why": "smoke"})
    _dump(os.path.join(root, "chipbench", "traffic", "smoke-coupled4.json"),
          dict(SMOKE_TRAFFIC["smoke-coupled"], pilots=PILOTS))
    bench["workloads"].append({"name": "smoke.coupled4",
                               "config": "smoke-gqa",
                               "traffic": "smoke-coupled4", "chips": 4,
                               "why": "smoke"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "coupled.internlm2-1.8b" in m.get("workloads", ()):
            m["workloads"].append("smoke.coupled4")
    _dump(os.path.join(root, "BENCHMARK.json"), bench)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [harness.ROOT, os.path.join(harness.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", RUN, root], env=env,
                       capture_output=True, text=True, timeout=900)
    lines = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-3000:]
    res = json.loads(lines[-1][len("RESULT "):])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert {"orchestration_ms.coupled", "cu_queue_ms.coupled",
            "kmeans_fit_ms.coupled", "coupled_mfu"} <= set(res["metrics"])
