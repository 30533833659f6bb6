"""The harness finds its files by name and runs every driver end to end
at smoke size on the CPU, in a copy that only adds files and entries."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = harness.ROOT

SMOKE_MODEL = {
    "name": "smoke-gqa", "source": "test", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 500,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "bias": False, "torch_dtype": "float32",
    "limits": {"grad_gap": 1e-3, "delta_gap": 1e-3}}
SMOKE_KMEANS = {
    "name": "smoke-kmeans", "source": "test", "dim": 3, "clusters": 8,
    "iterations": 2, "points": 8192, "mixture": 4, "dtype": "float32",
    "limits": {"centroid_gap": 1e-3, "cost_gap": 1e-3}}
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0, "warmup_steps": 1, "total_steps": 1000}
SMOKE_TRAFFIC = {
    "smoke-coupled": {
        "driver": "coupled",
        "pilots": {"hpc": {"runtime": "hpc", "chips": 1},
                   "ana": {"runtime": "analytics", "chips": 1}},
        "train": {"batch": 2, "seq": 64, "steps_per_round": 2},
        "optimizer": OPT, "frame": {"points": 4096, "dim": 3, "mixture": 4},
        "kmeans": {"config": "smoke-kmeans", "clusters": 8, "iterations": 2},
        "warm_rounds": 1},
    "smoke-train": {
        "driver": "train", "pilots": {"hpc": {"runtime": "hpc", "chips": 1}},
        "train": {"batch": 2, "seq": 64}, "optimizer": OPT, "warm_steps": 1},
    "smoke-kmeans": {
        "driver": "kmeans",
        "pilots": {"ana": {"runtime": "analytics", "chips": 1}},
        "warm_fits": 1},
}
SMOKE_CELLS = {"smoke.coupled": ("smoke-gqa", "smoke-coupled"),
               "smoke.train": ("smoke-gqa", "smoke-train"),
               "smoke.kmeans": ("smoke-kmeans", "smoke-kmeans")}
EXTRA_METRIC = '''"""rounds_in_window.smoke: coupled rounds the window completed."""


def read(rec):
    return rec.counters.get("units") or None
'''


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    """A copy of the benchmark with smoke cells added as new files and
    new entries of BENCHMARK.json; no file of the copy is edited."""
    root = str(tmp_path_factory.mktemp("bench"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_benchmark(ROOT)
    for cj in (SMOKE_MODEL, SMOKE_KMEANS):
        rel = f"chipbench/configs/{cj['name']}.json"
        _dump(os.path.join(root, rel), cj)
        bench["configs"].append({"name": cj["name"], "source": "test",
                                 "file": rel, "reduced": [], "why": "smoke"})
    for name, t in SMOKE_TRAFFIC.items():
        _dump(os.path.join(root, "chipbench", "traffic", f"{name}.json"), t)
    for cell, (conf, traffic) in SMOKE_CELLS.items():
        bench["workloads"].append({"name": cell, "config": conf,
                                   "traffic": traffic, "chips": 1,
                                   "why": "smoke"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            real = {"coupled": "coupled.internlm2-1.8b",
                    "train": "train.internlm2-1.8b",
                    "kmeans": "kmeans.fig6-block"}[cell.split(".")[1]]
            if real in m.get("workloads", ()):
                m["workloads"].append(cell)
    with open(os.path.join(root, "chipbench", "metrics",
                           "rounds_in_window.smoke.py"), "w") as f:
        f.write(EXTRA_METRIC)
    bench["per_layer"].append({
        "name": "rounds_in_window.smoke", "unit": "rounds", "better": "higher",
        "source": "host_clock", "layer": "Session and DAG (core/session.py)",
        "moves": "round_s", "workloads": ["smoke.coupled"]})
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def test_every_named_file_is_found():
    bench = harness.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert hasattr(cell.driver, "setup") and hasattr(cell.driver, "check")
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


@pytest.mark.parametrize("cell", sorted(SMOKE_CELLS))
def test_smoke_cell_runs_correct(smoke_root, cell, capsys):
    code, res = harness.run(cell, 2 ** 33 + 17, 1.0, False, root=smoke_root,
                            require_tpu=False)
    assert code == 0 and res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert list(res)[-1] == "checks"
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["correct"] is True


def test_extra_metric_is_read_in_traced_run(smoke_root):
    code, res = harness.run("smoke.coupled", 5, 1.0, True, root=smoke_root,
                            require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["rounds_in_window.smoke"]["value"] >= 1
    assert "orchestration_ms.coupled" in res["metrics"]
    assert "busy_s" in res["device"] and "breakdown" in res


@pytest.mark.parametrize("cell", sorted(SMOKE_CELLS))
def test_calibration_readings_come_from_the_cells_driver(smoke_root, cell,
                                                          tmp_path):
    import math
    from chipbench import calibrate
    out = tmp_path / "readings.jsonl"
    assert calibrate.main(["--workload", cell, "--seeds", "1", "--controls",
                           "1", "--first-seed", str(2 ** 33 + 3),
                           "--out", str(out)], root=smoke_root) == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    want = {"smoke.coupled": {"train", "frame"}, "smoke.train": {"train"},
            "smoke.kmeans": {"block"}}[cell]
    assert {r["part"] for r in rows} == want
    for r in rows:
        assert r["workload"] == cell and r["seed"] == 2 ** 33 + 3
        assert "control_fp8" in r or "control_bf16" in r
        assert all(math.isfinite(v) for v in r["program"].values())


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "kmeans.fig6-block", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_too_few_chips_refused(smoke_root):
    import jax
    bench = harness.load_benchmark(smoke_root)
    bench["workloads"].append({"name": "smoke.four", "config": "smoke-kmeans",
                               "traffic": "smoke-kmeans",
                               "chips": len(jax.devices()) + 1,
                               "why": "smoke"})
    _dump(os.path.join(smoke_root, "BENCHMARK.json"), bench)
    with pytest.raises(harness.NoChip):
        harness.run("smoke.four", 1, 1.0, False, root=smoke_root,
                    require_tpu=False)


def test_benchmark_json_shape():
    import re
    bench = harness.load_benchmark(ROOT)
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert name.match(e["name"]) and e["name"] not in seen
            seen.add(e["name"])
            if "unit" in e:
                assert unit.match(e["unit"]) and e["better"] in ("lower",
                                                                 "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                              "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
