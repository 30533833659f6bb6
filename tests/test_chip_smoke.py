"""The chip smoke on the CPU: it refuses to run without a TPU, and its
phase functions pass in-process at smoke configs.  Also the pieces it
relies on: the compile-cache helper and the per-chip device table."""
import importlib.util
import os
import subprocess
import sys
import types

import jax
import pytest

from repro import compat, configs
from repro.analytics.engine import AnalyticsEngine
from repro.core import ResourceManager
from repro.launch import cache
from repro.roofline.terms import CHIPS, V5E, chip_spec
from repro.train.trainer import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_exits_nonzero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, SMOKE], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_dag_phase_at_smoke_config(chip_smoke):
    cfg = configs.get_smoke("llama3.2-1b")
    out = chip_smoke.run_dag(
        cfg, ResourceManager(devices=jax.devices()[:1] * 2), batch=4, seq=32,
        steps=5, scenario="10k_points_5k_clusters", traj_points=256)
    chip_smoke.check_dag(out)            # finite, falling loss; cost match
    assert out["cu_states"] == {"hpc": {"done": 2}, "ana": {"done": 1}}
    assert out["placements"]["analyze"] == {"pilot": "ana", "mode": "native"}
    assert out["replica_pilot"] == "ana" and out["replica_wire_bytes"] > 0
    assert out["results"]["train"]["next_seed"] in range(997)


def test_serve_phase_at_smoke_config(chip_smoke):
    cfg = configs.get_smoke("llama3.2-1b")
    out = chip_smoke.serve_and_check(cfg, prompt_lens=(32, 20, 32), gen=4,
                                     slots=2, bucket=32)
    assert out["n_requests"] == 3 and out["near_ties"] == []
    assert out["steps"] >= 3               # 3 requests through 2 slots


def test_four_chip_checks_on_host_devices():
    """(a)-(c) of ``--chips 4`` on four CPU host devices, smoke config."""
    script = (
        "import os, sys\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "import jax, chip_smoke as cs\n"
        "from repro import configs\n"
        "cfg = configs.get_smoke('llama3.2-1b')\n"
        "devs = jax.devices()\n"
        "assert len(devs) == 4, devs\n"
        "a = cs.check_two_pilots(cfg, devs, seq=32, batch=4,\n"
        "                        scenario='10k_points_5k_clusters')\n"
        "b = cs.check_mesh_training(cfg, devs)\n"
        "c = cs.check_kmeans_paths(devs, scenario='10k_points_5k_clusters')\n"
        "print('FOUR_OK', sorted(a['hpc_devices']), sorted(a['ana_devices']))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FOUR_OK [0, 1] [2, 3]" in proc.stdout


def test_smoke_check_fails_on_rising_loss(chip_smoke):
    with pytest.raises(chip_smoke.CheckFailed):
        chip_smoke.check_losses([5.0, 5.0, 5.1])
    with pytest.raises(chip_smoke.CheckFailed):
        chip_smoke.check_losses([5.0, float("nan"), 4.0])
    chip_smoke.check_losses([5.0, 5.0, 4.9])


# ------------------------------------------------------------------ cache
def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.compile_cache_dir() == str(tmp_path)


def test_compile_cache_in_checkout_is_fixed(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = cache.compile_cache_dir(), cache.compile_cache_dir()
    assert first == second == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_enable_compile_cache_sets_in_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert path.startswith(ROOT)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_autotune_registry_defaults_into_checkout(monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE_REGISTRY", raising=False)
    assert cache.autotune_registry_path().startswith(ROOT + os.sep)


# ----------------------------------------------------------- device table
def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_device_table_knows_v5e():
    hw = chip_spec(_device("tpu", "TPU v5 lite"))
    assert hw is CHIPS["TPU v5 lite"] is V5E
    assert (hw.peak_flops, hw.hbm_bw, hw.hbm_bytes) == (197e12, 819e9, 16e9)


def test_device_table_raises_on_unknown_tpu_kind():
    with pytest.raises(KeyError, match="TPU v99"):
        chip_spec(_device("tpu", "TPU v99"))


def test_device_table_cpu_models_v5e():
    assert chip_spec(jax.devices("cpu")[0]) is V5E


# ------------------------------------------------------ explicit meshes
def test_explicit_axis_mesh_is_refused():
    mesh = compat.make_mesh((1, 1), ("data", "model"), explicit=True)
    with pytest.raises(ValueError, match="Auto axes"):
        Trainer(configs.get_smoke("llama3.2-1b"), mesh)
    with pytest.raises(ValueError, match="Auto axes"):
        AnalyticsEngine(mesh)
