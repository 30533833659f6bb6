"""End-to-end behaviour tests: training convergence, checkpoint/restart
recovery, the coupled HPC+analytics pipeline (the paper's application
pattern), and serving."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat, configs
from repro.core import (ComputeUnitDescription, PilotDescription, PilotManager,
                        ResourceManager)
from repro.optim import adamw
from repro.train.trainer import Trainer


@pytest.fixture
def pm():
    m = PilotManager(ResourceManager())
    yield m
    m.shutdown()


def _mesh1():
    return compat.make_mesh((1, 1), ("data", "model"))


def test_train_loss_decreases(tmp_path):
    cfg = configs.get_smoke("llama3.2-1b")
    tr = Trainer(cfg, _mesh1(), global_batch=8, seq=32,
                 hyper=adamw.Hyper(lr=1e-2), seed=0)
    hist = tr.run(60, log_every=0)
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first - 0.5, f"no learning: {first:.3f} -> {last:.3f}"


def test_train_microbatched_matches_flat_loss():
    cfg = configs.get_smoke("internlm2-1.8b")
    t1 = Trainer(cfg, _mesh1(), global_batch=8, seq=16, n_microbatches=1, seed=1)
    t2 = Trainer(cfg, _mesh1(), global_batch=8, seq=16, n_microbatches=4, seed=1)
    h1 = t1.run(3, log_every=0)
    h2 = t2.run(3, log_every=0)
    for a, b in zip(h1, h2):
        assert a["loss"] == pytest.approx(b["loss"], rel=2e-2)


def test_checkpoint_restart_resumes_exactly(tmp_path):
    """Kill-and-restart: the restored run continues from the same state."""
    cfg = configs.get_smoke("yi-6b")
    d = str(tmp_path / "ck")
    t1 = Trainer(cfg, _mesh1(), global_batch=4, seq=16, ckpt_dir=d,
                 ckpt_every=5, seed=2)
    t1.run(10, log_every=0)

    # fresh trainer (simulated restart) resumes from step 10
    t2 = Trainer(cfg, _mesh1(), global_batch=4, seq=16, ckpt_dir=d,
                 ckpt_every=5, seed=2)
    step = t2.restore()
    assert step == 10
    h2 = t2.run(12, log_every=0)
    assert [h["step"] for h in h2] == [10, 11]

    # uninterrupted reference run gives the same losses at steps 10-11
    t3 = Trainer(cfg, _mesh1(), global_batch=4, seq=16, seed=2)
    h3 = t3.run(12, log_every=0)
    ref = {h["step"]: h["loss"] for h in h3}
    for h in h2:
        assert h["loss"] == pytest.approx(ref[h["step"]], rel=1e-3)


def test_failure_recovery_via_checkpoint(tmp_path, pm):
    """Node failure mid-run -> pilot shrinks -> restore -> finish."""
    cfg = configs.get_smoke("llama3.2-1b")
    d = str(tmp_path / "ck")
    tr = Trainer(cfg, _mesh1(), global_batch=4, seq=16, ckpt_dir=d,
                 ckpt_every=4, seed=3)
    with pytest.raises(RuntimeError, match="injected node failure"):
        tr.run(20, log_every=0, inject_failure_at=9)
    # recovery: new trainer on the surviving resources
    tr2 = Trainer(cfg, _mesh1(), global_batch=4, seq=16, ckpt_dir=d, seed=3)
    step = tr2.restore()
    assert step == 8  # last checkpoint before the failure
    hist = tr2.run(12, log_every=0)
    assert hist[-1]["step"] == 11


def test_coupled_hpc_analytics_pipeline(pm, tmp_path):
    """The paper's motivating pattern: an HPC stage (training) produces
    trajectory data; a Mode-I analytics cluster clusters it with K-Means;
    the result steers the next HPC stage. All on one pilot."""
    from repro.analytics import kmeans as km

    pilot = pm.submit(PilotDescription(n_chips=1, name="coupled"))
    cfg = configs.get_smoke("hymba-1.5b")

    def hpc_stage(mesh=None):
        tr = Trainer(cfg, mesh, global_batch=4, seq=16, seed=4)
        hist = tr.run(3, log_every=0)
        # 'trajectory data': final hidden states of a probe batch
        from repro.data.batches import make_batch
        from repro.models import transformer
        rng = np.random.default_rng(0)
        b = make_batch(cfg, "train", 4, 16, rng)
        logits, _ = transformer.forward(cfg, tr.state["params"], b, remat=False)
        traj = np.asarray(logits.reshape(-1, logits.shape[-1])[:, :3],
                          np.float32)
        return hist[-1]["loss"], traj

    cu = pilot.submit(ComputeUnitDescription(fn=hpc_stage, gang=True,
                                             n_chips=1, tag="sim"))
    loss, traj = cu.wait(600)
    assert np.isfinite(loss)

    cluster = pilot.spawn_analytics_cluster(1)
    cluster.engine.put("traj", traj)
    centroids, cost = km.kmeans_fit(cluster.engine, "traj", 4, iters=2)
    assert np.isfinite(cost) and centroids.shape == (4, 3)
    cluster.shutdown()
    assert pilot.agent.scheduler.n_free == 1  # chips returned to HPC stage


def test_serving_pipeline():
    from repro.launch.serve import serve_batch
    cfg = configs.get_smoke("internvl2-2b")
    res = serve_batch(cfg, n_requests=2, prompt_len=16, gen=4)
    assert res["tokens"].shape == (2, 4)
    assert (res["tokens"] >= 0).all() and (res["tokens"] < cfg.vocab_size).all()


def test_dryrun_cell_single_device():
    """The dry-run machinery works on arbitrary meshes (1 device here)."""
    from repro.launch.dryrun import build_cell
    from repro.models.config import SHAPES, ShapeConfig
    from repro.sharding import Plan
    import dataclasses

    cfg = configs.get_smoke("llama3.2-1b")
    shape = ShapeConfig("tiny_train", 32, 4, "train")
    mesh = _mesh1()
    plan = Plan.for_mesh(mesh)
    fn, args, extra = build_cell(cfg, shape, mesh, plan,
                                 overrides={"n_microbatches": 1})
    with mesh:
        compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0
