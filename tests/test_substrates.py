"""Substrate tests: checkpoint roundtrip/restart, pipeline determinism,
gradient compression, optimizer, analytics engine + K-Means."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # suite degrades to skips without it
from hypothesis import given, settings, strategies as st

from repro import compat, configs
from repro.analytics import kmeans as km
from repro.analytics.engine import AnalyticsEngine
from repro.checkpoint import CheckpointManager
from repro.core.pilot_data import PilotDataRegistry
from repro.data.pipeline import TokenPipeline
from repro.optim import adamw, compression


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(12).reshape(3, 4).astype(jnp.float32),
             "nested": {"b": jnp.ones((5,), jnp.bfloat16)},
             "step": jnp.asarray(7, jnp.int32)}
    cm = CheckpointManager(str(tmp_path), async_save=True)
    cm.save(state, 7)
    cm.wait()
    target = jax.eval_shape(lambda: state)
    out = cm.restore(target)
    assert int(out["step"]) == 7
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(state["a"]))
    assert out["nested"]["b"].dtype == jnp.bfloat16


def test_checkpoint_retention_and_latest(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    s = {"x": jnp.zeros((2,))}
    for step in (1, 2, 3, 4):
        cm.save({"x": jnp.full((2,), step, jnp.float32)}, step)
    assert cm.latest_step() == 4
    assert sorted(cm.all_steps()) == [3, 4]
    out = cm.restore(jax.eval_shape(lambda: s))
    assert float(out["x"][0]) == 4.0


def test_checkpoint_restore_resharded(tmp_path):
    """Restore onto a different sharding (elastic resize path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = compat.make_mesh((1,), ("data",))
    cm = CheckpointManager(str(tmp_path), async_save=False)
    state = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    cm.save(state, 1)
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    out = cm.restore(jax.eval_shape(lambda: state), shardings=shardings)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(state["w"]))


# --------------------------------------------------------------- pipeline
def test_pipeline_deterministic_and_restartable():
    cfg = configs.get_smoke("llama3.2-1b")
    p1 = TokenPipeline(cfg, batch=4, seq=16, seed=3)
    b5 = p1.batch_at(5)
    p2 = TokenPipeline(cfg, batch=4, seq=16, seed=3)
    np.testing.assert_array_equal(np.asarray(b5["tokens"]),
                                  np.asarray(p2.batch_at(5)["tokens"]))
    # labels are next-token shifted
    np.testing.assert_array_equal(np.asarray(b5["labels"][:, :-1]),
                                  np.asarray(b5["tokens"][:, 1:]))


def test_pipeline_prefetch_thread():
    cfg = configs.get_smoke("llama3.2-1b")
    p = TokenPipeline(cfg, batch=2, seq=8, seed=0, prefetch_depth=2).start()
    batches = [next(p) for _ in range(4)]
    p.stop()
    assert all(b["tokens"].shape == (2, 8) for b in batches)
    ref = TokenPipeline(cfg, batch=2, seq=8, seed=0)
    np.testing.assert_array_equal(np.asarray(batches[2]["tokens"]),
                                  np.asarray(ref.batch_at(2)["tokens"]))


# ------------------------------------------------------------ compression
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31), st.integers(1, 500))
def test_int8_quantization_error_bounded(seed, n):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    q, scale = compression.quantize_int8(x)
    err = np.abs(np.asarray(compression.dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) / 2 + 1e-7


def test_error_feedback_accumulates():
    """EF residual carries dropped mass into the next round (mean error
    of the running sum stays bounded, not growing with rounds)."""
    rng = np.random.default_rng(0)
    residual = jnp.zeros((64,), jnp.float32)
    total_in = np.zeros(64, np.float32)
    total_out = np.zeros(64, np.float32)
    for i in range(50):
        g = rng.normal(size=(64,)).astype(np.float32) * (1 + i % 3)
        q, scale, residual = compression.ef_quantize(jnp.asarray(g), residual)
        total_in += g
        total_out += np.asarray(compression.dequantize_int8(q, scale))
    # residual ~ what is still owed; sum identity holds exactly
    np.testing.assert_allclose(total_out + np.asarray(residual), total_in,
                               rtol=1e-4, atol=1e-3)


def test_compressed_psum_matches_fp32():
    """int8 shared-scale psum over a mesh axis ~= exact psum."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("pod",))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 16)).astype(np.float32))
    res = jnp.zeros_like(x)

    def f(xs, rs):
        return compression.compressed_psum(xs, rs, "pod")

    out, new_res = shard_map(f, mesh=mesh, in_specs=(P(), P()),
                             out_specs=(P(), P()))(x, res)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=2e-2,
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(out + new_res), np.asarray(x),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- optimizer
def test_adamw_decreases_quadratic():
    w = {"w": jnp.asarray([3.0, -2.0])}
    opt = adamw.init(w)
    h = adamw.Hyper(lr=0.1, weight_decay=0.0)
    step = jnp.asarray(0, jnp.int32)
    for i in range(200):
        g = {"w": 2 * w["w"]}
        w, opt, _ = adamw.update(w, g, opt, step + i, h)
    assert float(jnp.abs(w["w"]).max()) < 0.05


def test_adamw_scanned_update_matches_elementwise():
    """The lax.map big-leaf path must equal the plain path bitwise-ish."""
    import repro.optim.adamw as A
    rng = np.random.default_rng(0)
    p_small = jnp.asarray(rng.normal(size=(4, 8, 16)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(4, 8, 16)).astype(np.float32))
    opt = A.init({"w": p_small})
    h = A.Hyper()
    out_plain, _, _ = A.update({"w": p_small}, {"w": g}, opt,
                               jnp.asarray(0), h)
    old = A._SCANNED_UPDATE_BYTES
    try:
        A._SCANNED_UPDATE_BYTES = 0  # force the scanned path
        out_scan, _, _ = A.update({"w": p_small}, {"w": g}, opt,
                                  jnp.asarray(0), h)
    finally:
        A._SCANNED_UPDATE_BYTES = old
    np.testing.assert_allclose(np.asarray(out_plain["w"]),
                               np.asarray(out_scan["w"]), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------- analytics
def test_map_reduce_matches_numpy():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    eng = AnalyticsEngine(mesh, PilotDataRegistry())
    x = np.random.default_rng(0).normal(size=(64, 3)).astype(np.float32)
    eng.put("x", x)
    total = eng.map_reduce(lambda blk: jnp.sum(blk, axis=0), "x")
    np.testing.assert_allclose(np.asarray(total), x.sum(0), rtol=1e-5)


def test_kmeans_local_equals_global_path():
    """Identical math on both data paths; only movement differs."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    eng = AnalyticsEngine(mesh, PilotDataRegistry())
    pts = km.make_dataset(2048, 3, n_clusters=5, seed=1)
    eng.put("p", pts)
    c1, cost1 = km.kmeans_fit(eng, "p", 5, iters=2, data_path="local", seed=2)
    moved_before = eng.moved_bytes
    c2, cost2 = km.kmeans_fit(eng, "p", 5, iters=2, data_path="global", seed=2)
    assert cost1 == pytest.approx(cost2, rel=1e-5)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-5)
    assert eng.moved_bytes > moved_before  # the Lustre path paid movement


def test_kmeans_cost_decreases_with_iters():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    eng = AnalyticsEngine(mesh, PilotDataRegistry())
    pts = km.make_dataset(4096, 3, n_clusters=6, seed=0)
    eng.put("p", pts)
    _, cost1 = km.kmeans_fit(eng, "p", 6, iters=1, seed=0)
    _, cost4 = km.kmeans_fit(eng, "p", 6, iters=4, seed=0)
    assert cost4 <= cost1 * 1.001


def test_kmeans_kernel_path_matches_ref_path():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    eng = AnalyticsEngine(mesh, PilotDataRegistry())
    pts = km.make_dataset(1024, 3, n_clusters=4, seed=3)
    eng.put("p", pts)
    _, cost_ref = km.kmeans_fit(eng, "p", 4, iters=2, use_kernel=False, seed=1)
    _, cost_ker = km.kmeans_fit(eng, "p", 4, iters=2, use_kernel=True, seed=1)
    assert cost_ref == pytest.approx(cost_ker, rel=1e-4)
