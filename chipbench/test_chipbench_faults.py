"""``correct`` comes out false when the timed path is broken underneath:
a run at smoke size on the CPU (the harness's look for a chip skipped),
once for each fault a cell can have.  The exchange between chips is
absent from these one-chip cells."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import checks, harness
from chipbench.reference import dense_gqa
from chipbench.test_chipbench_harness import SMOKE_MODEL, smoke_root  # noqa: F401


def _run(root, cell, seed=21):
    code, res = harness.run(cell, seed, 0.5, False, root=root,
                            require_tpu=False)
    assert code == 0
    return res


def _failed(res):
    return sorted(n for n, c in res["checks"].items()
                  if not c["value"] <= c["limit"])


@pytest.mark.parametrize("cell", ["smoke.train", "smoke.coupled"])
def test_step_returning_state_unchanged(smoke_root, cell, monkeypatch):
    import repro.train.trainer as trainer_mod
    real = trainer_mod.make_train_step

    def frozen(*a, **kw):
        step = real(*a, **kw)
        return lambda state, batch: (state, step(state, batch)[1])
    monkeypatch.setattr(trainer_mod, "make_train_step", frozen)
    res = _run(smoke_root, cell)
    assert not res["correct"] and "delta_gap" in _failed(res)


@pytest.mark.parametrize("cell", ["smoke.train", "smoke.coupled"])
def test_half_batch_left_out(smoke_root, cell, monkeypatch):
    import repro.train.trainer as trainer_mod
    real = trainer_mod.make_train_step

    def half(*a, **kw):
        step = real(*a, **kw)
        return lambda state, batch: step(
            state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    monkeypatch.setattr(trainer_mod, "make_train_step", half)
    res = _run(smoke_root, cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", ["smoke.kmeans", "smoke.coupled"])
def test_kmeans_answer_altered(smoke_root, cell, monkeypatch):
    from repro.analytics import kmeans as km
    real = km.kmeans_fit

    def altered(*a, **kw):
        c, cost = real(*a, **kw)
        return c.at[0, 0].add(0.05), cost
    monkeypatch.setattr(km, "kmeans_fit", altered)
    res = _run(smoke_root, cell)
    assert not res["correct"] and "centroid_gap" in _failed(res)


@pytest.mark.parametrize("cell", ["smoke.kmeans", "smoke.coupled"])
def test_half_the_points_left_out(smoke_root, cell, monkeypatch):
    from repro.analytics import kmeans as km
    real = km.assign_partials

    def half(points, centroids, **kw):
        return real(points[: points.shape[0] // 2], centroids, **kw)
    monkeypatch.setattr(km, "assign_partials", half)
    res = _run(smoke_root, cell)
    assert not res["correct"] and "cost_gap" in _failed(res)


def test_frame_altered_in_the_dataplane(smoke_root, monkeypatch):
    from repro.core.dataplane import DataPlane
    real = DataPlane.move_to_pilot

    def altered(self, name, *a, **kw):
        arr, n = real(self, name, *a, **kw)
        bad = arr.at[:, 0].add(1.0)
        self.put(name, bad)
        return bad, n
    monkeypatch.setattr(DataPlane, "move_to_pilot", altered)
    res = _run(smoke_root, "smoke.coupled")
    assert not res["correct"] and "frame_rows_mismatch" in _failed(res)


def test_fp8_control_fails_the_configured_limits():
    """The control, the reference with fp8 matmul operands in the
    program's place, reads above the internlm2 configuration's limits at
    smoke size (at full width it does not; see PERF.md)."""
    import os
    from chipbench import data
    limits = checks.limits(harness.load_json(os.path.join(
        harness.ROOT, "chipbench", "configs", "internlm2-1.8b.json")))
    cj = dict(SMOKE_MODEL, torch_dtype="bfloat16")
    opt = harness.load_json(os.path.join(
        harness.ROOT, "chipbench", "traffic", "train.json"))["optimizer"]
    k = data.key(77, 1)
    batches = [data.tokens_at(77, s, 2, 64, cj["vocab_size"])
               for s in range(3)]
    ref = dense_gqa.train_steps(cj, k, batches, opt)
    ctl = dense_gqa.train_steps(cj, k, batches, opt, quant="fp8")
    gaps = checks.train_gaps(ctl, ref)
    assert any(gaps[n] > limits[n] for n in limits), (gaps, limits)
    assert np.isfinite(list(gaps.values())).all()


def test_bf16_control_fails_the_kmeans_limits():
    """The control, the K-Means reference with its distances' cross term
    in bfloat16, reads above the K-Means configuration's limits."""
    import os
    from chipbench import data
    from chipbench.reference import kmeans as ref
    cj = harness.load_json(os.path.join(
        harness.ROOT, "chipbench", "configs", "kmeans-fig6-block.json"))
    limits = checks.limits(cj)
    k, iters = int(cj["clusters"]), int(cj["iterations"])
    pts = data.mixture(data.key(2 ** 33 + 5, 3), 1 << 16, int(cj["dim"]),
                       int(cj["mixture"]))
    rc, rcost = ref.fit(pts, k, iters, 123)
    cc, ccost = ref.fit(pts, k, iters, 123, bf16=True)
    gaps = checks.kmeans_gaps(cc, ccost, rc, rcost)
    assert any(gaps[n] > limits[n] for n in limits), (gaps, limits)
