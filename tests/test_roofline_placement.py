"""Roofline-aware placement: StageCost estimates steer the Session
placer toward the pilot whose advertised roofline runs the stage
fastest, and the estimate-vs-actual error is exported via heartbeats."""
import jax
import numpy as np
import pytest

from repro.core import (PilotDescription, ResourceManager, Session,
                        StageCost, TransferCostModel, hpc_stage)
from repro.roofline.placement import est_runtime, estimate_error

BIGFLOPS = {"peak_flops_per_chip": 100e12, "hbm_bw_per_chip": 100e9}
BIGMEM = {"peak_flops_per_chip": 10e12, "hbm_bw_per_chip": 1000e9}


def make_session(**kw) -> Session:
    rm = ResourceManager(devices=jax.devices() * 2)
    s = Session(rm, cost_model=TransferCostModel(dcn_cost_per_byte=0.0),
                **kw)
    s.add_pilot(PilotDescription(n_chips=1, name="bigflops", runtime="hpc",
                                 **BIGFLOPS))
    s.add_pilot(PilotDescription(n_chips=1, name="bigmem", runtime="hpc",
                                 **BIGMEM))
    return s


def _noop(**kw):
    return {}


# ------------------------------------------------------------- est math
def test_est_runtime_bound_selection():
    compute = est_runtime(StageCost(flops=1e15, hbm_bytes=1.0),
                          n_chips=1, **{"peak_flops": 1e12, "hbm_bw": 1e9})
    assert compute["bound"] == "compute"
    assert compute["est_s"] == pytest.approx(1e3)
    memory = est_runtime(StageCost(flops=1.0, hbm_bytes=1e12),
                         n_chips=1, peak_flops=1e12, hbm_bw=1e9)
    assert memory["bound"] == "memory"
    assert memory["est_s"] == pytest.approx(1e3)
    # chips divide both terms
    half = est_runtime(StageCost(flops=1e15, hbm_bytes=1.0),
                       n_chips=2, peak_flops=1e12, hbm_bw=1e9)
    assert half["est_s"] == pytest.approx(500.0)


def test_stage_cost_validates():
    with pytest.raises(ValueError):
        StageCost(flops=-1.0)
    assert StageCost(flops=100.0, hbm_bytes=10.0).intensity == \
        pytest.approx(10.0)


def test_estimate_error_ratio():
    assert estimate_error(2.0, 4.0) == pytest.approx(2.0)
    assert estimate_error(0.0, 4.0) is None


def test_stage_cost_from_model_smoke():
    from repro import configs
    from repro.models.config import SHAPES
    cfg = configs.get("llama3.2-1b")
    shape = next(s for s in SHAPES.values() if s.kind == "train")
    cost = StageCost.from_model(cfg, shape, n_devices=256)
    assert cost.flops > 0 and cost.hbm_bytes > 0


# -------------------------------------------------------- placer routing
def test_compute_bound_prefers_high_flops_pilot():
    s = make_session()
    try:
        s.run([hpc_stage("c", _noop,
                         cost=StageCost(flops=1000e12, hbm_bytes=10e9))])
        assert s.placements["c"]["pilot"] == "bigflops"
        chosen = s.placements["c"]["chosen"]
        assert chosen["bound"] == "compute"
        assert chosen["est_runtime"] > 0
    finally:
        s.shutdown()


def test_memory_bound_prefers_high_bw_pilot():
    s = make_session()
    try:
        s.run([hpc_stage("m", _noop,
                         cost=StageCost(flops=10e12, hbm_bytes=2000e9))])
        assert s.placements["m"]["pilot"] == "bigmem"
        assert s.placements["m"]["chosen"]["bound"] == "memory"
    finally:
        s.shutdown()


def test_roofline_off_ignores_cost():
    """With roofline_placement=False both profiles tie on bytes and land
    on the same (first) pilot — the pre-PR behavior."""
    s = make_session(roofline_placement=False)
    try:
        s.run([
            hpc_stage("c", _noop,
                      cost=StageCost(flops=1000e12, hbm_bytes=10e9)),
            hpc_stage("m", _noop,
                      cost=StageCost(flops=10e12, hbm_bytes=1000e9)),
        ])
        assert s.placements["c"]["pilot"] == s.placements["m"]["pilot"]
        assert "est_runtime" not in s.placements["c"]["chosen"]
    finally:
        s.shutdown()


def test_stage_without_cost_unaffected():
    s = make_session()
    try:
        s.run([hpc_stage("plain", _noop)])
        assert "est_runtime" not in s.placements["plain"]["chosen"]
    finally:
        s.shutdown()


# ----------------------------------------------- estimate cross-checking
def test_estimate_error_recorded_and_exported():
    s = make_session()
    try:
        s.run([hpc_stage("c", _noop,
                         cost=StageCost(flops=1000e12, hbm_bytes=10e9))])
        place = s.placements["c"]
        assert place["est_runtime_s"] > 0
        assert place["actual_runtime_s"] >= 0
        assert place["est_error_ratio"] > 0

        # the error rides the chosen pilot's heartbeat...
        pilot = s.pilots[place["pilot"]]
        hb = pilot.agent.heartbeat()
        assert hb["roofline"]["n"] == 1
        assert hb["roofline"]["ema_error_ratio"] == \
            pytest.approx(place["est_error_ratio"])
        assert hb["roofline"]["last"]["tag"] == "stage:c"

        # ...and surfaces as est_drift in ControlPlane polls
        snap = next(v for v in s.control_plane.poll().values()
                    if v["name"] == place["pilot"])
        assert snap["est_drift"] is not None and snap["est_drift"] >= 0
    finally:
        s.shutdown()


def test_calibration_opt_in():
    """calibrate_estimates applies the pilot's EMA actual/est ratio to
    later estimates; off by default."""
    s = make_session(calibrate_estimates=True)
    try:
        cost = StageCost(flops=1000e12, hbm_bytes=10e9)
        s.run([hpc_stage("first", _noop, cost=cost)])
        s.run([hpc_stage("second", _noop, cost=cost)])
        chosen = s.placements["second"]["chosen"]
        assert "calibration_ratio" in chosen
        assert chosen["calibration_ratio"] > 0
    finally:
        s.shutdown()


def test_pilot_description_advertises_roofline_defaults():
    """Unset peaks come from the granted chips' device table entry; the
    CPU backend models a TPU v5e."""
    s = Session(ResourceManager(devices=jax.devices()))
    try:
        d = s.add_pilot(PilotDescription(n_chips=1, name="p")).desc
        assert d.peak_flops_per_chip == pytest.approx(197e12)   # TPU v5e
        assert d.hbm_bw_per_chip == pytest.approx(819e9)
    finally:
        s.shutdown()
