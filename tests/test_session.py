"""Session + DataPlane behaviour: cross-pilot stage placement driven by
the locality-vs-movement cost model (the paper's central question as a
runtime decision), the moved-bytes ledger, lineage, and the scheduler's
non-contiguous locality placement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import (ComputeUnitDescription, PilotDescription,
                        ResourceManager, Session, TransferCostModel,
                        analytics_stage, hpc_stage)
from repro.core.compute_unit import ComputeUnit
from repro.core.dataplane import DataPlane, Link
from repro.core.scheduler import YarnStyleScheduler


def make_session(dcn_cost_per_byte: float) -> Session:
    # two pilots over aliased device slots (dry-run multi-allocation)
    rm = ResourceManager(devices=jax.devices() * 2)
    s = Session(rm, cost_model=TransferCostModel(
        dcn_cost_per_byte=dcn_cost_per_byte))
    s.add_pilot(PilotDescription(n_chips=1, name="hpc", runtime="hpc"))
    s.add_pilot(PilotDescription(n_chips=1, name="ana", runtime="analytics"))
    return s


def make_dag():
    def simulate(mesh=None):
        rng = np.random.default_rng(0)
        return {"traj": rng.normal(size=(64, 4)).astype(np.float32)}

    def analyze(engine=None, traj=None):
        from repro.analytics import kmeans as km
        centroids, cost = km.kmeans_fit(engine, "traj", 4, iters=2)
        return {"centroids": centroids, "cost": cost}

    def train(centroids=None, results=None, mesh=None):
        assert np.isfinite(results["analyze"]["cost"])
        return float(np.sum(np.asarray(centroids)))

    return [
        hpc_stage("simulate", simulate, outputs=("traj",)),
        analytics_stage("analyze", analyze, inputs=("traj",),
                        outputs=("centroids",)),
        hpc_stage("train", train, inputs=("centroids",),
                  after=("analyze",)),
    ]


# -------------------------------------------------------- acceptance tests
def test_session_dag_executes_across_pilots():
    """simulate -> analyze -> train runs to completion over >= 2 pilots,
    every stage has a recorded placement decision, and data deps flowed
    through the shared DataPlane."""
    s = make_session(dcn_cost_per_byte=0.0)
    try:
        results = s.run(make_dag())
        assert set(results) == {"simulate", "analyze", "train"}
        assert np.isfinite(results["train"])
        assert len(s.pilots) == 2
        assert set(s.placements) == {"simulate", "analyze", "train"}
        # HPC stages must land on the HPC-runtime pilot
        assert s.placements["simulate"]["pilot"] == "hpc"
        assert s.placements["train"]["pilot"] == "hpc"
        assert "traj" in s.dataplane and "centroids" in s.dataplane
    finally:
        s.shutdown()


def test_high_movement_cost_runs_where_data_lives():
    """Expensive DCN: the analytics stage goes to the data (Mode-I carve
    inside the HPC pilot); zero inter-pilot bytes move."""
    s = make_session(dcn_cost_per_byte=1.0)
    try:
        s.run(make_dag())
        place = s.placements["analyze"]
        assert place["pilot"] == "hpc"
        assert place["mode"] == "mode1-carve"
        assert s.dataplane.moved_by_link(Link.DCN) == 0
    finally:
        s.shutdown()


def test_zero_movement_cost_consolidates():
    """Free DCN: the data goes to the compute — the analytics stage
    consolidates onto its native pilot and the move is on the ledger."""
    s = make_session(dcn_cost_per_byte=0.0)
    try:
        s.run(make_dag())
        place = s.placements["analyze"]
        assert place["pilot"] == "ana"
        assert place["mode"] == "native"
        assert s.dataplane.moved_by_link(Link.DCN) > 0
        assert place["dcn_bytes_moved"] > 0
    finally:
        s.shutdown()


# ------------------------------------------------------------ data plane
def test_record_moved_public_ledger():
    dp = DataPlane()
    dp.record_moved(100, Link.DCN, "x")
    dp.record_moved(50, Link.GFS, "y")
    dp.record_moved(25, Link.ICI)
    assert dp.moved_bytes == 175
    assert dp.moved_by_link(Link.DCN) == 100
    ledger = dp.ledger()
    assert ledger["by_reason"]["x"] == 100
    with pytest.raises(ValueError):
        dp.record_moved(1, "carrier-pigeon")


def test_global_reshard_routes_through_ledger():
    """The GFS spool path (Lustre analogue) accounts both the persist
    and the re-read through record_moved — no private counter pokes."""
    from repro.analytics.engine import AnalyticsEngine
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    eng = AnalyticsEngine(mesh, DataPlane())
    eng.put("d", np.ones((32, 4), np.float32))
    nbytes = eng.get("d").nbytes
    eng.global_reshard("d")
    assert eng.data.moved_by_link(Link.GFS) == 2 * nbytes
    assert eng.data.ledger()["by_reason"]["gfs-spool-write"] == nbytes


def test_replica_tracking_and_lineage():
    dp = DataPlane()
    arr = jnp.ones((8,))
    from repro.core.dataplane import Lineage
    dp.put("a", arr, pilot="p0", lineage=Lineage("prod", ("x",)))
    assert dp.home_pilots("a") == {"p0"}
    assert dp.resident_on("a", "p0") is True
    assert dp.resident_on("a", "p1") is False
    assert dp.pilot_locality(["a"], "p0") == 1.0
    assert dp.bytes_nonresident(["a"], "p1") == arr.nbytes
    dp.add_replica("a", "p1")
    assert dp.bytes_nonresident(["a"], "p1") == 0
    lost = dp.drop_pilot_replicas("p0")
    assert lost == []                      # p1 still holds a replica
    lost = dp.drop_pilot_replicas("p1")
    assert lost == ["a"]                   # gone — rematerialization needed
    assert dp.lineage_of("a").stage == "prod"


def test_session_rematerializes_lost_output():
    """Lineage recovery: dropping every replica of a stage output lets
    the Session re-run its producer to get it back."""
    s = make_session(dcn_cost_per_byte=1.0)
    try:
        s.run(make_dag())
        traj_before = np.asarray(s.dataplane.get("traj").array)
        hpc_uid = s.pilots["hpc"].uid
        lost = s.dataplane.drop_pilot_replicas(hpc_uid)
        assert "traj" in lost
        s.rematerialize("traj")
        assert s.dataplane.home_pilots("traj")
        np.testing.assert_allclose(
            np.asarray(s.dataplane.get("traj").array), traj_before)
    finally:
        s.shutdown()


def test_multi_pilot_trainer_reports_wire_bytes_to_dataplane():
    """The trainer is a Session client: gradient-exchange traffic lands
    on the shared DCN ledger."""
    from repro import configs
    from repro.train.multi_pilot import MultiPilotTrainer

    rm = ResourceManager(devices=jax.devices() * 2)
    s = Session(rm)
    s.add_pilot(PilotDescription(n_chips=1, name="pod-a", runtime="hpc"))
    s.add_pilot(PilotDescription(n_chips=1, name="pod-b", runtime="hpc"))
    try:
        cfg = configs.get_smoke("llama3.2-1b")
        tr = MultiPilotTrainer(cfg, global_batch=4, seq=16, session=s, seed=0)
        assert tr.pilots == s.pilots_by_runtime("hpc")
        tr.run(2, log_every=0)
        assert tr.wire_bytes > 0
        assert s.dataplane.moved_by_link(Link.DCN) == tr.wire_bytes
        assert s.dataplane.ledger()["by_reason"]["grad-exchange"] \
            == tr.wire_bytes
    finally:
        s.shutdown()


def test_dag_cycle_detection():
    s = make_session(0.0)
    try:
        dag = [hpc_stage("a", lambda mesh=None: None, inputs=("y",),
                         outputs=("x",)),
               hpc_stage("b", lambda mesh=None: None, inputs=("x",),
                         outputs=("y",))]
        with pytest.raises(ValueError, match="cycle"):
            s.run(dag)
    finally:
        s.shutdown()


# ------------------------------------------------- scheduler locality fix
class FakeDevice:
    def __init__(self, i):
        self.i = i
        self.platform = "fake"


class FakeData:
    """Registry entry pinned to an explicit device subset."""

    def __init__(self, devices, nbytes=1024):
        self._devices = set(devices)
        self.nbytes = nbytes

    def device_set(self):
        return set(self._devices)

    def locality(self, devices):
        return len(self._devices & set(devices)) / len(self._devices)


def test_scheduler_finds_noncontiguous_local_placement():
    """Data on devices {0, 2}: a 2-chip CU must get exactly those chips
    (a locality hit), not a contiguous window scoring 0.5."""
    devs = [FakeDevice(i) for i in range(4)]
    dp = DataPlane()
    dp._data["ds"] = FakeData({devs[0], devs[2]})
    sched = YarnStyleScheduler(devs, 16, dp, locality_delay_rounds=3)
    cu = ComputeUnit(ComputeUnitDescription(
        fn=lambda: None, n_chips=2, data=("ds",)))
    sched.submit(cu)
    bound = sched.try_schedule()
    assert len(bound) == 1
    _, idxs = bound[0]
    assert sorted(idxs) == [0, 2]
    assert sched.stats["locality_hits"] == 1
    assert sched.stats["locality_misses"] == 0


def test_scheduler_skip_counts_cleaned_up():
    """Delay-scheduling state must not grow unbounded: once a CU binds,
    its skip counter is dropped."""
    devs = [FakeDevice(i) for i in range(2)]
    dp = DataPlane()
    dp._data["ds"] = FakeData({FakeDevice(99)})   # data is nowhere local
    sched = YarnStyleScheduler(devs, 16, dp, locality_delay_rounds=2)
    cu = ComputeUnit(ComputeUnitDescription(
        fn=lambda: None, n_chips=1, data=("ds",)))
    sched.submit(cu)
    bound = []
    for _ in range(5):                      # 2 delay rounds, then bind
        bound += sched.try_schedule()
    assert len(bound) == 1
    assert sched.stats["locality_misses"] == 1
    assert cu.uid not in sched._skip_counts
