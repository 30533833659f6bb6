"""The program's host spans (``repro.spans``): recorded by the profiler
from a Session DAG on two CPU pilots, a smoke-width Trainer and K-Means,
with the arguments that link one DAG's spans across threads."""
import ast
import glob
import os
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro import compat, configs, spans
from repro.core import (PilotDescription, ResourceManager, Session,
                        TransferCostModel, analytics_stage, hpc_stage)
from repro.train.trainer import Trainer

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")

# the arguments each span carries (spans.py's table)
ARGS = {
    "session.run": {"dag"},
    "session.dag": {"dag", "stages"},
    "session.stage": {"dag", "stage"},
    "session.deps": {"dag", "stage"},
    "session.place": {"stage"},
    "session.inputs": {"stage", "bytes"},
    "session.wait": {"stage", "cu"},
    "session.store": {"stage", "bytes"},
    "agent.schedule": {"bound"},
    "cu.spawn": {"cu", "tag"},
    "cu.body": {"cu", "tag"},
    "cu.finish": {"cu"},
    "trainer.run": {"steps"},
    "trainer.resume": set(),
    "trainer.batch": {"step"},
    "trainer.dispatch": {"step"},
    "trainer.sync": {"step"},
    "trainer.stop": set(),
    "engine.put": {"bytes"},
    "engine.map_reduce": set(),
    "kmeans.init": set(),
    "kmeans.update": set(),
    "kmeans.cost": set(),
}
TRAIN_STEPS = 3


def _dag():
    def simulate(mesh=None):
        rng = np.random.default_rng(0)
        return {"traj": rng.normal(size=(64, 4)).astype(np.float32)}

    def analyze(engine=None, traj=None):
        from repro.analytics import kmeans as km
        engine.put("points", traj)
        centroids, cost = km.kmeans_fit(engine, "points", 4, iters=2)
        return {"cost": cost}

    return [hpc_stage("simulate", simulate, outputs=("traj",)),
            analytics_stage("analyze", analyze, inputs=("traj",))]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Spans by name: (args, start_ns, end_ns), in order of start."""
    d = str(tmp_path_factory.mktemp("trace"))
    session = Session(ResourceManager(devices=jax.devices() * 2),
                      cost_model=TransferCostModel(dcn_cost_per_byte=0.0))
    session.add_pilot(PilotDescription(n_chips=1, name="hpc",
                                       runtime="hpc"))
    session.add_pilot(PilotDescription(n_chips=1, name="ana",
                                       runtime="analytics"))
    tr = Trainer(configs.get_smoke("llama3.2-1b"),
                 compat.make_mesh((1, 1), ("data", "model")),
                 global_batch=2, seq=16, seed=0)
    jax.profiler.start_trace(d)
    try:
        session.run(_dag(), timeout=120.0)
        tr.run(TRAIN_STEPS, log_every=0)
    finally:
        jax.profiler.stop_trace()
        placements = dict(session.placements)
        session.shutdown()
    path = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    by_name = defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in spans.NAMES:
                    by_name[e.name].append(
                        (dict(e.stats), e.start_ns, e.end_ns))
    for evs in by_name.values():
        evs.sort(key=lambda ev: ev[1])
    return by_name, placements


def test_every_span_is_recorded_with_its_args(recorded):
    by_name, _ = recorded
    assert set(ARGS) == set(spans.NAMES)
    assert sorted(by_name) == sorted(spans.NAMES)
    for name, evs in by_name.items():
        for args, _, _ in evs:
            assert set(args) == ARGS[name], name


def test_one_dag_shares_its_id(recorded):
    by_name, _ = recorded
    (run,), (dag,) = by_name["session.run"], by_name["session.dag"]
    assert run[0]["dag"] == dag[0]["dag"] >= 1
    assert dag[0]["stages"] == 2
    for name in ("session.stage", "session.deps"):
        assert sorted(a["stage"] for a, _, _ in by_name[name]) == \
            ["analyze", "simulate"]
        assert {a["dag"] for a, _, _ in by_name[name]} == {run[0]["dag"]}
    # the DAG is submitted before the Session waits on it
    assert dag[2] <= run[1]


def test_session_wait_and_cu_body_share_the_cu(recorded):
    by_name, placements = recorded
    assert placements["analyze"]["pilot"] == "ana"
    bodies = {a["cu"]: (a["tag"], t0, t1) for a, t0, t1 in by_name["cu.body"]}
    waits = by_name["session.wait"]
    assert sorted(a["stage"] for a, _, _ in waits) == ["analyze", "simulate"]
    for args, w0, w1 in waits:
        tag, b0, b1 = bodies[args["cu"]]     # another thread's span
        assert tag == f"stage:{args['stage']}"
        assert w0 <= b0 <= b1 <= w1
    spawned = {a["cu"] for a, _, _ in by_name["cu.spawn"]}
    finished = {a["cu"] for a, _, _ in by_name["cu.finish"]}
    assert set(bodies) <= spawned and set(bodies) <= finished
    assert sum(a["bound"] for a, _, _ in by_name["agent.schedule"]) >= 2


def test_bytes_are_the_moved_and_published(recorded):
    by_name, placements = recorded
    store = {a["stage"]: a["bytes"] for a, _, _ in by_name["session.store"]}
    assert store == {"simulate": 64 * 4 * 4, "analyze": 0}
    (inputs,) = [a for a, _, _ in by_name["session.inputs"]
                 if a["stage"] == "analyze"]
    assert inputs["bytes"] == placements["analyze"]["dcn_bytes_moved"]
    (put,) = by_name["engine.put"]
    assert put[0]["bytes"] == 64 * 4 * 4


def test_trainer_steps_number_in_order(recorded):
    by_name, _ = recorded
    (run,) = by_name["trainer.run"]
    assert run[0]["steps"] == TRAIN_STEPS
    for name in ("trainer.batch", "trainer.dispatch", "trainer.sync"):
        assert [a["step"] for a, _, _ in by_name[name]] == \
            list(range(TRAIN_STEPS))
    for i in range(TRAIN_STEPS):
        b, d, s = (by_name[n][i] for n in ("trainer.batch",
                                           "trainer.dispatch",
                                           "trainer.sync"))
        assert b[2] <= d[1] and d[2] <= s[1]
    assert len(by_name["trainer.resume"]) == len(by_name["trainer.stop"]) == 1
    assert len(by_name["kmeans.update"]) == 2       # one per iteration
    assert len(by_name["engine.map_reduce"]) == 2


def _span_literals():
    """(file, name) of every ``span("<name>", ...)`` call under src/repro."""
    out = []
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        tree = ast.parse(open(path).read(), path)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                    and node.func.id == "span"):
                arg = node.args[0]
                assert isinstance(arg, ast.Constant), (path, node.lineno)
                out.append((os.path.relpath(path, SRC), arg.value))
    return out


def test_every_span_literal_is_listed():
    found = _span_literals()
    assert {n for _, n in found} == set(spans.NAMES)
    assert len(set(spans.NAMES)) == len(spans.NAMES)
    from chipbench import harness
    assert not set(spans.NAMES) & set(harness.SPAN_NAMES)
    assert not any(n.startswith("stage:") for n in spans.NAMES)
