"""Coupled rounds: simulate -> analyze -> steer as one ``Session.run`` DAG
over two pilots, ``hpc`` and ``ana``, closed loop (the next round starts
when the last one ends).

* simulate (hpc): ``steps_per_round`` steps of the kept ``Trainer``, then
  a frame of points made on the device from (seed, round), published to
  the DataPlane;
* analyze (ana): ``kmeans_fit`` on that frame through the pilot's
  ``AnalyticsEngine`` (compiled kernel, local data path);
* steer (hpc): the next round's K-Means seed from the cost.

Checked: the first training steps against the reference; for a sample
of the window's rounds drawn from the seed, the K-Means answer against
the reference on the frame the round should have read, the frame's
sampled rows as analyze read them, and the steered seed; every CU DONE.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from chipbench import checks, data, harness, sessions, training

SAMPLE_ROWS = 64
CHECK_ROUNDS = 3


def steer(cost: float, rnd: int) -> int:
    """The next round's K-Means seed, from the cost the round reported."""
    return (int(abs(cost) * 1000.0) * 1_000_003 + rnd) % (2 ** 31)


def setup(ctx: harness.Context) -> Dict[str, Any]:
    import jax
    cell, traffic = ctx.cell, ctx.cell.traffic
    km = traffic["kmeans"]
    fr = traffic["frame"]
    session, pilots = sessions.open_session(ctx.devices, traffic["pilots"])
    hpc, ana = pilots["hpc"], pilots["ana"]
    rig = training.TrainRig(cell.config, traffic, ctx.seed, hpc.mesh())
    rig.first_steps()
    st: Dict[str, Any] = {
        "session": session, "hpc": hpc, "ana": ana, "rig": rig,
        "k": int(km["clusters"]), "iters": int(km["iterations"]),
        "n": int(fr["points"]), "d": int(fr["dim"]),
        "mixture": int(fr["mixture"]),
        "steps": int(traffic["train"]["steps_per_round"]),
        "next_seed": steer(float(ctx.seed % 1000), -1),
        "rounds": [], "round": 0}
    rows = np.random.default_rng((ctx.seed, 7)).choice(
        st["n"], SAMPLE_ROWS, replace=False)
    st["rows"] = np.sort(rows)
    from repro.analytics import kmeans as km_lib
    st["km_lib"] = km_lib
    st["stages"] = _stages(ctx, st)
    for _ in range(int(traffic["warm_rounds"])):
        _round(ctx, st, record=False)
    jax.effects_barrier()
    return st


def _stages(ctx, st):
    import jax
    from repro.core import analytics_stage, hpc_stage
    spans = ctx.rec.spans
    km_lib = st["km_lib"]

    def simulate(mesh=None):
        with spans.span("stage:simulate"):
            rig = st["rig"]
            with spans.span("train.step"):
                rig.run_to(rig.step + st["steps"])
            frame = data.mixture(data.key(ctx.seed, 2, st["round"]), st["n"],
                                 st["d"], st["mixture"])
            frame.block_until_ready()
        return {"frame": frame, "loss": rig.tr.history[-1]["loss"]}

    def analyze(engine=None, frame=None):
        with spans.span("stage:analyze"):
            seed = st["next_seed"]
            engine.put("frame_points", frame)
            with spans.span("kmeans.fit"):
                cents, cost = km_lib.kmeans_fit(
                    engine, "frame_points", st["k"], iters=st["iters"],
                    use_kernel=True, data_path="local", seed=seed)
            rows = np.asarray(engine.get("frame_points")[st["rows"]])
            cents = np.asarray(jax.block_until_ready(cents))
        return {"centroids": cents, "cost": cost, "seed": seed, "rows": rows}

    def steer_stage(results=None):
        with spans.span("stage:steer"):
            cost = results["analyze"]["cost"]
            st["next_seed"] = steer(cost, st["round"])
        return {"next_seed": st["next_seed"]}

    return [hpc_stage("simulate", simulate, outputs=("frame",)),
            analytics_stage("analyze", analyze, inputs=("frame",)),
            hpc_stage("steer", steer_stage, after=("analyze",))]


def _round(ctx, st, *, record: bool) -> None:
    with ctx.rec.spans.span("round"):
        out = st["session"].run(st["stages"], timeout=600.0)
    if record:
        an = out["analyze"]
        st["rounds"].append({
            "round": st["round"], "seed": an["seed"], "cost": an["cost"],
            "centroids": an["centroids"], "rows": an["rows"],
            "next_seed": out["steer"]["next_seed"]})
    st["round"] += 1


def window(ctx: harness.Context, st: Dict[str, Any], deadline: float) -> None:
    c = ctx.rec.counters
    c["attempted"] = c["failed"] = 0
    first = st["round"]
    t0 = time.monotonic()
    while time.monotonic() < deadline:
        c["attempted"] += 1
        try:
            _round(ctx, st, record=True)
        except Exception as e:      # a failed round counts, and ends the run
            c["failed"] += 1
            ctx.say(f"round {st['round']} failed: {e!r}")
            break
    rounds = st["round"] - first
    c["units"] = rounds
    c["train_tokens"] = rounds * st["steps"] * st["rig"].tokens_per_step
    c["train_steps"] = rounds * st["steps"]
    c["kmeans_iters"] = rounds * st["iters"]
    c["kmeans_points"] = st["n"]
    c["kmeans_k"] = st["k"]
    c["kmeans_d"] = st["d"]
    c["cu_overheads"] = sessions.cu_overheads(st["session"], t0)


def release(ctx: harness.Context, st: Dict[str, Any]) -> None:
    states = sessions.cu_states(st["session"])
    st["cu_not_done"] = sum(n for s, n in states.items() if s != "done")
    st["session"].shutdown()
    st["rig"].release()
    st.pop("stages")


def check(ctx: harness.Context, st: Dict[str, Any]) -> List[harness.Check]:
    from chipbench import kmeans_check
    out = training.reference_checks(st["rig"], ctx.say)
    out.append(harness.Check("cu_not_done", float(st["cu_not_done"]), 0.0))
    done = st["rounds"]
    if not done:
        return out + [harness.Check("rounds_checked", 0.0, -1.0)]
    pick = np.random.default_rng((ctx.seed, 11)).choice(
        len(done), min(CHECK_ROUNDS, len(done)), replace=False)
    km_cfg = kmeans_check.config(ctx.cell.root, ctx.cell.traffic)
    worst: Dict[str, float] = {}
    rows_bad = steer_bad = 0
    for i in sorted(pick):
        r = done[i]
        frame = data.mixture(data.key(ctx.seed, 2, r["round"]), st["n"],
                             st["d"], st["mixture"])
        rows_bad += int(np.sum(np.asarray(frame[st["rows"]]) != r["rows"]))
        want = steer(r["cost"], r["round"])
        steer_bad += int(want != r["next_seed"])
        if i > 0:
            steer_bad += int(done[i - 1]["next_seed"] != r["seed"])
        gaps = kmeans_check.gaps(frame, st["k"], st["iters"], r["seed"],
                                 r["centroids"], r["cost"])
        for k, v in gaps.items():
            worst[k] = max(worst.get(k, 0.0), v)
        del frame
    klim = checks.limits(km_cfg)
    out += [harness.Check(k, v, klim[k]) for k, v in worst.items()]
    out.append(harness.Check("frame_rows_mismatch", float(rows_bad), 0.0))
    out.append(harness.Check("steer_mismatch", float(steer_bad), 0.0))
    return out


def readings(cell, devices, seed: int, control: bool) -> List[Dict[str, Any]]:
    """Calibration rows (``calibrate.py``): the training gaps, then the
    K-Means gaps on a frame."""
    from chipbench import kmeans_check
    from repro import compat
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    rows = [dict(part="train", **training.readings(
        cell.config, cell.traffic, seed, mesh, control))]
    fr, km = cell.traffic["frame"], cell.traffic["kmeans"]
    rows.append(dict(part="frame", **kmeans_check.readings(
        devices, seed, int(fr["points"]), int(fr["dim"]), int(fr["mixture"]),
        int(km["clusters"]), int(km["iterations"]), control)))
    return rows
