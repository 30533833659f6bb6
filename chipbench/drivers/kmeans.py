"""K-Means, closed loop: repeated ``kmeans_fit`` calls (a fresh init seed
each fit) on one resident block through the ``AnalyticsEngine`` of an
``analytics`` pilot, inside one analytics stage for the whole window.

Checked: a sample of the window's fits drawn from the seed, each against
the reference on the same points from the same init seed.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from chipbench import checks, data, harness, kmeans_check, sessions

CHECK_FITS = 3
NAME = "block"


def _points(ctx, cj, sharding=None):
    return data.mixture(data.key(ctx.seed, 3), int(cj["points"]),
                        int(cj["dim"]), int(cj["mixture"]), sharding)


def setup(ctx: harness.Context) -> Dict[str, Any]:
    import jax
    from repro.analytics import kmeans as km_lib
    from repro.core import analytics_stage
    cj, traffic = ctx.cell.config, ctx.cell.traffic
    session, pilots = sessions.open_session(ctx.devices, traffic["pilots"])
    st: Dict[str, Any] = {
        "session": session, "km": km_lib, "k": int(cj["clusters"]),
        "iters": int(cj["iterations"]), "fits": [],
        "rng": np.random.default_rng((ctx.seed, 5))}

    def load(engine=None):
        engine.put(NAME, _points(ctx, cj, engine.block_sharding()))
        for _ in range(int(traffic["warm_fits"])):
            _fit(ctx, st, engine, record=False)
        jax.effects_barrier()
        return {}

    st["stage"] = lambda fn: analytics_stage("kmeans", fn)
    session.run([st["stage"](load)], timeout=1200.0)
    return st


def _fit(ctx, st, engine, *, record: bool) -> None:
    import jax
    seed = int(st["rng"].integers(0, 2 ** 31))
    with ctx.rec.spans.span("kmeans.fit"):
        cents, cost = st["km"].kmeans_fit(
            engine, NAME, st["k"], iters=st["iters"], use_kernel=True,
            data_path="local", seed=seed)
        cents = jax.block_until_ready(cents)
    if record:
        st["fits"].append({"seed": seed, "cost": cost, "centroids": cents})


def window(ctx: harness.Context, st: Dict[str, Any], deadline: float) -> None:
    c = ctx.rec.counters
    c["attempted"] = c["failed"] = 0

    def body(engine=None):
        while time.monotonic() < deadline:
            c["attempted"] += 1
            _fit(ctx, st, engine, record=True)
        return {}

    t0 = time.monotonic()
    try:
        st["session"].run([st["stage"](body)], timeout=600.0)
    except Exception as e:
        c["failed"] += 1
        ctx.say(f"K-Means stage failed: {e!r}")
    cj = ctx.cell.config
    fits = len(st["fits"])
    c["units"] = fits
    c["kmeans_iters"] = fits * st["iters"]
    c["kmeans_points"] = int(cj["points"])
    c["kmeans_k"] = st["k"]
    c["kmeans_d"] = int(cj["dim"])
    c["cu_overheads"] = sessions.cu_overheads(st["session"], t0)


def release(ctx: harness.Context, st: Dict[str, Any]) -> None:
    states = sessions.cu_states(st["session"])
    st["cu_not_done"] = sum(n for s, n in states.items() if s != "done")
    for f in st["fits"]:
        f["centroids"] = np.asarray(f["centroids"])
    st["session"].shutdown()
    st.pop("stage")


def check(ctx: harness.Context, st: Dict[str, Any]) -> List[harness.Check]:
    cj = ctx.cell.config
    out = [harness.Check("cu_not_done", float(st["cu_not_done"]), 0.0)]
    fits = st["fits"]
    if not fits:
        return out + [harness.Check("fits_checked", 0.0, -1.0)]
    pick = np.random.default_rng((ctx.seed, 13)).choice(
        len(fits), min(CHECK_FITS, len(fits)), replace=False)
    points = _points(ctx, cj)
    worst: Dict[str, float] = {}
    for i in sorted(pick):
        f = fits[i]
        g = kmeans_check.gaps(points, st["k"], st["iters"], f["seed"],
                              f["centroids"], f["cost"])
        for k, v in g.items():
            worst[k] = max(worst.get(k, 0.0), v)
    lim = checks.limits(cj)
    return out + [harness.Check(k, v, lim[k]) for k, v in worst.items()]


def readings(cell, devices, seed: int, control: bool) -> List[Dict[str, Any]]:
    """Calibration rows (``calibrate.py``): the K-Means gaps on the block."""
    cj = cell.config
    return [dict(part="block", **kmeans_check.readings(
        devices, seed, int(cj["points"]), int(cj["dim"]), int(cj["mixture"]),
        int(cj["clusters"]), int(cj["iterations"]), control))]
