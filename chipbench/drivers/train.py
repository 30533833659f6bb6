"""Training, closed loop: ``Trainer.run`` inside one Compute-Unit on an
``hpc`` pilot for the whole window, a step at a time until the deadline.

Checked: the Trainer's first steps (set-up, the same object and feed the
window drives) against the reference.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

from chipbench import harness, sessions, training


def setup(ctx: harness.Context) -> Dict[str, Any]:
    import jax
    traffic = ctx.cell.traffic
    session, pilots = sessions.open_session(ctx.devices, traffic["pilots"])
    hpc = pilots["hpc"]
    rig = training.TrainRig(ctx.cell.config, traffic, ctx.seed, hpc.mesh())
    rig.first_steps()
    rig.run_to(rig.step + int(traffic["warm_steps"]))
    jax.effects_barrier()
    return {"session": session, "hpc": hpc, "rig": rig}


def window(ctx: harness.Context, st: Dict[str, Any], deadline: float) -> None:
    from repro.core import ComputeUnitDescription
    rig, spans = st["rig"], ctx.rec.spans
    c = ctx.rec.counters
    first = rig.step

    def body(mesh=None):
        while time.monotonic() < deadline:
            with spans.span("train.step"):
                rig.run_to(rig.step + 1)
        return rig.step

    t0 = time.monotonic()
    cu = st["hpc"].submit(ComputeUnitDescription(
        fn=body, gang=True, n_chips=1, tag="train", needs_mesh=True))
    try:
        cu.follow(timeout=600.0)
        c["failed"] = 0
    except Exception as e:
        c["failed"] = 1
        ctx.say(f"training CU failed: {e!r}")
    steps = rig.step - first
    c["attempted"] = steps + c["failed"]
    c["units"] = steps
    c["train_steps"] = steps
    c["train_tokens"] = steps * rig.tokens_per_step
    c["cu_overheads"] = sessions.cu_overheads(st["session"], t0)


def release(ctx: harness.Context, st: Dict[str, Any]) -> None:
    states = sessions.cu_states(st["session"])
    st["cu_not_done"] = sum(n for s, n in states.items() if s != "done")
    st["session"].shutdown()
    st["rig"].release()


def check(ctx: harness.Context, st: Dict[str, Any]) -> List[harness.Check]:
    out = training.reference_checks(st["rig"], ctx.say)
    out.append(harness.Check("cu_not_done", float(st["cu_not_done"]), 0.0))
    return out


def readings(cell, devices, seed: int, control: bool) -> List[Dict[str, Any]]:
    """Calibration rows (``calibrate.py``): the training gaps."""
    from repro import compat
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    return [dict(part="train", **training.readings(
        cell.config, cell.traffic, seed, mesh, control))]
