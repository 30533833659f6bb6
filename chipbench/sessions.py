"""Pilots for a cell: a ``Session`` whose pilots lease the cell's chips,
and what the benchmark reads back from their agents."""
from __future__ import annotations

from typing import Any, Dict, List, Tuple


def open_session(devices: List[Any], pilots: Dict[str, Dict[str, Any]]
                 ) -> Tuple[Any, Dict[str, Any]]:
    """A Session with one pilot per entry of the traffic's ``pilots``
    (``{"name": {"runtime": "hpc"|"analytics", "chips": n}}``).  Pilots
    that ask for more chips than the cell has in all share them: on one
    chip every pilot's lease slot is that chip."""
    from repro.core import PilotDescription, ResourceManager, Session
    want = sum(int(p["chips"]) for p in pilots.values())
    pool = list(devices) * max(1, -(-want // len(devices)))
    session = Session(ResourceManager(devices=pool[:max(want, 1)]))
    out = {}
    for name, p in pilots.items():
        # no speculative copies: a second full-width training CU would
        # not fit the chip beside the first
        out[name] = session.add_pilot(PilotDescription(
            n_chips=int(p["chips"]), name=name, runtime=p["runtime"],
            enable_speculation=False))
    assert_no_models(session)
    return session, out


def assert_no_models(session) -> None:
    """Nothing on a timed path may sleep a modeled cost."""
    if session.cost_model.simulate_time:
        raise RuntimeError("DataPlane simulate_time is on")
    for p in session.pilots.values():
        if p.desc.app_master_overhead_s:
            raise RuntimeError(f"pilot {p.desc.name} sleeps a modeled "
                               "AppMaster overhead")


def _units(session) -> List[Any]:
    # the agents keep their CUs in a private registry; the benchmark only
    # reads the per-state monotonic stamps the CUs record
    return [cu for p in session.pilots.values()
            for cu in list(p.agent._cus.values())]


def cu_overheads(session, since: float) -> List[float]:
    """Pending -> running seconds of every CU submitted after ``since``."""
    out = []
    for cu in _units(session):
        t = cu.timings.get("t_pending")
        o = cu.overhead_s()
        if t is not None and t >= since and o is not None:
            out.append(o)
    return out


def cu_states(session) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for cu in _units(session):
        out[cu.state.value] = out.get(cu.state.value, 0) + 1
    return out
