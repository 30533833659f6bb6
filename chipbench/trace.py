"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

* device busy time: the union of the intervals in which an operation ran
  on a chip (the ``XLA Ops`` line of a ``/device:TPU:n`` plane), inside
  the benchmark's ``window`` host span, averaged over the chips;
* device time per operation name and per program (``XLA Modules``);
* idle gaps: the complement of the busy intervals inside the window, each
  attributed to the innermost benchmark host span that covers its middle
  (``other`` where none does).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


class Ev(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Device:
    ops: List[Ev]
    modules: List[Ev]


@dataclasses.dataclass
class Reduced:
    busy_s: float                      # mean over devices
    window_s: float
    n_devices: int
    ops_s: Dict[str, float]            # per op name, mean over devices
    modules_s: Dict[str, float]        # per program, mean over devices
    idle_s: Dict[str, float]           # per host span, mean over devices
    module_counts: Dict[str, int]

    def op_time(self, match) -> float:
        return sum(s for n, s in self.ops_s.items() if match(n))

    def module_time(self, match) -> Tuple[float, int]:
        t = sum(s for n, s in self.modules_s.items() if match(n))
        c = sum(k for n, k in self.module_counts.items() if match(n))
        return t, c

    def breakdown(self) -> Dict[str, List[List]]:
        """The operations that took most device time (by HLO name and
        opcode) and the idle time by what the host was doing."""
        ops: Dict[str, float] = {}
        for n, s in self.ops_s.items():
            ops[short_op(n)] = ops.get(short_op(n), 0.0) + s
        top = lambda d: [[n, s] for n, s in
                         sorted(d.items(), key=lambda x: -x[1])[:TOP]]
        return {"device_ops": top(ops), "idle_gaps": top(self.idle_s)}


_HLO = re.compile(r"^(%\S+) = .*?\s([a-z][\w-]*)\(")


def short_op(name: str) -> str:
    """``%fusion.12 = bf16[..]{..} fusion(...)`` -> ``%fusion.12 fusion``."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


# ------------------------------------------------------------------ loading
def find(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, span_names: Iterable[str]
         ) -> Tuple[Dict[str, Device], List[Ev]]:
    """Device planes by name, and the benchmark's host spans."""
    import jax
    names = set(span_names)
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Device] = {}
    spans: List[Ev] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device(ops=[], modules=[])
            lines = {line.name: line for line in plane.lines}
            for lname, dst in ((OPS_LINE, dev.ops), (MODULES_LINE, dev.modules)):
                if lname in lines:
                    dst.extend(Ev(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in lines[lname].events)
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Ev(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in names)
    return devices, spans


# ------------------------------------------------------------------ reducing
def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(evs: Sequence[Ev], lo: float, hi: float) -> List[Ev]:
    return [Ev(e.name, max(e.start_ns, lo), min(e.end_ns, hi))
            for e in evs if e.end_ns > lo and e.start_ns < hi]


def _label(t: float, spans: Sequence[Ev]) -> str:
    best: Optional[Ev] = None
    for s in spans:
        if s.name != "window" and s.start_ns <= t <= s.end_ns:
            if best is None or s.end_ns - s.start_ns < best.end_ns - best.start_ns:
                best = s
    return best.name if best is not None else "other"


def reduce(devices: Dict[str, Device], spans: List[Ev]) -> Reduced:
    win = [s for s in spans if s.name == "window"]
    if win:
        w = max(win, key=lambda s: s.end_ns - s.start_ns)
        lo, hi = w.start_ns, w.end_ns
    else:
        evs = [e for d in devices.values() for e in d.ops]
        lo = min((e.start_ns for e in evs), default=0.0)
        hi = max((e.end_ns for e in evs), default=0.0)
    n = max(len(devices), 1)
    busy = 0.0
    ops: Dict[str, float] = {}
    mods: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    idle: Dict[str, float] = {}
    for dev in devices.values():
        clipped = _clip(dev.ops, lo, hi)
        merged = union((e.start_ns, e.end_ns) for e in clipped)
        busy += sum(b - a for a, b in merged)
        for e in clipped:
            ops[e.name] = ops.get(e.name, 0.0) + (e.end_ns - e.start_ns)
        for e in _clip(dev.modules, lo, hi):
            mods[e.name] = mods.get(e.name, 0.0) + (e.end_ns - e.start_ns)
            counts[e.name] = counts.get(e.name, 0) + 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                lab = _label((a + b) / 2, spans)
                idle[lab] = idle.get(lab, 0.0) + (b - a)
    ns = 1e-9 / n
    return Reduced(busy_s=busy * ns, window_s=(hi - lo) * 1e-9, n_devices=n,
                   ops_s={k: v * ns for k, v in ops.items()},
                   modules_s={k: v * ns for k, v in mods.items()},
                   idle_s={k: v * ns for k, v in idle.items()},
                   module_counts={k: -(-v // n) for k, v in counts.items()})


def reduce_dir(trace_dir: str, span_names: Iterable[str]) -> Reduced:
    devices, spans = load(find(trace_dir), span_names)
    return reduce(devices, spans)


def profile_options():
    """Host spans and device activity, without the Python tracer (which
    would slow every call the window makes)."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o
