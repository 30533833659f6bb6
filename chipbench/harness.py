"""The harness: finds a cell's files by name, runs it, prints one result.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own, found by the name that ``BENCHMARK.json`` gives:

* ``chipbench/configs/<config>.json``  the configuration as it is run;
* ``chipbench/traffic/<traffic>.json`` the mix's parameters, whose
  ``driver`` key names ``chipbench/drivers/<driver>.py``;
* ``chipbench/metrics/<metric>.py``   one reader per metric, with a
  ``read(rec)`` that returns a number or ``None`` (nothing to read).

A driver module has ``setup(ctx)``, ``window(ctx, state, deadline)``,
``release(ctx, state)`` and ``check(ctx, state)``; see ``drivers/``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
import types
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPAN_NAMES = ("window", "round", "stage:simulate", "stage:analyze",
              "stage:steer", "train.step", "kmeans.fit")


class NoChip(RuntimeError):
    """JAX found no accelerator this cell can run on."""


# ------------------------------------------------------------------ files
def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(known: {sorted(e['name'] for e in entries)})")


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with every file it names."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    driver: types.ModuleType
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str

    def reader(self, metric: str) -> types.ModuleType:
        return load_module(
            os.path.join(self.root, "chipbench", "metrics", f"{metric}.py"),
            f"chipbench_metric_{metric.replace('.', '_')}")


def _reports(metric: Dict[str, Any], cell: str, e2e: List[str]) -> bool:
    """A metric with ``workloads`` is reported in those cells; one without
    in every cell (end to end) or every cell that reports what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    w = _by_name(bench["workloads"], name, "workload")
    conf = _by_name(bench["configs"], w["config"], "config")
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "chipbench", "traffic",
                                     f"{w['traffic']}.json"))
    driver = load_module(
        os.path.join(root, "chipbench", "drivers", f"{traffic['driver']}.py"),
        f"chipbench_driver_{traffic['driver']}")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, driver=driver, end_to_end=e2e,
                per_layer=per_layer, root=root)


# ------------------------------------------------------------------ spans
class Spans:
    """Host spans of the benchmark's own calls into each layer: kept in
    memory on the monotonic clock, and written into the profiler's trace
    (``jax.profiler.TraceAnnotation``) so idle gaps can be attributed."""

    def __init__(self) -> None:
        self.records: List[Tuple[str, float, float]] = []

    def span(self, name: str):
        return _Span(self, name)

    def durations(self, name: str, since: float = 0.0) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.records
                if n == name and t0 >= since]


class _Span:
    def __init__(self, spans: Spans, name: str):
        import jax
        self.spans, self.name = spans, name
        self.annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.annotation.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        self.annotation.__exit__(*exc)
        self.spans.records.append((self.name, self.t0, t1))
        return False


# ------------------------------------------------------------------ record
@dataclasses.dataclass
class Record:
    """What one run leaves for the metric readers."""
    cell: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    peak: Any                       # peaks.Peak of the device kind
    setup_s: float = 0.0
    window_s: float = 0.0
    window_start: float = 0.0       # monotonic
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    spans: Spans = dataclasses.field(default_factory=Spans)
    trace: Any = None               # trace.Reduced of the traced window


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the seed, the devices and the record."""
    cell: Cell
    seed: int
    devices: List[Any]
    rec: Record

    def say(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


# ------------------------------------------------------------------ device
def device_info(devices, require_tpu: bool):
    """(platform, kind, peak) of the run's chips; raises NoChip."""
    from chipbench import peaks
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev.platform!r})")
    if require_tpu:
        return dev.platform, dev.device_kind, peaks.peak_for(dev.device_kind)
    return dev.platform, dev.device_kind, peaks.PEAKS["TPU v5 lite"]


def memory_peak(devices) -> int:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices]
    return int(max(peaks_) if peaks_ else 0)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``; every program is cached, however fast
    it compiled, so a second run compiles nothing."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts the programs JAX had to compile or load from the persistent
    cache while ``on``: any such request inside the window means a shape
    was not warmed up."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self) -> None:
        from jax._src import monitoring
        self._monitoring = monitoring
        self.n = 0
        self.on = False
        monitoring.register_event_listener(self._event)

    def _event(self, name: str, **_kw) -> None:
        if self.on and name == self.EVENT:
            self.n += 1

    def close(self) -> None:
        self._monitoring.unregister_event_listener(self._event)


# ------------------------------------------------------------------ run
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, require_tpu: bool = True,
        t_start: Optional[float] = None) -> Tuple[int, Optional[Dict]]:
    """Run one cell; print the checks on stderr and the result line on
    stdout.  Returns (exit code, result)."""
    t_start = time.monotonic() if t_start is None else t_start
    cell = load_cell(workload, root)
    import jax
    enable_compile_cache(root)
    devices = jax.devices()
    platform, kind, peak = device_info(devices, require_tpu)
    if len(devices) < cell.chips:
        raise NoChip(f"cell {workload} needs {cell.chips} chips; JAX found "
                     f"{len(devices)}")
    devices = devices[:cell.chips]
    rec = Record(cell=workload, chips=cell.chips, config=cell.config,
                 traffic=cell.traffic, peak=peak)
    ctx = Context(cell=cell, seed=int(seed), devices=devices, rec=rec)
    compiles = CompileCounter()

    state = cell.driver.setup(ctx)
    trace_dir = None
    if trace:
        import tempfile
        from chipbench import trace as trace_mod
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=trace_mod.profile_options())
    compiles.on = True
    rec.window_start = time.monotonic()
    rec.setup_s = rec.window_start - t_start
    try:
        with rec.spans.span("window"):
            cell.driver.window(ctx, state, rec.window_start + seconds)
        rec.window_s = time.monotonic() - rec.window_start
    finally:
        compiles.on = False
        compiles.close()
        if trace:
            jax.profiler.stop_trace()
    rec.counters["window_compiles"] = compiles.n
    mem = memory_peak(devices)
    if trace:
        try:
            rec.trace = trace_mod.reduce_dir(trace_dir, SPAN_NAMES)
        finally:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
    cell.driver.release(ctx, state)
    checks: List[Check] = list(cell.driver.check(ctx, state))
    checks.append(Check("window_compiles", float(compiles.n), 0.0))

    metrics: Dict[str, Dict[str, Any]] = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.reader(m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": mem}
    result: Dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": int(rec.counters.get("attempted", 0)),
        "failed": int(rec.counters.get("failed", 0)),
        "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = rec.trace.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0, result
