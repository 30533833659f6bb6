"""The trace reduction, on events with known answers and on a recorded
CPU trace."""
from __future__ import annotations

import pytest

from chipbench import harness, readers, trace
from chipbench.trace import Device, Ev


def test_reduce_known_trace():
    ms = 1e6
    dev = Device(
        ops=[Ev("dot", 0 * ms, 4 * ms), Ev("add", 3 * ms, 5 * ms),
             Ev("dot", 7 * ms, 8 * ms), Ev("dot", 11 * ms, 13 * ms)],
        modules=[Ev("jit_train_step(1)", 0, 5 * ms),
                 Ev("jit_train_step(1)", 7 * ms, 8 * ms)])
    spans = [Ev("window", 0, 10 * ms), Ev("round", 0, 10 * ms),
             Ev("stage:analyze", 5 * ms, 6.5 * ms)]
    r = trace.reduce({"/device:TPU:0": dev}, spans)
    assert r.window_s == pytest.approx(0.010)
    assert r.busy_s == pytest.approx(0.006)           # [0,5] + [7,8]
    assert r.ops_s["dot"] == pytest.approx(0.005)      # 4 + 1, 11-13 clipped
    assert r.ops_s["add"] == pytest.approx(0.002)
    assert r.idle_s == pytest.approx({"stage:analyze": 0.002, "round": 0.002})
    t, n = r.module_time(lambda s: s.startswith("jit_train_step"))
    assert (t, n) == (pytest.approx(0.006), 2)
    b = r.breakdown()
    assert b["device_ops"][0] == ["dot", pytest.approx(0.005)]
    assert len(b["idle_gaps"]) == 2


def test_kmeans_kernel_is_named_as_the_chip_names_it():
    # operation names as a TPU v5e trace gives them (shapes shortened)
    kernel = ("%branch_0_fun.1 = (s32[11184128]{0:T(1024)S(1)}, "
              "f32[11184128]{0:T(1024)S(1)}) custom-call(f32[11184128,3]"
              "{1,0:T(8,128)} %copy.3, f32[56,3]{1,0:T(8,128)S(1)} %pad.2), "
              "custom_call_target=\"tpu_custom_call\"")
    sort = ("%sort.20 = (u32[11184128]{0:T(1024)}, s32[11184128]{0:T(1024)}, "
            "s32[11184128]{0:T(1024)}) sort(u32[11184128]{0:T(1024)S(1)} %a)")
    assert readers.is_kmeans_kernel(kernel)
    assert not readers.is_kmeans_kernel(sort)
    assert trace.short_op(kernel) == "%branch_0_fun.1 custom-call"


def test_kmeans_readers_split_kernel_draw_and_reduce():
    ms = 1e6
    kernel = ("%k = (s32[1024]{0}, f32[1024]{0}) custom-call(f32[1024,3]{1,0}"
              " %p), custom_call_target=\"tpu_custom_call\"")
    dev = Device(
        ops=[Ev("%sort.1 = u32[1024]{0} sort(u32[1024]{0} %a)", 0, 3 * ms),
             Ev(kernel, 3 * ms, 5 * ms), Ev("%fusion = f32[8,3]{1,0} "
                                             "fusion(f32[1024,3]{1,0} %p)",
                                             5 * ms, 5.5 * ms),
             Ev(kernel, 6 * ms, 8 * ms), Ev("%fusion = f32[8,3]{1,0} "
                                             "fusion(f32[1024,3]{1,0} %p)",
                                             8 * ms, 8.5 * ms)],
        modules=[Ev("jit__shuffle(7)", 0, 3 * ms),
                 Ev("jit_shard_fn(3)", 3 * ms, 5.5 * ms),
                 Ev("jit_shard_fn(3)", 6 * ms, 8.5 * ms)])
    rec = harness.Record(cell="k", chips=1, config={}, traffic={}, peak=None)
    rec.trace = trace.reduce({"/device:TPU:0": dev},
                             [Ev("window", 0, 10 * ms)])
    rec.counters.update(units=1, kmeans_iters=2)
    root = harness.ROOT
    read = lambda m: harness.load_module(
        f"{root}/chipbench/metrics/{m}.py", m.replace(".", "_")).read(rec)
    assert read("kmeans_init_ms") == pytest.approx(3.0)
    assert read("kmeans_reduce_ms") == pytest.approx(0.5)   # (8 - 4 - 3) / 2


def test_two_devices_average():
    ms = 1e6
    devs = {"/device:TPU:0": Device([Ev("a", 0, 10 * ms)], []),
            "/device:TPU:1": Device([Ev("a", 0, 5 * ms)], [])}
    r = trace.reduce(devs, [Ev("window", 0, 10 * ms)])
    assert r.busy_s == pytest.approx(0.0075) and r.n_devices == 2
    assert r.idle_s == pytest.approx({"other": 0.0025})


def test_recorded_cpu_trace_has_the_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    spans = harness.Spans()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=trace.profile_options())
    with spans.span("window"):
        for _ in range(2):
            with spans.span("round"):
                jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    devices, found = trace.load(trace.find(str(tmp_path)),
                                harness.SPAN_NAMES)
    names = sorted(s.name for s in found)
    assert names == ["round", "round", "window"]
    win = next(s for s in found if s.name == "window")
    assert all(win.start_ns <= s.start_ns <= s.end_ns <= win.end_ns
               for s in found)
    assert [n for n, _, _ in spans.records] == ["round", "round", "window"]
