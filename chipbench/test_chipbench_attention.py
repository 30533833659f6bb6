"""The attention readers (``attention_ms.train``, ``attention_ms.train-ep``)
on synthetic traces whose operations are named as a TPU v5e trace names
them (shapes shortened)."""
from __future__ import annotations

import pytest

from chipbench import attention_ops, harness, trace
from chipbench.trace import Device, Ev

T = "{3,2,1,0:T(8,128)(2,1)}"
KERNEL = [  # the fused kernel: forward, dq, dk/dv
    f"%splash_mqa_fwd_residuals.17 = (f32[2,8,512,128]{T}, bf16[2,8,2,2048,"
    f"128]{T}) custom-call(bf16[2,8,2,2048,128]{T} %q), "
    "custom_call_target=\"tpu_custom_call\"",
    f"%splash_mqa_dq_no_residuals.9 = (f32[2,8,512,128]{T}, bf16[2,8,2,2048,"
    f"128]{T}) custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"",
    f"%splash_mqa_dkv_no_residuals.9 = (bf16[2,8,2048,128]{T}, bf16[2,8,2048,"
    f"128]{T}) custom-call(%a, %b), custom_call_target=\"tpu_custom_call\""]
DENSE_SCORES = [  # sdpa at S 2048: the product, the softmax, its gradient
    f"%convolution.3 = bf16[2,16,2048,2048]{T} convolution(bf16[2,2048,16,"
    f"128]{T} %q, bf16[2,2048,16,128]{T} %k), dim_labels=b0f_0io->b0f",
    f"%fusion.31 = bf16[2,16,2048,2048]{T} fusion(f32[2,16,2048,2048]{T} "
    "%l), kind=kLoop, calls=%fused_computation.31",
    f"%fusion.40 = bf16[2,2048,16,128]{T} fusion(bf16[2,16,2048,2048]{T} %p,"
    f" bf16[2,2048,16,128]{T} %v), kind=kOutput, calls=%fused_computation.4"]
CHUNK_SCORES = [  # chunked_sdpa at S 4096: one (1024, 1024) block pair
    f"%fusion.77 = f32[4,16,1024,1024]{T} fusion(bf16[4,1024,16,192]{T} %q,"
    f" bf16[4,1024,16,192]{T} %k), kind=kOutput, calls=%fused_computation.7"]
OTHER = [  # projections, the layer loop, the MLP, the optimizer
    f"%while.19 = (s32[], bf16[2,16,2048,2048]{T}) while(%t)",
    f"%convolution.9 = bf16[2,2048,16,128]{T} convolution(bf16[2,2048,2048]"
    f"{T} %x, bf16[2048,16,128]{T} %w), dim_labels=b0f_0io->b0f",
    f"%fusion.12 = bf16[2,2048,8192]{T} fusion(bf16[2,2048,2048]{T} %x), "
    "kind=kOutput",
    f"%custom-call.5 = bf16[2,2048,16,128]{T} custom-call(%s, %t), "
    "custom_call_target=\"ConcatBitcast\"",
    f"%fusion.5 = f32[9,2048,2048]{T} fusion(f32[9,2048,2048]{T} %m), "
    "kind=kLoop"]


@pytest.mark.parametrize("seq,yes", [
    (2048, KERNEL + DENSE_SCORES), (4096, KERNEL + CHUNK_SCORES)],
    ids=["dense-2048", "chunked-4096"])
def test_attention_ops_are_the_kernel_and_the_score_arrays(seq, yes):
    scores = attention_ops.score_arrays(seq)
    assert all(attention_ops.is_attention_op(n, scores) for n in yes)
    assert not any(attention_ops.is_attention_op(n, scores) for n in OTHER)


def _rec(cell, seq, ops, steps):
    ms = 1e6
    evs, t = [], 0.0
    for name in ops:
        evs.append(Ev(name, t, t + ms))
        t += ms
    mods = [Ev("jit_train_step(3)", i * ms, (i + 1) * ms) for i in range(steps)]
    rec = harness.Record(cell=cell, chips=1, config={},
                         traffic={"train": {"batch": 2, "seq": seq}},
                         peak=None)
    rec.trace = trace.reduce({"/device:TPU:0": Device(evs, mods)},
                             [Ev("window", 0, 100 * ms)])
    return rec


def _read(metric, rec):
    return harness.load_module(
        f"{harness.ROOT}/chipbench/metrics/{metric}.py",
        metric.replace(".", "_").replace("-", "_")).read(rec)


@pytest.mark.parametrize("metric,seq,scores", [
    ("attention_ms.train", 2048, DENSE_SCORES),
    ("attention_ms.train-ep", 4096, CHUNK_SCORES)],
    ids=["train", "train-ep"])
def test_readers_give_attention_time_per_step(metric, seq, scores):
    """1 ms for each operation: the parent's score operations and the
    kernel's calls read alike; the other operations are left out."""
    assert _read(metric, _rec("c", seq, scores + OTHER, 2)) == \
        pytest.approx(len(scores) / 2)
    assert _read(metric, _rec("c", seq, KERNEL * 4 + OTHER, 4)) == \
        pytest.approx(len(KERNEL))
    assert _read(metric, _rec("c", seq, OTHER, 2)) is None
    rec = _rec("c", seq, KERNEL, 1)
    rec.trace = None
    assert _read(metric, rec) is None
