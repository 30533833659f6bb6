"""The expert-parallel training cell at smoke size on the CPU: the whole
run is ``correct``, each planted fault makes it not, and the layout check
refuses a tree that is not the benchmark's."""
from __future__ import annotations

import json
import os
import shutil

import pytest

from chipbench import harness, model_mla_moe
from chipbench.test_chipbench_harness import OPT

SMOKE_EP = {
    "name": "smoke-mla-moe", "source": "test", "hidden_act": "silu",
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 16,
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_routed_experts": 4,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_method": "greedy", "v_head_dim": 16, "vocab_size": 500,
    "router_experts": 16, "first_expert": 4, "aux_loss_alpha": 0.001,
    "torch_dtype": "float32",
    "limits": {"grad_gap": 1e-3, "delta_gap": 1e-3}}
SMOKE_TRAFFIC = {"driver": "train_ep",
                 "pilots": {"hpc": {"runtime": "hpc", "chips": 1}},
                 "train": {"batch": 2, "seq": 64}, "optimizer": OPT,
                 "warm_steps": 1}
CELL = "smoke.train-ep"


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def ep_root(tmp_path_factory):
    """A copy of the benchmark with a smoke expert-parallel cell added as
    new files and new entries of BENCHMARK.json."""
    root = str(tmp_path_factory.mktemp("bench_ep"))
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_benchmark(harness.ROOT)
    rel = "chipbench/configs/smoke-mla-moe.json"
    _dump(os.path.join(root, rel), SMOKE_EP)
    bench["configs"].append({"name": SMOKE_EP["name"], "source": "test",
                             "file": rel, "reduced": [], "why": "smoke"})
    _dump(os.path.join(root, "chipbench", "traffic", "smoke-train-ep.json"),
          SMOKE_TRAFFIC)
    bench["workloads"].append({"name": CELL, "config": SMOKE_EP["name"],
                               "traffic": "smoke-train-ep", "chips": 1,
                               "why": "smoke"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "train-ep.deepseek-v2-lite" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def _run(root, trace=False, seed=2 ** 33 + 29):
    code, res = harness.run(CELL, seed, 0.5, trace, root=root,
                            require_tpu=False)
    assert code == 0
    return res


def _failed(res):
    return sorted(n for n, c in res["checks"].items()
                  if not c["value"] <= c["limit"])


def test_smoke_cell_runs_correct(ep_root):
    res = _run(ep_root)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert {"setup_s", "train_tokens_per_s"} <= set(res["metrics"])


def test_traced_run_reads_the_new_metrics(ep_root):
    """The counter-based metric and the idle share read; the step
    program's device time needs a device plane, which the CPU's trace
    lacks."""
    res = _run(ep_root, trace=True)
    assert res["correct"], res["checks"]
    assert {"moe_train_mfu", "device_idle.train-ep"} <= set(res["metrics"])
    assert "train_step_ms.train-ep" not in res["metrics"]


def _norm_topk(monkeypatch):
    import dataclasses
    real = model_mla_moe.program_config
    monkeypatch.setattr(model_mla_moe, "program_config", lambda cj:
                        dataclasses.replace(real(cj), moe_norm_topk=True))


def _no_mscale(monkeypatch):
    from repro.models.layers import attention
    monkeypatch.setattr(attention, "mla_softmax_scale", lambda cfg: (
        cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5)


def _expert_dropped(monkeypatch):
    from repro.models.layers import moe
    real = moe._expert_block
    monkeypatch.setattr(moe, "_expert_block", lambda p, buf, dt:
                        real(p, buf, dt).at[:, 0].set(0))


def _half_batch(monkeypatch):
    import repro.train.trainer as trainer_mod
    real = trainer_mod.make_train_step

    def half(*a, **kw):
        step = real(*a, **kw)
        return lambda state, batch: step(
            state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    monkeypatch.setattr(trainer_mod, "make_train_step", half)


@pytest.mark.parametrize("plant", [_norm_topk, _no_mscale, _expert_dropped,
                                   _half_batch],
                         ids=["topk_renormalised", "yarn_mscale_left_out",
                              "held_expert_dropped", "half_batch_left_out"])
def test_planted_fault_fails_a_check(ep_root, plant, monkeypatch):
    plant(monkeypatch)
    res = _run(ep_root)
    assert not res["correct"], res["checks"]
    assert set(_failed(res)) & {"grad_gap", "delta_gap"}, res["checks"]


def test_layout_check_refuses_a_changed_tree():
    import dataclasses
    import jax
    from repro.models import transformer
    cfg = model_mla_moe.program_config(SMOKE_EP)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.key(0)))
    model_mla_moe.check_layout(SMOKE_EP, shapes)
    more = dataclasses.replace(cfg, moe_experts_held=cfg.moe_experts_held + 1)
    with pytest.raises(ValueError, match="layout"):
        model_mla_moe.check_layout(SMOKE_EP, jax.eval_shape(
            lambda: transformer.init_params(more, jax.random.key(0))))
    latent_q = dataclasses.replace(cfg, q_lora_rank=8)
    with pytest.raises(ValueError, match="layout"):
        model_mla_moe.check_layout(SMOKE_EP, jax.eval_shape(
            lambda: transformer.init_params(latent_q, jax.random.key(0))))


def test_calibration_rows_hold_the_faults(ep_root, tmp_path):
    from chipbench import calibrate
    out = tmp_path / "readings.jsonl"
    assert calibrate.main(["--workload", CELL, "--seeds", "1", "--controls",
                           "1", "--first-seed", str(2 ** 33 + 3),
                           "--out", str(out)], root=ep_root) == 0
    (row,) = [json.loads(line) for line in out.read_text().splitlines()]
    lim = SMOKE_EP["limits"]
    assert all(row["program"][k] <= v for k, v in lim.items()), row
    for fault in ("fault_renorm", "fault_no_mscale", "fault_drop_expert",
                  "fault_half_batch"):
        assert any(row[fault][k] > v for k, v in lim.items()), (fault, row)
    assert "control_fp8" in row


def test_expert_matmuls_are_matched_by_the_held_weights_shape():
    """Operation names as a v5e trace gives them (shortened): the
    products and gradients of the 8 held experts (convolutions and the
    output fusions around them) match; the scan's ``while``, the
    optimizer's tuple update, copies, the norms' reductions, and the
    loop fusions that slice a layer's weights out of the stack or write
    its gradient into it do not."""
    from chipbench import harness as h
    mod = h.load_module(os.path.join(
        harness.ROOT, "chipbench", "metrics", "moe_expert_ms.train-ep.py"),
        "moe_expert_ms_test")
    w = mod._weights({"n_routed_experts": 8, "hidden_size": 2048,
                      "moe_intermediate_size": 1408})
    t = "{2,1,0:T(8,128)(2,1)}"
    out_fusion = ", kind=kOutput, calls=%fused_computation.722.clone.clone"
    loop_fusion = ", kind=kLoop, calls=%fused_computation.31"
    yes = [
        f"%fusion.1572 = bf16[8,1920,2048]{t} fusion(bf16[5,8,2048,1408]{t} "
        f"%get-tuple-element.9682, s32[]{{:T(128)}} %subtract.41){out_fusion}",
        f"%fusion.1589 = bf16[5,8,2048,1408]{t} fusion(bf16[5,8,2048,1408]{t}"
        f" %g, bf16[8,1920,2048]{t} %c){out_fusion}",
        f"%convolution_add_fusion.4 = bf16[8,1920,2048]{t} fusion(bf16[8,1920,"
        f"2048]{t} %f, bf16[5,8,2048,1408]{t} %g){out_fusion}",
        f"%convolution.12 = bf16[8,1920,1408]{t} convolution(bf16[8,1920,2048]"
        f"{t} %x, bf16[8,2048,1408]{t} %w), dim_labels=b0f_0io->b0f"]
    no = [
        f"%while.557 = (s32[]{{:T(128)}}, bf16[5,8,2048,1408]{t}) while(%t)",
        f"%fusion.746 = (bf16[5,8,1408,2048]{t}, f32[5,8,1408,2048]{t}) "
        f"fusion(bf16[5,8,1408,2048]{t} %p)",
        f"%copy.719 = bf16[5,8,2048,1408]{t} copy(bf16[5,8,2048,1408]{t} %p)",
        f"%fusion.744 = f32[]{{:T(128)}} fusion(bf16[5,8,2048,1408]{t} %w)"
        f"{loop_fusion}",
        f"%fusion.9 = bf16[4,4096,2816]{t} fusion(bf16[5,2048,2816]{t} %s)"
        f"{out_fusion}",
        # one layer's weights sliced out of the (5, 8, 2048, 1408) stack
        f"%fusion.1601 = bf16[8,2048,1408]{t} fusion(bf16[5,8,2048,1408]{t} "
        f"%get-tuple-element.9602, s32[]{{:T(128)}} %subtract.41){loop_fusion}",
        # a layer's gradient written into the stacked gradients
        f"%fusion.1602 = bf16[5,8,2048,1408]{t} fusion(bf16[5,8,2048,1408]{t}"
        f" %acc, bf16[8,2048,1408]{t} %gw, s32[]{{:T(128)}} %i){loop_fusion}",
        f"%dynamic-update-slice.7 = bf16[5,8,2048,1408]{t} dynamic-update-slice("
        f"bf16[5,8,2048,1408]{t} %acc, bf16[1,8,2048,1408]{t} %gw, s32[] %i, "
        f"s32[] %z, s32[] %z, s32[] %z)"]
    assert all(mod.is_expert_matmul(n, w) for n in yes)
    assert not any(mod.is_expert_matmul(n, w) for n in no)
