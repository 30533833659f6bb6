import os

from repro.launch import platform as _platform
_platform.configure()
# ^ MUST precede any jax import: XLA flags lock at first backend init.
import argparse
import dataclasses
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.data.batches import input_specs, DEFAULT_ENC_LEN
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.models.config import SHAPES, ModelConfig, ShapeConfig, shape_applicable
from repro.roofline import analytic
from repro.roofline.hlo import collective_bytes_per_device
from repro.roofline.terms import V5E, roofline_terms
from repro.serve import make_decode_step, make_prefill_step
from repro.sharding import Plan
from repro.train import make_train_state, make_train_step, microbatch_count

HBM_PER_CHIP = V5E.hbm_bytes  # the dry-run models v5e pods


def _named(mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def _sharded_bytes(shapes_tree, spec_tree, mesh_axes) -> float:
    """Exact per-device bytes of a sharded pytree."""
    total = 0.0
    flat_shapes = jax.tree_util.tree_leaves(shapes_tree)
    flat_specs = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    for s, spec in zip(flat_shapes, flat_specs):
        n = 1.0
        for d in s.shape:
            n *= d
        denom = 1
        for ax in spec:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                denom *= mesh_axes[a]
        total += n * s.dtype.itemsize / denom
    return total


# Per-arch memory configuration for the train cells. The largest archs
# need bf16 optimizer moments + bf16 grad accumulation to fit 16 GB/chip
# (f32 moments alone for 236B params are 3.7 GB/chip on a 256-chip pod;
# f32 accumulation double-buffers another 7.4 GB). Real technique, see
# DESIGN.md 'hardware adaptation'.
TRAIN_MEMORY_OVERRIDES: Dict[str, Dict[str, Any]] = {
    # multi-pod doubles dp -> local batch halves -> n_mb=8 suffices,
    # halving FSDP expert-weight streaming (347 s -> 51 s measured)
    "deepseek-v2-236b": {"n_microbatches": 16, "n_microbatches_multi": 8,
                         "moment_dtype": jnp.bfloat16,
                         "accum_dtype": jnp.bfloat16},
    "deepseek-67b": {"n_microbatches": 16, "accum_dtype": jnp.bfloat16,
                     "pure_dp_single": True},
    # pure-DP (no TP) wins for attention-dense archs on the single-pod
    # mesh when global_batch >= chips: zero TP activation psums, weights
    # ZeRO-3-gathered per layer (EXPERIMENTS §Perf cell 2 + follow-on).
    # Refuted for SSM (channel-sharded scan has zero-comm TP already) and
    # for multi-pod (cross-pod gather/reduce explosion) — gated off there.
    "llama3.2-1b": {"pure_dp_single": True},
    "internlm2-1.8b": {"pure_dp_single": True},
    "internvl2-2b": {"pure_dp_single": True},
    "yi-6b": {"pure_dp_single": True},
    "hymba-1.5b": {"pure_dp_single": True},
    "seamless-m4t-medium": {"pure_dp_single": True},
}


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, plan: Plan,
               overrides: Optional[Dict[str, Any]] = None):
    """Returns (jitted_fn, example_args, extra-info) for one cell."""
    overrides = {**TRAIN_MEMORY_OVERRIDES.get(cfg.name, {}), **(overrides or {})}
    params_shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.key(0)))
    if shape.kind in ("prefill", "decode"):
        # weight-stationary serving: TP-sharded leaves drop FSDP when the
        # TP shard fits HBM — FSDP at inference re-gathers every weight
        # every token (measured ~8 GB/device/step on deepseek-67b decode)
        tp_shard_bytes = cfg.n_params() * 2 / plan.mesh_axes[plan.tp_axis]
        if tp_shard_bytes < 10e9 and not overrides.get("keep_fsdp_serving"):
            plan = dataclasses.replace(plan, serving=True)
    pspec = plan.param_specs(params_shapes)
    batch = input_specs(cfg, shape)
    bspec = plan.batch_specs(batch)
    axes = plan.mesh_axes
    params_dev = _sharded_bytes(params_shapes, pspec, axes)
    extra: Dict[str, Any] = {"params_bytes_per_device": params_dev}

    if shape.kind == "train":
        if overrides.get("pure_dp") or (overrides.get("pure_dp_single")
                                        and "pod" not in plan.mesh_axes):
            # small-model schedule: no tensor parallelism — batch over
            # (data x model), params ZeRO-3 over both axes, weights
            # gathered per layer. Zero TP activation psums.
            plan = dataclasses.replace(plan, dp_axes=("data", "model"))
            pspec = plan.param_specs(params_shapes)
            bspec = plan.batch_specs(batch)
        if overrides.get("pure_dp") or (overrides.get("pure_dp_single")
                                        and "pod" not in plan.mesh_axes):
            n_mb = overrides.get("n_microbatches_pure_dp", 1)
        elif "pod" in plan.mesh_axes and "n_microbatches_multi" in overrides:
            n_mb = overrides["n_microbatches_multi"]
        else:
            n_mb = overrides.get("n_microbatches") or microbatch_count(
                cfg, shape.global_batch, shape.seq_len, mesh.size)
        moment_dtype = overrides.get("moment_dtype", jnp.float32)
        accum_dtype = overrides.get("accum_dtype", jnp.float32)
        state_shapes = jax.eval_shape(
            lambda: make_train_state(
                cfg, transformer.init_params(cfg, jax.random.key(0)), moment_dtype))
        sspec = {"params": pspec, "opt": {"m": pspec, "v": pspec}, "step": P()}
        step = make_train_step(cfg, n_microbatches=n_mb,
                               remat=overrides.get("remat", True),
                               act_spec=plan.act_spec(sp=overrides.get("sp", False)),
                               moe_groups=plan.dp_size,
                               moe_ep_axis=overrides.get("moe_ep_axis",
                                                         plan.tp_axis),
                               accum_dtype=accum_dtype,
                               remat_policy=overrides.get("remat_policy"),
                               save_spec=(plan.act_spec(sp=True)
                                          if overrides.get("save_sp") else None))
        metrics_spec = {"loss": P(), "lr_scale": P(), "grad_norm": P()}
        fn = jax.jit(step,
                     in_shardings=(_named(mesh, sspec), _named(mesh, bspec)),
                     out_shardings=(_named(mesh, sspec), _named(mesh, metrics_spec)),
                     donate_argnums=(0,))
        # analytic TPU-resident peak (see run_cell docstring)
        state_dev = _sharded_bytes(state_shapes, sspec, axes)
        mb_local = max(1, shape.global_batch // n_mb // plan.dp_size)
        layers = cfg.n_layers + cfg.n_encoder_layers
        stacks = layers * mb_local * shape.seq_len * cfg.d_model * 2
        if cfg.family == "hybrid":
            stacks *= 1.25
        if overrides.get("remat_policy") == "save_tp_out":
            stacks *= 3.0
        if overrides.get("save_sp"):
            stacks = stacks * (2.0 / 3.0) / plan.mesh_axes[plan.tp_axis] \
                + stacks / 3.0  # saved tp-outs sharded; layer inputs full
        accum_bytes = 2 * params_dev / jnp.dtype(cfg.dtype).itemsize \
            * jnp.dtype(accum_dtype).itemsize
        peak = state_dev + accum_bytes + params_dev + stacks + 2e9
        extra.update({"n_microbatches": n_mb,
                      "state_bytes_per_device": state_dev,
                      "analytic_peak_bytes_per_device": peak,
                      "moment_dtype": str(jnp.dtype(moment_dtype)),
                      "accum_dtype": str(jnp.dtype(accum_dtype))})
        return fn, (state_shapes, batch), extra

    if shape.kind == "prefill":
        # EP shard_map only under the weight-stationary serving plan (same
        # gate as decode: EP pins expert weights dp-replicated)
        step = make_prefill_step(
            cfg, moe_groups=plan.dp_size,
            moe_ep_axis=overrides.get(
                "moe_ep_axis", plan.tp_axis if plan.serving else None))
        cache_shapes = jax.eval_shape(
            lambda: transformer.init_caches(cfg, shape.global_batch,
                                            shape.seq_len,
                                            shape.seq_len if cfg.is_encoder_decoder else 0))
        cspec = plan.cache_specs(cfg, cache_shapes)
        logits_spec = plan.logits_spec(shape.global_batch)
        fn = jax.jit(step,
                     in_shardings=(_named(mesh, pspec), _named(mesh, bspec)),
                     out_shardings=(_named(mesh, cspec), _named(mesh, logits_spec)))
        cache_dev = _sharded_bytes(cache_shapes, cspec, axes)
        extra.update({"cache_bytes_per_device": cache_dev,
                      "analytic_peak_bytes_per_device":
                          params_dev + 2 * cache_dev + 2e9})
        return fn, (params_shapes, batch), extra

    # decode
    enc_len = DEFAULT_ENC_LEN if cfg.is_encoder_decoder else 0
    cache_shapes = jax.eval_shape(
        lambda: transformer.init_caches(cfg, shape.global_batch,
                                        shape.seq_len, enc_len))
    cspec = plan.cache_specs(cfg, cache_shapes)
    # EP shard_map pins expert weights dp-replicated — only valid under the
    # weight-stationary serving plan; with FSDP'd weights (params too big
    # for TP-only) it would re-gather all experts every token.
    step = make_decode_step(cfg, moe_groups=plan.dp_size,
                            moe_ep_axis=overrides.get(
                                "moe_ep_axis",
                                plan.tp_axis if plan.serving else None))
    logits_spec = plan.logits_spec(shape.global_batch)
    fn = jax.jit(step,
                 in_shardings=(_named(mesh, pspec), _named(mesh, cspec),
                               _named(mesh, bspec["tokens"]), _named(mesh, bspec["pos"])),
                 out_shardings=(_named(mesh, cspec), _named(mesh, logits_spec)),
                 donate_argnums=(1,))
    args = (params_shapes, cache_shapes, batch["tokens"], batch["pos"])
    cache_dev = _sharded_bytes(cache_shapes, cspec, axes)
    extra.update({"cache_bytes_per_device": cache_dev,
                  "analytic_peak_bytes_per_device":
                      params_dev + cache_dev + 1e9})
    return fn, args, extra


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: Optional[Dict[str, Any]] = None,
             verbose: bool = True) -> Dict[str, Any]:
    """Lower + compile one (arch x shape x mesh) cell; derive roofline terms.

    Memory note: ``memory_analysis()`` (printed) is the XLA:CPU upper
    bound — the CPU backend f32-widens scan-saved bf16 stacks (verified
    absent at the jaxpr level, tests/test_dryrun.py). The
    ``analytic_peak_bytes_per_device`` field is the TPU-resident
    estimate used for the fits-HBM check.
    """
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                           "kind": shape.kind}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec.update({"applicable": False, "skip_reason": why})
        return rec
    rec["applicable"] = True

    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = Plan.for_mesh(mesh)
    t0 = time.time()
    fn, args, extra = build_cell(cfg, shape, mesh, plan, overrides)
    with jax.set_mesh(mesh):   # set_mesh: populates the abstract mesh that
        lowered = fn.lower(*args)  # the MoE EP shard_map path reads
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    mem["peak_bytes_per_device"] = (mem["argument_bytes"] + mem["temp_bytes"]
                                    + mem["output_bytes"] - mem["alias_bytes"])
    rec["memory"] = mem
    ca = compiled.cost_analysis() or {}
    rec["cost_analysis"] = {"flops_per_device": float(ca.get("flops", 0.0)),
                            "bytes_per_device": float(ca.get("bytes accessed", 0.0))}

    colls = collective_bytes_per_device(compiled.as_text())
    rec["collectives"] = {k: float(v) for k, v in colls.items()}

    n_mb = extra.get("n_microbatches", 1)
    tp = plan.mesh_axes[plan.tp_axis]
    cost = analytic.step_cost(cfg, shape, n_devices=mesh.size, tp=tp,
                              n_microbatches=n_mb)
    rec["analytic"] = {"flops": cost.flops, "hbm_bytes": cost.hbm_bytes,
                       "model_flops": cost.model_flops}
    rec.update({k: (float(v) if isinstance(v, (int, float)) else v)
                for k, v in extra.items()})
    rec["fits_hbm_analytic"] = bool(
        extra["analytic_peak_bytes_per_device"] < HBM_PER_CHIP)
    rec["n_devices"] = mesh.size
    rec["terms"] = roofline_terms(
        flops_global=cost.flops, hbm_bytes_global=cost.hbm_bytes,
        collective_bytes_per_device=colls["total"], n_chips=mesh.size,
        model_flops=cost.model_flops)
    if verbose:
        t = rec["terms"]
        print(f"[{mesh_name}] {arch} x {shape_name}: "
              f"mem/dev={mem['peak_bytes_per_device']/1e9:.2f}GB(cpu-ub) "
              f"analytic={extra['analytic_peak_bytes_per_device']/1e9:.2f}GB "
              f"fits={rec['fits_hbm_analytic']} "
              f"compute={t['compute_s']*1e3:.1f}ms memory={t['memory_s']*1e3:.1f}ms "
              f"collective={t['collective_s']*1e3:.1f}ms dominant={t['dominant']} "
              f"roofline_frac={t['roofline_fraction']:.3f} "
              f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)")
        print(f"    memory_analysis: {ma}")
    return rec


def main():
    ap = argparse.ArgumentParser(description="Multi-pod dry-run driver")
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="out/dryrun")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else configs.names()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                tag = f"{arch}__{shape_name}__{'multi' if multi else 'single'}"
                try:
                    rec = run_cell(arch, shape_name, multi)
                except Exception as e:  # a failure here is a bug in the system
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "pod2x16x16" if multi else "pod16x16",
                           "error": f"{type(e).__name__}: {e}"}
                    failures.append(tag)
                    print(f"FAILED {tag}: {rec['error']}")
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")
    print("dry-run: all cells OK")


if __name__ == "__main__":
    main()
