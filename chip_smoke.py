#!/usr/bin/env python3
"""Bring-up smoke: the paper's coupled DAG and the serving path on a TPU,
at the full width of llama3.2-1b, through the system's own entry points.

    python chip_smoke.py              # one chip: simulate/analyze/train + serve
    python chip_smoke.py --chips 4    # the four-chip checks (a)-(c) only

One chip (``Session`` with two pilots, ``hpc`` and ``ana``, over the chip):

* simulate — ``Trainer`` steps on llama3.2-1b at full width (d_model 2048,
  32/8 heads, head_dim 64, d_ff 8192, vocab 128256, bf16), depth cut to
  what 16 GiB holds with AdamW state; the loss on a fixed batch is finite
  and falls.
* analyze — ``kmeans_fit`` on the paper's 1m_points_50_clusters scenario
  with the compiled Pallas assignment kernel; its cost matches the jnp
  path's.
* train — the steering stage.  Every stage's Compute-Unit ends DONE.
* serve — the full 16-layer model in bf16 through ``ServeEngine`` with a
  ``ModelBackend``; each request's first token is the argmax of
  ``transformer.forward`` on its prompt (near-ties below bf16 resolution
  are reported, not failed).

Four chips: (a) the DAG on two 2-chip pilots, each pilot's arrays on its
own chips and a cross-pilot ``replicate_to`` landing on the other pilot's;
(b) ``Trainer`` on a (2, 2) mesh against one chip; (c) K-Means, local
against global data path on a 4-chip ``AnalyticsEngine``.

Seconds printed are set-up information (host clock, compiles included),
not speed metrics.  The last line of standard output is
``{"ok": true, "device": {...}}``; without a TPU, or when a check fails,
the script exits nonzero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Training depth that fits one v5e: the Trainer step compiled for a
# described v5e at batch 8 x 512 tokens needs 13.80 GiB at 12 layers and
# 14.99 GiB at 13 (memory_analysis: arguments + temporaries), against
# 16 GiB of HBM that also holds the analytics data and the runtime's own.
TRAIN_LAYERS = 12
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 5
KMEANS_SCENARIO = "1m_points_50_clusters"
KMEANS_RTOL = 1e-4          # kernel vs jnp cost, both f32 at full precision
TRAJ_POINTS, TRAJ_CLUSTERS = 8192, 4
SERVE_PROMPT_LENS = (256, 200, 256, 200, 256, 200)
SERVE_GEN, SERVE_SLOTS, SERVE_BUCKET = 24, 4, 256
LOSS_RTOL_4CHIP = 2e-2      # (2, 2) mesh vs one chip, bf16 params
DAG_TIMEOUT_S = 1000.0      # covers the cold compile of every stage


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class FixedBatch:
    """Stands in for a Trainer's TokenPipeline: the same batch every step,
    so a falling loss shows the optimizer working on a fixed target."""

    def __init__(self, batch: Dict[str, Any]):
        self.batch = batch

    def start(self, from_step: int = 0) -> "FixedBatch":
        return self

    def stop(self) -> None:
        pass

    def __iter__(self) -> "FixedBatch":
        return self

    def __next__(self) -> Dict[str, Any]:
        return self.batch


def _device_ids(tree) -> set:
    import jax
    return {d.id for x in jax.tree.leaves(tree) for d in x.sharding.device_set}


def _memory() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return (f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
            f"bytes_limit {stats.get('bytes_limit')}")


def train_steps(cfg, mesh, *, batch: int, seq: int, steps: int,
                seed: int = 0):
    """A few Trainer steps on one fixed batch; returns (trainer, losses,
    set-up seconds)."""
    from repro.optim import adamw
    from repro.train.trainer import Trainer
    t0 = time.monotonic()
    tr = Trainer(cfg, mesh, global_batch=batch, seq=seq,
                 hyper=adamw.Hyper(lr=1e-3), warmup_steps=1, seed=seed)
    tr.pipeline = FixedBatch(tr.pipeline.batch_at(0))
    tr.init_state()
    init_s = time.monotonic() - t0
    hist = tr.run(steps, log_every=0)
    losses = [h["loss"] for h in hist]
    return tr, losses, {"init_s": init_s, "first_step_s": hist[0]["step_s"]}


def check_losses(losses: Sequence[float]) -> None:
    import math
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")


def kmeans_paths(engine, name: str, k: int, *,
                 seed: int = 0) -> Dict[str, float]:
    """kmeans_fit through the compiled kernel and through the jnp path."""
    from repro.analytics import kmeans as km
    t0 = time.monotonic()
    _, cost_kernel = km.kmeans_fit(engine, name, k, use_kernel=True,
                                   seed=seed)
    kernel_s = time.monotonic() - t0
    _, cost_ref = km.kmeans_fit(engine, name, k, use_kernel=False,
                                seed=seed)
    return {"cost_kernel": cost_kernel, "cost_ref": cost_ref,
            "kernel_first_fit_s": kernel_s}


def run_dag(cfg, rm, *, pilot_chips: int = 1, batch: int = TRAIN_BATCH,
            seq: int = TRAIN_SEQ, steps: int = TRAIN_STEPS,
            scenario: str = KMEANS_SCENARIO, traj_points: int = TRAJ_POINTS,
            timeout: float = DAG_TIMEOUT_S, seed: int = 0) -> Dict[str, Any]:
    """simulate -> analyze -> train as a Session DAG over two pilots
    (``hpc`` and ``ana``, ``pilot_chips`` each) leased from ``rm``.

    Returns the stage results, placements, CU states, the chips each
    pilot's arrays sit on, and set-up seconds.  Raises CheckFailed."""
    import jax
    import numpy as np
    from repro.analytics import kmeans as km
    from repro.core import (PilotDescription, Session, analytics_stage,
                            hpc_stage)
    from repro.core.dataplane import replicated_sharding

    n_points, k = km.PAPER_SCENARIOS[scenario]
    session = Session(rm)
    try:
        # no speculative duplicates: a second copy of a full-width
        # training CU would not fit the chip next to the first
        hpc = session.add_pilot(PilotDescription(
            n_chips=pilot_chips, name="hpc", runtime="hpc",
            enable_speculation=False))
        ana = session.add_pilot(PilotDescription(
            n_chips=pilot_chips, name="ana", runtime="analytics",
            enable_speculation=False))

        def simulate(mesh=None):
            tr, losses, setup = train_steps(cfg, mesh, batch=batch, seq=seq,
                                            steps=steps, seed=seed)
            # 'trajectory' features: 3 columns of the trained embedding rows
            emb = tr.state["params"]["embed"]
            traj = np.asarray(emb[:traj_points, :3], np.float32)
            return {"traj": traj, "losses": losses, "setup": setup,
                    "state_devices": _device_ids(tr.state),
                    "mesh_shape": dict(mesh.shape)}

        def analyze(engine=None, traj=None):
            engine.put("points", km.make_dataset(n_points, seed=seed))
            out = kmeans_paths(engine, "points", k, seed=seed)
            engine.put("traj_points", traj)
            centroids, cost = km.kmeans_fit(engine, "traj_points",
                                            TRAJ_CLUSTERS, use_kernel=True,
                                            seed=seed)
            return {**out, "centroids": centroids, "cost": cost,
                    "points_devices": _device_ids(engine.get("points")),
                    "traj_devices": _device_ids(traj)}

        def train(centroids=None, results=None, mesh=None):
            # steer: the next round's data seed from the cluster cost
            return {"next_seed": int(results["analyze"]["cost"]) % 997}

        t0 = time.monotonic()
        res = session.run([
            hpc_stage("simulate", simulate, outputs=("traj",)),
            analytics_stage("analyze", analyze, inputs=("traj",),
                            outputs=("centroids",)),
            hpc_stage("train", train, inputs=("centroids",),
                      after=("analyze",)),
        ], timeout=timeout)
        dag_s = time.monotonic() - t0

        states = {p.desc.name: dict(p.agent.heartbeat()["cu_states"])
                  for p in (hpc, ana)}
        n_cus = sum(sum(s.values()) for s in states.values())
        check(n_cus >= 2 and all(set(s) <= {"done"} for s in states.values()),
              f"a stage's Compute-Unit did not end DONE: {states}")

        # a cross-pilot replica of the centroids (homed on the pilot that
        # last read them) lands on the other pilot's chips
        dst = ana if hpc.uid in session.dataplane.home_pilots("centroids") \
            else hpc
        landed, wire = session.dataplane.replicate_to(
            "centroids", dst.uid, replicated_sharding(dst.devices),
            reason="smoke:replicate")
        return {
            "results": res, "dag_s": dag_s, "cu_states": states,
            "placements": {n: {"pilot": p.get("pilot"), "mode": p.get("mode")}
                           for n, p in session.placements.items()},
            "hpc_devices": {d.id for d in hpc.devices},
            "ana_devices": {d.id for d in ana.devices},
            "replica_pilot": dst.desc.name,
            "replica_devices": _device_ids(landed), "replica_wire_bytes": wire,
        }
    finally:
        session.shutdown()


def check_dag(out: Dict[str, Any]) -> None:
    an = out["results"]["analyze"]
    check_losses(out["results"]["simulate"]["losses"])
    rel = abs(an["cost_kernel"] - an["cost_ref"]) / max(abs(an["cost_ref"]),
                                                       1e-30)
    check(rel <= KMEANS_RTOL,
          f"K-Means kernel cost {an['cost_kernel']} vs jnp {an['cost_ref']} "
          f"(rel {rel:.2e} > {KMEANS_RTOL})")


def serve_and_check(cfg, *, prompt_lens: Sequence[int] = SERVE_PROMPT_LENS,
                    gen: int = SERVE_GEN, slots: int = SERVE_SLOTS,
                    bucket: int = SERVE_BUCKET, seed: int = 0
                    ) -> Dict[str, Any]:
    """Serve random prompts greedily through ServeEngine + ModelBackend
    (``repro.launch.serve.serve_requests``); check each first token
    against the argmax of ``transformer.forward`` on the same prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import serve_requests
    from repro.models import transformer

    t0 = time.monotonic()
    params = jax.jit(functools.partial(transformer.init_params, cfg))(
        jax.random.key(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in prompt_lens]
    init_s = time.monotonic() - t0
    res = serve_requests(cfg, params, prompts, gen=gen, slots=slots,
                         prompt_bucket=bucket)

    fwd = jax.jit(functools.partial(transformer.forward, cfg, remat=False))
    ref_top: Dict[int, int] = {}
    ref_gap: Dict[int, float] = {}
    ref_res: Dict[int, float] = {}
    for n in sorted(set(prompt_lens)):
        idx = [i for i, p in enumerate(prompts) if len(p) == n]
        toks = jnp.asarray(np.stack([prompts[i] for i in idx]), jnp.int32)
        logits, _ = fwd(params, {"tokens": toks})
        last = np.asarray(logits[:, -1, :cfg.vocab_size], np.float32)
        for row, i in zip(last, idx):
            top2 = np.sort(row)[-2:]
            ref_top[i] = int(np.argmax(row))
            ref_gap[i] = float(top2[1] - top2[0])
            # one bf16 step at the top logit's magnitude
            ref_res[i] = float(abs(top2[1])) * 2.0 ** -7

    near_ties: List[Dict[str, Any]] = []
    for i, out in enumerate(res["outputs"]):
        check(out is not None and len(out) == gen,
              f"request {i}: {None if out is None else len(out)} tokens, "
              f"want {gen}")
        check(bool(((out >= 0) & (out < cfg.vocab_size)).all()),
              f"request {i}: token outside the vocabulary")
        if int(out[0]) == ref_top[i]:
            continue
        if ref_gap[i] < ref_res[i]:
            near_ties.append({"request": i, "served": int(out[0]),
                              "reference": ref_top[i], "gap": ref_gap[i]})
            continue
        raise CheckFailed(
            f"request {i}: first token {int(out[0])} != reference argmax "
            f"{ref_top[i]} (top-two gap {ref_gap[i]:.4g} > bf16 step "
            f"{ref_res[i]:.4g})")
    return {"n_requests": len(prompts), "gen": gen, "steps": res["steps"],
            "serve_s": res["wall_s"], "init_s": init_s,
            "near_ties": near_ties,
            "first_tokens": [int(o[0]) for o in res["outputs"]]}


# ------------------------------------------------------------ four chips
def check_two_pilots(cfg, devices, **dag_kw) -> Dict[str, Any]:
    """(a) The DAG on two 2-chip pilots: arrays on their own pilot's
    chips; a cross-pilot replicate_to lands on the other pilot's."""
    from repro.core import ResourceManager
    dag_kw.setdefault("seq", 256)
    out = run_dag(cfg, ResourceManager(devices=devices[:4]), pilot_chips=2,
                  **dag_kw)
    check_dag(out)
    hpc, ana = out["hpc_devices"], out["ana_devices"]
    sim, an = out["results"]["simulate"], out["results"]["analyze"]
    check(len(hpc) == 2 and len(ana) == 2 and not hpc & ana,
          f"pilots do not own distinct chips: hpc {hpc} ana {ana}")
    check(sim["state_devices"] == hpc,
          f"train state on {sim['state_devices']}, hpc pilot owns {hpc}")
    check(an["points_devices"] == ana,
          f"analytics data on {an['points_devices']}, ana pilot owns {ana}")
    check(an["traj_devices"] <= ana,
          f"traj read by analyze on {an['traj_devices']}, not ana's {ana}")
    dst = out[f"{out['replica_pilot']}_devices"]
    check(out["replica_devices"] == dst and out["replica_wire_bytes"] > 0,
          f"replicate_to {out['replica_pilot']} landed on "
          f"{out['replica_devices']} ({out['replica_wire_bytes']} B), it "
          f"owns {dst}")
    return out


def check_mesh_training(cfg, devices, *, steps: int = 3) -> Dict[str, Any]:
    """(b) Trainer on a (2, 2) mesh against the same steps on one chip."""
    import numpy as np
    from repro import compat
    from jax.sharding import Mesh
    one = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
    four = compat.make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    _, l1, _ = train_steps(cfg, one, batch=8, seq=256, steps=steps)
    tr4, l4, _ = train_steps(cfg, four, batch=8, seq=256, steps=steps)
    check_losses(l1)
    check(len(_device_ids(tr4.state)) == 4,
          f"(2, 2) train state on {_device_ids(tr4.state)}")
    for a, b in zip(l1, l4):
        check(abs(a - b) <= LOSS_RTOL_4CHIP * abs(a),
              f"(2, 2) mesh losses {l4} vs one chip {l1}")
    return {"losses_1chip": l1, "losses_2x2": l4}


def check_kmeans_paths(devices, *, scenario: str = KMEANS_SCENARIO,
                       seed: int = 0) -> Dict[str, Any]:
    """(c) K-Means, local against global data path, on 4 chips."""
    from repro import compat
    from repro.analytics import kmeans as km
    from repro.analytics.engine import AnalyticsEngine
    n, k = km.PAPER_SCENARIOS[scenario]
    engine = AnalyticsEngine(compat.make_mesh((4, 1), ("data", "model"),
                                              devices=devices[:4]))
    engine.put("points", km.make_dataset(n, seed=seed))
    check(len(_device_ids(engine.get("points"))) == 4, "points not on 4 chips")
    costs = {}
    for path in ("local", "global"):
        _, costs[path] = km.kmeans_fit(engine, "points", k, data_path=path,
                                       use_kernel=True, seed=seed)
    rel = abs(costs["local"] - costs["global"]) / max(abs(costs["local"]),
                                                      1e-30)
    check(rel <= KMEANS_RTOL, f"local vs global K-Means cost {costs}")
    return {"costs": costs, "moved_bytes": engine.moved_bytes}


# ------------------------------------------------------------------ main
def one_chip(full) -> None:
    import jax
    from repro.core import ResourceManager
    from repro.kernels.kmeans import ops as km_ops
    from repro.analytics import kmeans as km

    cut = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    n, k = km.PAPER_SCENARIOS[KMEANS_SCENARIO]
    log(f"simulate: {full.name} at full width, depth cut {full.n_layers} -> "
        f"{cut.n_layers} layers (16 GiB with AdamW state); batch "
        f"{TRAIN_BATCH} x {TRAIN_SEQ} tokens, {TRAIN_STEPS} steps")
    log(f"kernels: kmeans blocks (bn, bk) = "
        f"{km_ops.resolve_blocks(n, k, km.PAPER_DIM, 'float32', None, None)}"
        f" at {n} x {km.PAPER_DIM}, k={k}; flash_attention and mamba_scan "
        f"are not on this path")
    # two pilots over the one chip: each lease slot aliases it
    out = run_dag(cut, ResourceManager(devices=jax.devices()[:1] * 2))
    sim, an = out["results"]["simulate"], out["results"]["analyze"]
    log(f"simulate: losses {sim['losses']}; set-up {sim['setup']['init_s']:.1f}"
        f" s, first step {sim['setup']['first_step_s']:.1f} s (compile "
        f"included); mesh {sim['mesh_shape']}")
    log(f"analyze: {KMEANS_SCENARIO} cost kernel {an['cost_kernel']!r} jnp "
        f"{an['cost_ref']!r}; first kernel fit {an['kernel_first_fit_s']:.1f}"
        f" s (compile included); trajectory cost {an['cost']!r}")
    log(f"train: {out['results']['train']}; placements {out['placements']}; "
        f"CU states {out['cu_states']}; DAG {out['dag_s']:.1f} s")
    check_dag(out)
    log(f"memory after the DAG: {_memory()}")
    del out, sim, an
    gc.collect()

    log(f"serve: {full.name} full depth ({full.n_layers} layers), "
        f"{len(SERVE_PROMPT_LENS)} prompts of {sorted(set(SERVE_PROMPT_LENS))}"
        f" tokens, {SERVE_GEN} new tokens each, {SERVE_SLOTS} slots")
    srv = serve_and_check(full)
    log(f"serve: {srv['n_requests']} requests in {srv['steps']} decode steps;"
        f" first tokens {srv['first_tokens']} match transformer.forward; "
        f"near-ties {srv['near_ties']}; set-up {srv['init_s']:.1f} s, "
        f"serving {srv['serve_s']:.1f} s (compiles included)")
    log(f"memory: {_memory()}")


def four_chips(full) -> None:
    import jax
    devices = jax.devices()[:4]
    cut = dataclasses.replace(full, n_layers=2)
    log(f"four-chip checks on {full.name} at full width, 2 layers")
    a = check_two_pilots(cut, devices)
    log(f"(a) two pilots: hpc chips {sorted(a['hpc_devices'])}, ana chips "
        f"{sorted(a['ana_devices'])}; placements {a['placements']}; losses "
        f"{a['results']['simulate']['losses']}; K-Means cost kernel "
        f"{a['results']['analyze']['cost_kernel']!r} jnp "
        f"{a['results']['analyze']['cost_ref']!r}; replicate_to "
        f"{a['replica_pilot']} landed on {sorted(a['replica_devices'])} "
        f"({a['replica_wire_bytes']} B)")
    b = check_mesh_training(cut, devices)
    log(f"(b) training: one chip {b['losses_1chip']} vs (2, 2) mesh "
        f"{b['losses_2x2']}")
    c = check_kmeans_paths(devices)
    log(f"(c) K-Means on 4 chips: cost local {c['costs']['local']!r} global "
        f"{c['costs']['global']!r}; {c['moved_bytes']} B moved")
    log(f"memory (chip 0): {_memory()}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip checks")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro import configs
    from repro.kernels import autotune
    from repro.launch.cache import enable_compile_cache
    from repro.roofline.terms import chip_spec
    hw = chip_spec(dev)        # an unknown TPU kind stops the run here
    log(f"device: {dev.device_kind}, {len(devices)} device(s), peaks "
        f"{hw.peak_flops:.3g} FLOP/s {hw.hbm_bw:.3g} B/s, "
        f"{hw.hbm_bytes:.3g} B HBM")
    log(f"compile cache: {enable_compile_cache()}")
    log(f"autotune registry: {autotune.default_registry().path} "
        f"({len(autotune.default_registry())} entries; DEFAULTS "
        f"{autotune.DEFAULTS})")

    full = configs.get("llama3.2-1b")
    t0 = time.monotonic()
    if args.chips == 4:
        four_chips(full)
    else:
        one_chip(full)
    log(f"total {time.monotonic() - t0:.1f} s (set-up and compiles included)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
