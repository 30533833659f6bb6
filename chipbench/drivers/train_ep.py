"""Training one chip's share of an expert-parallel MoE model, closed loop:
``Trainer.run`` inside one Compute-Unit on an ``hpc`` pilot for the whole
window, a step at a time until the deadline.

The configuration (``chipbench/model_mla_moe.py``) holds a share of each
MoE layer's experts, so the rig is ``chipbench.training.TrainRig`` with
this layout's config, layout check and weights, and first steps that
read each layer's and held expert's slice; the Trainer, the feed, the
stepping and the state made on the device from the seed are the dense
rig's.

Checked: the Trainer's first steps (set-up, the same object and feed the
window drives) against ``reference/mla_moe.py``: ``grad_gap`` and
``delta_gap`` (``checks.train_gaps``) over every layer's slice of each
leaf and every held expert's slice of the expert leaves; every CU DONE.
Read, not compared: the loss gap; the share of the held experts' pairs
the capacity dropped, and the pairs routed to them against their even
share of the router's 64 outputs (``held_load``), in the compared steps
and in the window.
Counters: ``moe_pairs`` and ``moe_dropped``, the program's own, summed
over the window's steps.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

from chipbench import (checks, data, harness, model_mla_moe, sessions,
                       training)
from chipbench.drivers import train as dense
from chipbench.training import FIRST_STEPS


class EPRig(training.TrainRig):
    """``training.TrainRig`` over the MoE layout: its own config, layout
    check and weights, and first steps that read per-slice norms."""

    def __init__(self, cj: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import transformer
        from repro.optim import adamw
        from repro.train.step import make_train_state
        from repro.train.trainer import Trainer
        self.cj, self.seed = cj, seed
        t = traffic["train"]
        self.batch, self.seq = int(t["batch"]), int(t["seq"])
        self.tokens_per_step = self.batch * self.seq
        self.opt = opt = dict(traffic["optimizer"])
        self.mcfg = model_mla_moe.program_config(cj)
        self.key = data.key(seed, 1)
        self.tr = Trainer(
            self.mcfg, mesh, global_batch=self.batch, seq=self.seq,
            hyper=adamw.Hyper(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                              eps=opt["eps"],
                              weight_decay=opt["weight_decay"],
                              clip_norm=opt["clip_norm"]),
            warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
            seed=seed & 0x7FFFFFFF)
        model_mla_moe.check_layout(cj, jax.eval_shape(
            lambda: transformer.init_params(self.mcfg, jax.random.key(0))))
        self.tr.pipeline = data.TokenFeed(
            seed, self.batch, self.seq, cj["vocab_size"],
            NamedSharding(mesh, P()))
        cfg = self.mcfg
        with jax.set_mesh(mesh):
            self.tr.state = jax.jit(
                lambda k: make_train_state(cfg, model_mla_moe.make_params(cj, k)),
                out_shardings=self.tr.state_shardings)(self.key)
        self.prog: Dict[str, Any] = {}

    def first_steps(self) -> None:
        """Steps 1..3 through ``Trainer.run``, reading the state after
        the first (the optimizer's m is (1 - b1) times the clipped
        gradient) and after the third (the parameters' change), each
        leaf by layer and held expert."""
        import jax
        import jax.numpy as jnp
        self.run_to(1)
        b1 = self.opt["b1"]
        g = {k: v / (1.0 - b1) for k, v in model_mla_moe.host_norms(
            model_mla_moe.slice_norms(self.tr.state["opt"]["m"])).items()}
        self.run_to(FIRST_STEPS)
        flat = jax.tree_util.tree_flatten_with_path(self.tr.state["params"])[0]
        leaves = []
        for p, x in flat:
            p0 = model_mla_moe.make_leaf(self.cj, self.key,
                                         model_mla_moe.path_str(p))
            leaves.append(x.astype(jnp.float32) - p0.astype(jnp.float32))
        delta = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.tr.state["params"]), leaves)
        d = model_mla_moe.host_norms(model_mla_moe.slice_norms(delta))
        hist = self.tr.history[:FIRST_STEPS]
        self.prog = {"losses": [h["loss"] for h in hist],
                     "g_norms": g, "d_norms": d,
                     "pairs": [{"kept": h["moe_pairs"],
                                "routed": h["moe_pairs"] + h["moe_dropped"]}
                               for h in hist]}


def dropped_share(pairs: List[Dict[str, float]]) -> float:
    routed = sum(p["routed"] for p in pairs)
    return (routed - sum(p["kept"] for p in pairs)) / routed if routed else 0.0


def held_load(cj: Dict[str, Any], tokens: int,
              pairs: List[Dict[str, float]]) -> float:
    """Pairs routed to the held experts a step, over their even share of
    all pairs (tokens x top-k x held / router outputs, every MoE layer):
    1 where the router spreads evenly over the 64, above 1 where it
    favours the held ids."""
    layers = cj["num_hidden_layers"] - cj["first_k_dense_replace"]
    even = (tokens * cj["num_experts_per_tok"] * cj["n_routed_experts"]
            / cj["router_experts"] * layers)
    return sum(p["routed"] for p in pairs) / (len(pairs) * even) if pairs else 0.0


def setup(ctx: harness.Context) -> Dict[str, Any]:
    import jax
    traffic = ctx.cell.traffic
    session, pilots = sessions.open_session(ctx.devices, traffic["pilots"])
    hpc = pilots["hpc"]
    rig = EPRig(ctx.cell.config, traffic, ctx.seed, hpc.mesh())
    rig.first_steps()
    rig.run_to(rig.step + int(traffic["warm_steps"]))
    jax.effects_barrier()
    return {"session": session, "hpc": hpc, "rig": rig}


def window(ctx: harness.Context, st: Dict[str, Any], deadline: float) -> None:
    """The dense cell's window (``drivers/train.py``), then the program's
    expert counters summed over the window's steps."""
    first = st["rig"].step
    dense.window(ctx, st, deadline)
    hist = st["rig"].tr.history[first:]
    st["pairs"] = [{"kept": h["moe_pairs"],
                    "routed": h["moe_pairs"] + h["moe_dropped"]} for h in hist]
    c = ctx.rec.counters
    c["moe_pairs"] = sum(h["moe_pairs"] for h in hist)
    c["moe_dropped"] = sum(h["moe_dropped"] for h in hist)


release = dense.release


def check(ctx: harness.Context, st: Dict[str, Any]) -> List[harness.Check]:
    from chipbench.reference import mla_moe
    rig = st["rig"]
    ref = mla_moe.train_steps(rig.cj, rig.key, rig.batches(), rig.opt)
    gaps = checks.train_gaps(rig.prog, ref)
    lim = checks.limits(rig.cj)
    win = st.get("pairs", [])
    load = lambda pairs: held_load(rig.cj, rig.tokens_per_step, pairs)
    readings = dict(
        gaps, dropped_share_checked=dropped_share(rig.prog["pairs"]),
        dropped_share_ref=dropped_share(ref["pairs"]),
        dropped_share_window=dropped_share(win),
        held_load_checked=load(rig.prog["pairs"]),
        held_load_window=load(win), held_load_window_first=load(win[:1]),
        held_load_window_last=load(win[-1:]))
    for k in sorted(set(readings) - set(lim)):
        ctx.say(f"reading {k} {readings[k]!r} (not compared)")
    out = [harness.Check(k, gaps[k], lim[k]) for k in lim]
    out.append(harness.Check("cu_not_done", float(st["cu_not_done"]), 0.0))
    return out


def readings(cell, devices, seed: int, control: bool) -> List[Dict[str, Any]]:
    """Calibration rows (``calibrate.py``): the gaps of the program's first
    steps; with ``control`` also those of the fp8 control and of each
    planted fault (the reference with the fault against the reference)."""
    import gc
    from chipbench.reference import mla_moe
    from repro import compat
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    rig = EPRig(cell.config, cell.traffic, seed, mesh)
    rig.first_steps()
    rig.release()
    del rig.tr
    gc.collect()
    t0 = time.monotonic()
    args = (rig.cj, rig.key, rig.batches(), rig.opt)
    ref = mla_moe.train_steps(*args)
    row = {"part": "train", "ref_s": time.monotonic() - t0,
           "program": checks.train_gaps(rig.prog, ref),
           "dropped_share": dropped_share(rig.prog["pairs"]),
           "dropped_share_ref": dropped_share(ref["pairs"]),
           "losses": rig.prog["losses"], "ref_losses": ref["losses"]}
    if control:
        row["control_fp8"] = checks.train_gaps(
            mla_moe.train_steps(*args, quant="fp8"), ref)
        for fault in mla_moe.FAULTS[1:]:
            row[f"fault_{fault}"] = checks.train_gaps(
                mla_moe.train_steps(*args, fault=fault), ref)
        row["fault_half_batch"] = checks.train_gaps(mla_moe.train_steps(
            *args, batch_rows=slice(0, rig.batch // 2)), ref)
    return [row]
