"""The training path both internlm2 cells drive: one ``Trainer`` built at
set-up, its state made on the device from the seed in one call, fed
``chipbench.data`` batches, driven through its first steps (which the
reference follows) and then handed to the window unchanged."""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import checks, data, model

FIRST_STEPS = 3


class TrainRig:
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
        self.mcfg = model.program_config(cj)
        self.key = data.key(seed, 1)
        self.tr = Trainer(
            self.mcfg, mesh, global_batch=self.batch, seq=self.seq,
            hyper=adamw.Hyper(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                              eps=opt["eps"],
                              weight_decay=opt["weight_decay"],
                              clip_norm=opt["clip_norm"]),
            warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
            seed=seed & 0x7FFFFFFF)
        model.check_layout(cj, jax.eval_shape(
            lambda: transformer.init_params(self.mcfg, jax.random.key(0))))
        self.tr.pipeline = data.TokenFeed(
            seed, self.batch, self.seq, cj["vocab_size"],
            NamedSharding(mesh, P()))
        cfg = self.mcfg
        with jax.set_mesh(mesh):
            self.tr.state = jax.jit(
                lambda k: make_train_state(cfg, model.make_params(cj, k)),
                out_shardings=self.tr.state_shardings)(self.key)
        self.prog: Dict[str, Any] = {}

    @property
    def step(self) -> int:
        return len(self.tr.history)

    def run_to(self, n: int) -> None:
        """Trainer.run up to global step ``n`` (the window's call)."""
        self.tr.run(n, log_every=0)

    def first_steps(self) -> None:
        """Steps 1..3 through ``Trainer.run``, reading the state after
        the first (the optimizer's m is (1 - b1) times the clipped
        gradient) and after the third (the parameters' change)."""
        import jax
        import jax.numpy as jnp
        from chipbench.model import make_leaf, path_str
        self.run_to(1)
        b1 = self.opt["b1"]
        flat = jax.tree_util.tree_flatten_with_path(self.tr.state["opt"]["m"])[0]
        g = {path_str(p): float(jnp.linalg.norm(x.ravel())) / (1.0 - b1)
             for p, x in flat}
        self.run_to(FIRST_STEPS)
        flat = jax.tree_util.tree_flatten_with_path(self.tr.state["params"])[0]
        d = {}
        for p, x in flat:
            pth = path_str(p)
            p0 = make_leaf(self.cj, self.key, pth)
            d[pth] = float(jnp.linalg.norm(
                (x.astype(jnp.float32) - p0.astype(jnp.float32)).ravel()))
        self.prog = {"losses": [h["loss"] for h in self.tr.history[:FIRST_STEPS]],
                     "g_norms": g, "d_norms": d}

    def release(self) -> None:
        self.tr.state = None

    def batches(self) -> List[Dict[str, np.ndarray]]:
        return [data.tokens_at(self.seed, s, self.batch, self.seq,
                               self.cj["vocab_size"])
                for s in range(FIRST_STEPS)]


def readings(cj: Dict[str, Any], traffic: Dict[str, Any], seed: int, mesh,
             control: bool) -> Dict[str, Any]:
    """Calibration: the gaps of a rig's first steps against the reference;
    with ``control`` also those of the control (the reference with fp8
    matmul operands) and of a fault (half of every batch left out)."""
    import gc
    import time
    from chipbench.reference import dense_gqa
    rig = TrainRig(cj, traffic, seed, mesh)
    rig.first_steps()
    rig.release()
    del rig.tr
    gc.collect()
    t0 = time.monotonic()
    ref = dense_gqa.train_steps(rig.cj, rig.key, rig.batches(), rig.opt)
    row = {"ref_s": time.monotonic() - t0,
           "program": checks.train_gaps(rig.prog, ref),
           "losses": rig.prog["losses"], "ref_losses": ref["losses"]}
    if control:
        ctl = dense_gqa.train_steps(rig.cj, rig.key, rig.batches(), rig.opt,
                                    quant="fp8")
        row["control_fp8"] = checks.train_gaps(ctl, ref)
        half = dense_gqa.train_steps(rig.cj, rig.key, rig.batches(), rig.opt,
                                     batch_rows=slice(0, 1))
        row["fault_half_batch"] = checks.train_gaps(half, ref)
    return row


def reference_checks(rig: TrainRig, say) -> List[Any]:
    """The rig's first steps against the reference: a Check for each gap
    the configuration holds to a limit; the others are only printed."""
    from chipbench import harness
    from chipbench.reference import dense_gqa
    ref = dense_gqa.train_steps(rig.cj, rig.key, rig.batches(), rig.opt)
    gaps = checks.train_gaps(rig.prog, ref)
    lim = checks.limits(rig.cj)
    for k in sorted(set(gaps) - set(lim)):
        say(f"reading {k} {gaps[k]!r} (not compared)")
    return [harness.Check(k, gaps[k], lim[k]) for k in lim]
