"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Runs the reduced (smoke) config of the selected architecture by default,
the full one with ``--full-config``. The training job executes as a gang-scheduled Compute-Unit on a Pilot (Mode-I-ready:
spawn an analytics cluster next to it; see examples/hybrid_pipeline.py).
"""
from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.core import PilotDescription, PilotManager, ComputeUnitDescription
from repro.launch.cache import enable_compile_cache
from repro.optim import adamw
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=configs.names())
    ap.add_argument("--full-config", action="store_true",
                    help="train the full architecture config (default: "
                         "its reduced smoke config)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--n-chips", type=int, default=len(jax.devices()))
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.get(args.arch) if args.full_config else configs.get_smoke(args.arch)
    pm = PilotManager()
    pilot = pm.submit(PilotDescription(n_chips=args.n_chips, tp=args.tp,
                                       name=f"train-{args.arch}"))
    print(f"pilot {pilot.uid} active on {len(pilot.devices)} chips "
          f"(startup {pilot.startup_s()*1e3:.1f} ms)")

    def job(mesh=None):
        trainer = Trainer(cfg, mesh, global_batch=args.batch, seq=args.seq,
                          hyper=adamw.Hyper(lr=args.lr),
                          n_microbatches=args.microbatches,
                          ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
        return trainer.run(args.steps)

    cu = pilot.submit(ComputeUnitDescription(
        fn=job, n_chips=args.n_chips, gang=True, tag="train",
        memory_bytes=0))
    history = cu.wait(timeout=3600)
    print(f"done: {len(history)} steps, final loss {history[-1]['loss']:.4f} "
          f"(CU overhead {cu.overhead_s()*1e3:.1f} ms)")
    pm.shutdown()


if __name__ == "__main__":
    main()
