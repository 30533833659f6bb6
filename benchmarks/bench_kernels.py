"""Kernel micro-benchmarks: pallas vs jnp reference wall time.

The kernels run compiled on a TPU and interpreted on the CPU; CPU
(interpret-mode) timings are NOT TPU-indicative — the
point of these rows is regression tracking of the wrapper overheads and
a correctness-at-size spot check; TPU timing comes from the roofline.

    PYTHONPATH=src python benchmarks/bench_kernels.py [--smoke] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, reps: int = 3) -> float:
    # warm up (compile) and block on EVERY output shape — the old
    # tuple-only block let single-array outputs start the clock with
    # the compile still in flight
    jax.block_until_ready(fn(*args))
    t0 = time.monotonic()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.monotonic() - t0) / reps


def run(reps: int = 3) -> List[Dict]:
    rows = []
    rng = np.random.default_rng(0)

    # kmeans assignment at the paper's mid scenario (scaled)
    from repro.kernels.kmeans import ops as km_ops, ref as km_ref
    p = jnp.asarray(rng.normal(size=(8192, 3)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(64, 3)).astype(np.float32))
    for name, fn in (("pallas", km_ops.assign),
                     ("ref", jax.jit(km_ref.assign))):
        dt = _time(fn, p, c, reps=reps)
        rows.append({"name": f"kernels/kmeans_assign_8192x64/{name}",
                     "us_per_call": dt * 1e6, "derived": ""})

    # flash attention 1k sequence
    from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
    q = jnp.asarray(rng.normal(size=(1, 1024, 4, 64)).astype(np.float32))
    for name, fn in (("pallas", lambda a: fa_ops.attention(a, a, a)),
                     ("ref", jax.jit(lambda a: fa_ref.attention(a, a, a)))):
        dt = _time(fn, q, reps=reps)
        rows.append({"name": f"kernels/flash_attn_1k/{name}",
                     "us_per_call": dt * 1e6, "derived": ""})

    # mamba scan
    from repro.kernels.mamba_scan import ops as ms_ops, ref as ms_ref
    B, S, di, st = 2, 256, 64, 16
    a = jnp.asarray(rng.uniform(0.8, 0.99, (B, S, di, st)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(B, S, di, st)).astype(np.float32)) * .1
    C = jnp.asarray(rng.normal(size=(B, S, st)).astype(np.float32))
    h0 = jnp.zeros((B, di, st), jnp.float32)
    for name, fn in (("pallas", lambda *xs: ms_ops.scan(*xs, bdi=64, bs=16)),
                     ("ref", jax.jit(ms_ref.scan))):
        dt = _time(fn, a, b, C, h0, reps=reps)
        rows.append({"name": f"kernels/mamba_scan_256/{name}",
                     "us_per_call": dt * 1e6, "derived": ""})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="fewer reps for CI; also writes --json")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write results as JSON (default "
                         "BENCH_kernels.json with --smoke)")
    args = ap.parse_args()
    rows = run(reps=2 if args.smoke else 3)
    json_path = args.json or ("BENCH_kernels.json" if args.smoke else None)
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"results": rows}, f, indent=2)
        print(f"wrote {json_path}")
    print(f"{'row':<42} {'us/call':>12}")
    print("-" * 55)
    for r in rows:
        print(f"{r['name']:<42} {r['us_per_call']:>12.1f}")


if __name__ == "__main__":
    main()
