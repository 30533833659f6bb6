#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 12 --controls 3 --out F

For each seed, the cell's driver (``readings`` of the driver its traffic
mix names, at the cell's configuration and sizes) gives the gaps of the
program's answers against the reference; on the first ``--controls``
seeds also those of the control and of the faults the driver plants in
the reference.  The benchmark's runs never call this.  Each reading is
one JSON line, appended to ``--out`` and printed.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=4_000_000_000)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.load_cell(args.workload, root)
    if not hasattr(cell.driver, "readings"):
        print(f"driver {cell.traffic['driver']!r} gives no readings",
              file=sys.stderr)
        return 2
    import jax
    harness.enable_compile_cache(root)
    devices = jax.devices()[:cell.chips]
    with open(args.out, "a") as f:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            for row in cell.driver.readings(cell, devices, seed,
                                            i < args.controls):
                line = json.dumps(dict(workload=args.workload, seed=seed,
                                       **row))
                f.write(line + "\n")
                f.flush()
                print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
