#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared with its
limit).  The same checks are the last lines of standard error.  Without
a TPU whose kind ``chipbench/peaks.py`` knows, or with fewer chips than
the cell asks for, it prints no result and exits 2.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness, peaks
    try:
        code, _ = harness.run(args.workload, args.seed, args.seconds,
                              bool(args.trace), root=ROOT, t_start=T_START)
    except (harness.NoChip, peaks.UnknownDevice) as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    return code


if __name__ == "__main__":
    sys.exit(main())
