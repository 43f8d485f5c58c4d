"""Readings that set a cell's correctness limits; not part of a timed run.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 15

For each seed, in this one process, runs the cell as ``run.py`` does and
prints one JSON line with what it compared: the program's numbers, and the
control's: the plain reference in the program's place, computed with every
matrix product in scaled float8 (the precision below the configuration's
bfloat16), read at the token it puts first.  A limit lies above the largest
program reading over a dozen seeds and below the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--no-control", action="store_true", help="the program's readings only")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    for s in args.seeds.split(","):
        t0 = time.perf_counter()
        out = harness.run_cell(ROOT, args.workload, int(s), args.seconds, False,
                               rehearse=args.rehearse, t_start=t0, control=not args.no_control)
        print(json.dumps({"seed": int(s), "checks": out["checks"], "correct": out["correct"],
                          "metrics": out["metrics"], "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
