"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name: its entry in
``BENCHMARK.json``; its configuration ``bench/configs/<config>.json`` with the
plain reference that file names under ``bench/reference/``; its traffic
``bench/traffic/<traffic>.json``, whose ``kind`` picks the driver in
``bench/kinds/``; its correctness limits ``bench/limits/<workload>.json``;
and one reader ``bench/metrics/<metric>.py`` per per-layer metric.

It runs only on the chips the cell asks for and exits non-zero, printing no
result, anywhere else.  ``--rehearse`` is the one exception: the same code
at the tiny sizes of each file's ``rehearsal`` section, on whatever JAX
finds (the CPU here), with the platform named in the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform (CPU rehearsal); never used for measurement")
    args = ap.parse_args(argv)
    out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
                           rehearse=args.rehearse, t_start=T_START)
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
