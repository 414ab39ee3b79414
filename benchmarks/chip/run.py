"""Chip benchmark of the served clustering path: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine whose JAX sees a TPU.  The
cells, configurations and metrics are in ``BENCHMARK.json``; see
``harness.py`` for how their files are found.  The last line of stdout
is the result as one JSON object; the last lines of stderr give each
number compared beside its limit.  Without a TPU, with fewer chips than
the cell asks for, or outside a checkout of the program, the run exits
2 and prints no result.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"run.py: no program (src/repro) in the checkout at {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    import harness

    cache_dir = harness.enable_compile_cache()
    try:
        harness.require_chip(1)
        print(f"device: {harness.device_info()}", flush=True)
        print(f"compile_cache={cache_dir}", flush=True)
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
