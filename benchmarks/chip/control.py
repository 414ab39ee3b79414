"""The control of the solve cell's ``correct``: it has to come out as not
correct.

The solve cell states float32 (``configs/dblp_1c.json``).  The control
is the cell's own run, the same timed path at the same size, with the
precision of its Laplacian products lowered one step, to bfloat16: every
product of L that the served solve makes (the node-blocked Pallas SpMM
on the chip, the segment matvec elsewhere) takes its panel operand
rounded to bfloat16 and sums in float32.  That is the step a later change
to the edge SpMM could be tempted to take (the gathered rows in half the
bytes).  The rounding works on the float's bits (:func:`round_bf16`), so
no compiler can fold the round trip away.  The run goes through
``harness.run_cell``, so the cell's own comparison (``sin_last`` against
the configuration's limit) decides it.

    python3 benchmarks/chip/control.py --workload dblp.solve \\
        --seeds 11,12,13 [--seconds 51]

Runs where JAX runs (the chip, for the readings in PERF.md), each seed
in turn in one process; the benchmark's own runs never call it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def round_bf16(x):
    """``x`` (float32) rounded to the nearest bfloat16, ties to even, and
    held in float32: integer operations on the bits, which no compiler
    treats as a precision it may keep in excess."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    lsb = (bits >> 16) & jnp.uint32(1)
    bits = (bits + jnp.uint32(0x7FFF) + lsb) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def lowered_precision_run(workload: str, seed: int, seconds: float, *,
                          check_chip: bool = True, **overrides) -> dict:
    """One run of a solve cell with every Laplacian product of the tick
    programs taking its panel rounded to bfloat16; returns the result
    line."""
    import harness
    from repro.core import laplacian as lap
    from repro.kernels.edge_spmm import ops as es_ops

    spmm, matvec = es_ops.edge_spmm_blocked, lap.edge_matvec_arrays

    def lowered_spmm(nb, v, alpha=1.0, beta=0.0, *, interpret=False):
        # alpha * L(round(v)) + beta * v: only the product is lowered
        return (spmm(nb, round_bf16(v), alpha=alpha, beta=0.0,
                     interpret=interpret) + beta * v)

    def lowered_matvec(src, dst, weight, v):
        return matvec(src, dst, weight, round_bf16(v))

    es_ops.edge_spmm_blocked = lowered_spmm
    lap.edge_matvec_arrays = lowered_matvec
    try:
        return harness.run_cell(workload, seed, seconds, False,
                                check_chip=check_chip, **overrides)
    finally:
        es_ops.edge_spmm_blocked = spmm
        lap.edge_matvec_arrays = matvec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="dblp.solve")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    import harness

    harness.enable_compile_cache()
    harness.require_chip(1)
    for seed in map(int, args.seeds.split(",")):
        r = lowered_precision_run(args.workload, seed, args.seconds)
        print(f"control {seed}: correct={r['correct']} "
              f"checks={json.dumps(r['checks'])}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
