"""Tests of the chip benchmark's own code, on the CPU at tiny sizes.

Run from the root of a checkout:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
for p in (BENCH_DIR, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
