#!/usr/bin/env bash
# Fast CI lane: everything except the `slow`-marked system/train suites.
# Full tier-1 verify remains `PYTHONPATH=src python -m pytest -x -q`.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# Stochastic probing suite first (fixed PRNG seeds — deterministic, and
# cheap): a regression in the spectral probes invalidates every
# downstream auto-tuned result, so fail fast on it.
python -m pytest -q -m "stochastic and not slow"
# Kernel/backend equivalence next (interpret-mode pallas == segment):
# a kernel regression silently corrupts every pallas-backend solve.
python -m pytest -q -m "pallas and not slow"
# Distributed lane: a SUBPROCESS with 8 virtual CPU devices (the flag
# must be set before jax initializes, hence the fresh interpreter) so
# the shard_map collectives — per-shard matvecs, psum'd series
# programs, sharded capacity-class ticks — actually cross device
# boundaries instead of degenerating to a 1x1 mesh.
# (forced flag LAST: XLA parses duplicate flags last-wins, so an
# inherited device-count flag must not override the lane's 8)
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
    python -m pytest -q -m "distributed and not slow"
python -m pytest -q -m "not slow and not stochastic and not pallas and not distributed" "$@"
# Serving smoke (BLOCKING): boot `python -m repro.serve` as a real
# subprocess, drive a short HTTP load through admit/push/labels/summary,
# assert a sane p99 and a clean SIGTERM shutdown — the process-level
# contract no in-process test exercises.
python -m benchmarks.bench_serve --http-smoke
# Perf-trajectory gate (BLOCKING for stream,serve): re-run the
# streaming + serving benches and diff their freshly written
# BENCH_*.json key metrics against the committed files.  These two
# lanes have been regression-quiet across PRs 6-9, so a >25% drop (or
# a crashed bench module) now fails CI.  Rows with committed
# us_per_call=0 are exempt by design: interpret-mode pallas rows
# (CPU kernel emulation, not real timings) and the serve ingest walls
# (thread-interleaving makes even best-of-3 walls bimodal; bench_serve
# gates via its internal correctness asserts instead) — which keeps
# the blocking gate on the stable jit-compute-bound stream numbers.
python -m benchmarks.run --check --only stream,serve
# Skew + weak-scaling rows (NON-BLOCKING): the kernels/distributed
# benches carry the CSR-vs-uniform padded-work rows and the
# fused-collective model-tick rows; their wall numbers are still noisy
# on shared runners, so regressions warn without failing CI.  The
# distributed bench runs in one process over the first d devices it
# sees, so this stage provides 8 virtual CPU devices.
# run.py exits 2 for a metric regression, 1 for a crashed bench module:
# word the warning accordingly so a broken bench is not mistaken for
# wall-clock noise.
bench_status=0
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
    python -m benchmarks.run --check --only kernels,distributed || bench_status=$?
if [ "$bench_status" -eq 2 ]; then
    echo "[ci] WARNING: kernels/distributed bench --check reported a >25% perf regression (non-blocking)"
elif [ "$bench_status" -ne 0 ]; then
    echo "[ci] WARNING: kernels/distributed bench --check FAILED TO RUN (exit $bench_status) — a bench module crashed (non-blocking)"
fi
