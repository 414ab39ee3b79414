"""Benchmark harness: one module per paper table/figure.  Prints
``name,us_per_call,derived`` CSV rows (and nothing else on stdout).

Modules with cross-PR perf trajectories (bench_spectral, bench_stream,
bench_kernels, bench_distributed) additionally write machine-readable
``BENCH_<name>.json`` files at the repo root via
:func:`benchmarks.common.write_bench_json`.

``--check`` snapshots the committed BENCH_*.json files before running,
then diffs the freshly written payloads against them
(:func:`benchmarks.common.bench_regressions`) and exits non-zero on a
>25% key-metric regression — the perf-trajectory gate scripts/ci.sh
runs as a non-blocking stage.  ``--only spectral,stream`` restricts the
run to a subset of module tags (the names in the table below).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _snapshot_bench_files() -> dict[str, dict]:
    """The COMMITTED baselines: ``git show HEAD:BENCH_*.json`` when the
    repo is available, so repeated ``--check`` runs on one checkout keep
    diffing against the committed numbers instead of self-healing
    against the previous run's freshly rewritten files; the on-disk
    payload is only the fallback outside a git checkout."""
    import subprocess

    committed = {}
    for path in glob.glob(os.path.join(REPO_ROOT, "BENCH_*.json")):
        fname = os.path.basename(path)
        try:
            blob = subprocess.run(
                ["git", "-C", REPO_ROOT, "show", f"HEAD:{fname}"],
                capture_output=True, text=True, timeout=30)
            if blob.returncode == 0:
                committed[fname] = json.loads(blob.stdout)
                continue
        except (OSError, subprocess.SubprocessError, json.JSONDecodeError):
            pass
        try:
            with open(path) as f:
                committed[fname] = json.load(f)
        except (OSError, json.JSONDecodeError):
            pass
    return committed


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--check", action="store_true",
        help="diff freshly written BENCH_*.json key metrics against the "
             "committed files; exit 2 on a >25% regression")
    parser.add_argument(
        "--only", default=None, metavar="TAGS",
        help="comma-separated module tags to run (e.g. 'stream,spectral')")
    args = parser.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_baselines, bench_cliques, bench_distributed,
                            bench_kernels, bench_linkpred, bench_mdp,
                            bench_serve, bench_series_degree, bench_spectral,
                            bench_stream, bench_transforms, bench_walks)
    from benchmarks.common import bench_regressions
    mods = [
        ("spectral", bench_spectral),
        ("stream", bench_stream),
        ("serve", bench_serve),
        ("distributed", bench_distributed),
        ("table2", bench_transforms),
        ("fig2_3", bench_mdp),
        ("fig4", bench_cliques),
        ("fig5", bench_linkpred),
        ("fig6", bench_series_degree),
        ("sec4.3", bench_walks),
        ("kernels", bench_kernels),
        ("appB_baselines", bench_baselines),
    ]
    if args.only:
        only = {t.strip() for t in args.only.split(",") if t.strip()}
        unknown = only - {t for t, _ in mods}
        if unknown:
            parser.error(f"unknown --only tags {sorted(unknown)}")
        mods = [(t, m) for t, m in mods if t in only]

    committed = _snapshot_bench_files() if args.check else {}

    print("name,us_per_call,derived")
    failures = 0
    for tag, mod in mods:
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us},{derived}", flush=True)
        except Exception as e:  # keep the harness robust
            failures += 1
            print(f"{tag}/ERROR,0,{type(e).__name__}:{e}", flush=True)

    if args.check:
        regressions = []
        for fname, old in sorted(committed.items()):
            path = os.path.join(REPO_ROOT, fname)
            try:
                with open(path) as f:
                    new = json.load(f)
            except (OSError, json.JSONDecodeError):
                continue  # this run did not rewrite the file
            if new == old:
                continue  # not re-run (or byte-identical): nothing to diff
            for msg in bench_regressions(old, new):
                regressions.append(f"{fname}: {msg}")
        if regressions:
            print("BENCH REGRESSIONS (>25% on key metrics):",
                  file=sys.stderr)
            for msg in regressions:
                print(f"  {msg}", file=sys.stderr)
            sys.exit(2)
        print("bench check: no key-metric regressions", file=sys.stderr)

    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
