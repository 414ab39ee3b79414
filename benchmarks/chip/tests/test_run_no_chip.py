"""run.py never falls back to the CPU and prints no result without a
TPU or outside a checkout of the program."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH_DIR, ROOT

ARGS = ["--workload", "dblp.solve", "--seed", "7", "--seconds", "1",
        "--trace", "0"]


def _run(root):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "chip", "run.py"), *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode == 2, p.stderr[-2000:]
    assert not _has_result(p.stdout)
