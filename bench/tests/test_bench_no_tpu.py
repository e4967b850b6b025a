"""The benchmark refuses to run without a TPU: a non-zero exit and no
result line, also in a directory that holds only the benchmark."""

import os
import shutil
import subprocess
import sys

from benchlib import cells

ROOT = os.path.dirname(cells.BENCH_DIR)
WORKLOAD = cells.load_benchmark(ROOT)["workloads"][0]["name"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOAD,
         "--seed", str(2 ** 40 + 1), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_refuses_without_tpu():
    proc = _run(ROOT)
    _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copytree(cells.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    _no_result(_run(tmp_path))
