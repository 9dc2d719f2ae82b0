"""Without a TPU the benchmark exits non-zero and prints no result; so
it does in a directory that holds only BENCHMARK.json and bench/."""
import os
import shutil
import subprocess
import sys

from bench import harness


def _run(root, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-decode-batch",
         "--seed", str(2 ** 40 + 3), "--seconds", "10", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_tpu():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    env = {"PYTHONPATH": ""}
    p = _run(str(tmp_path), env)
    assert p.returncode != 0
    assert "{" not in p.stdout
