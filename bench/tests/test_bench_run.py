"""The measuring path refuses to run without a TPU, or without the program."""

import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "win_k20_sat", "--seed", "5", "--seconds", "1", "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run(ROOT, env)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "repro" in out.stderr  # the program is not there
