"""The command refuses a machine without a TPU, and a directory that holds
only the benchmark, with a non-zero exit and no result line."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "nemotron-4-15b.chat", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def run_in(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_refuses_cpu():
    p = run_in(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_refuses_bare_benchmark_dir(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
