"""A run that finds no TPU, or no program, fails and prints no result."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from _tiny import CHIP, REPO

ARGS = ["--workload", "kddcup-k500.reseed", "--seed", "3000000000",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "bench.py"),
         *ARGS], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)


def _result_lines(out: str):
    return [ln for ln in out.splitlines() if ln.lstrip().startswith("{")]


def test_run_without_a_tpu_fails_without_a_result_line():
    proc = _run(REPO)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
    assert "not a TPU" in proc.stderr


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
