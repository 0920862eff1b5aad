"""The entry refuses a host without a TPU, and a directory without the
program, printing no result."""

import os
import shutil
import subprocess
import sys

from bench_tiny import ROOT


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fit_uhd_mnist60k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_host_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "no TPU" in r.stderr, r.stderr[-2000:]


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = _run(tmp_path)
    assert r.returncode != 0 and '"correct"' not in r.stdout
    assert "not in this checkout" in r.stderr, r.stderr[-2000:]
