"""``bench/run.py`` and its tools measure on a TPU only: off the chip they
exit non-zero and print no result."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def test_run_exits_nonzero_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "jacobi2d.solve",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr


def test_run_refuses_an_unknown_cell():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "no.such.cell",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and "{" not in proc.stdout


@pytest.mark.parametrize("script, args", [
    ("bench/control.py", ["--seeds", "1,2", "--seconds", "1"]),
    ("bench/sweep_knee.py", ["--seed", "1", "--seconds", "1", "--rates", "1"]),
])
def test_tools_run_on_a_tpu_only(script, args):
    """The control and the knee sweep share the benchmark's start-up."""
    proc = subprocess.run(
        [sys.executable, script, "--workload", "jacobi2d.serve", *args],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    assert "{" not in proc.stdout and "TPU" in proc.stderr
