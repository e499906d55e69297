"""``bench/run.py`` and its tools measure on a TPU only: off the chip they
exit non-zero and print no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

WORKER_KIND = '''
import contextlib

WORKERS_HOLD_CHIPS = True


def run(cell, seed, seconds, trace, t_start, log):
    raise AssertionError("start-up lets no run through off the TPU")


@contextlib.contextmanager
def control(cell):
    yield
'''


def worker_checkout(root: Path) -> Path:
    """A checkout with one more cell, ``jacobi2d.fleet`` on four chips,
    whose kind's worker processes hold the chips."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "jacobi2d.fleet", "config": "jacobi2d-9720x1024",
        "traffic": "fleet", "chips": 4, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "traffic" / "fleet.json").write_text('{"kind": "fleet"}')
    (root / "bench" / "kinds" / "fleet.py").write_text(WORKER_KIND)
    (root / "bench" / "limits" / "jacobi2d.fleet.json").write_text(
        '{"max_abs_err": {"limit": 1e-4}}')
    return root


@pytest.mark.parametrize("workload", ["jacobi2d.solve", "jacobi2d.fleet"])
def test_run_exits_nonzero_off_the_tpu(workload, tmp_path):
    """Also where the cell's chips belong to worker processes, and start-up
    counts them on the host's bus without JAX."""
    root = ROOT if workload == "jacobi2d.solve" else worker_checkout(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120,
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
