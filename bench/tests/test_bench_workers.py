"""A cell whose chips belong to worker processes that its driver starts:
start-up counts the chips without opening a JAX backend, and the result
line's device, memory and trace are the workers', never this process's."""
import dataclasses
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from sasabench import cells, harness  # noqa: E402
from test_bench_trace import NO_SPAN, ORIGIN  # noqa: E402

START = """
import json, os, sys
sys.path[:0] = [{bench!r}, {src!r}]
from repro import compat
compat.tpu_chips_on_host = lambda: {on_bus}
import jax

def refuse(*args, **kwargs):
    raise AssertionError("a JAX backend was asked for")

jax.devices = jax.local_devices = refuse
from jax._src import xla_bridge
from sasabench import startup

ok = startup.start({chips}, "test", 0.0, print, workers_hold_chips=True)
print(json.dumps({{"ok": ok, "backend": xla_bridge.backends_are_initialized(),
                  "cache": os.environ["JAX_COMPILATION_CACHE_DIR"]}}))
"""


@pytest.mark.parametrize("on_bus, chips, ok", [(4, 4, True), (8, 4, True),
                                               (2, 4, False), (0, 1, False)])
def test_startup_counts_the_workers_chips_with_no_backend(on_bus, chips, ok):
    """In a fresh process, as ``bench/run.py`` runs: the chips are those on
    the host's bus, too few are refused, and no backend is ever opened."""
    code = START.format(bench=str(ROOT / "bench"), src=str(ROOT / "src"),
                        on_bus=on_bus, chips=chips)
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="tpu"))
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out == {"ok": ok, "backend": False,
                   "cache": str(ROOT / ".jax_cache")}
    if ok:
        assert "counted with no JAX backend here" in proc.stdout
    else:
        assert f"needs {chips} TPU chip(s); {on_bus} TPU chip(s)" in proc.stderr


WORKERS = [{"chip": str(i), "pid": 100 + i, "memory_peak_bytes": 10 + i}
           for i in range(4)]
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
          "workers": WORKERS}


def fleet_cell(device, trace_files=()):
    """A cell whose kind's workers hold four chips; its driver reports
    ``device`` and ``trace_files`` with the window's host-clock stamps."""

    def run(cell, seed, seconds, trace, t_start, log):
        with harness.traced(trace, cell.workers_hold_chips) as tr:
            pass
        return harness.Outcome(
            attempted=3, failed=0, end_to_end={"setup_s": 2.5},
            compared={"max_abs_err": 0.0}, memory_peak_bytes=None,
            counters={}, work={}, window=(0.0, seconds),
            trace_files=list(trace_files),
            window_ns=(ORIGIN + 1000, ORIGIN + 11000) if trace_files
            else tr.window_ns,
            device=device)

    kind = types.SimpleNamespace(WORKERS_HOLD_CHIPS=True, run=run)
    serve = cells.load_cell("jacobi2d.serve")
    return dataclasses.replace(
        serve, name="jacobi2d.fleet", chips=4, kind=kind,
        end_to_end=[{"name": "setup_s", "unit": "s"}],
        per_layer=[{"name": "device_idle.fleet", "unit": "%"}],
        readers={"device_idle.fleet": serve.readers["device_idle.serve"]})


@pytest.fixture
def off_this_process(monkeypatch):
    """Fail on any look at this process's devices or profiler; record the
    device kind the peaks are asked for."""
    import jax

    def refuse(*args, **kwargs):
        raise AssertionError("this process's devices were asked for")

    asked = []
    monkeypatch.setattr(harness, "device_info", refuse)
    monkeypatch.setattr(harness, "memory_peak_bytes", refuse)
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(harness, "load_peaks",
                        lambda kind: asked.append(kind) or {})
    return asked


def test_kinds_say_who_holds_the_chips():
    assert fleet_cell(DEVICE).workers_hold_chips is True
    for name in ("jacobi2d.solve", "jacobi2d.serve"):
        assert cells.load_cell(name).workers_hold_chips is False


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_takes_the_workers_device(off_this_process, trace):
    result = harness.run_cell(fleet_cell(DEVICE), 2**33 + 7, 0.1, trace, 0.0)
    assert result["correct"] is True
    assert result["device"] == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4,
        "memory_peak_bytes": 13, "workers": WORKERS}
    assert off_this_process == (["TPU v5 lite"] if trace else [])
    if trace:
        # no worker trace yet: no device summary, and the reader reads nothing
        assert result["metrics"] == {} and "breakdown" not in result
    else:
        assert result["metrics"] == {"setup_s": {"value": 2.5, "unit": "s"}}


def test_memory_a_worker_does_not_report_is_null(off_this_process, capsys):
    workers = [dict(w, memory_peak_bytes=None) if w["chip"] == "2" else w
               for w in WORKERS]
    result = harness.run_cell(fleet_cell(dict(DEVICE, workers=workers)),
                              2**33 + 8, 0.1, False, 0.0)
    assert result["device"]["memory_peak_bytes"] is None
    assert "a worker reports no device memory" in capsys.readouterr().out


def test_workers_traces_are_reduced_together(off_this_process, tmp_path):
    """Two workers' traces, each where the profiler puts it under a
    directory of its own, reduced over the driver's host-clock window as
    two devices (the counts by hand are ``test_bench_trace``'s), then
    removed."""
    from jax.profiler import ProfileData

    files = []
    for i, origin in enumerate((ORIGIN, ORIGIN - 2000)):
        run_dir = tmp_path / f"worker{i}" / "plugins" / "profile" / "run"
        run_dir.mkdir(parents=True)
        path = run_dir / "host.xplane.pb"
        path.write_bytes(
            ProfileData.text_proto_to_serialized_xspace(NO_SPAN % origin))
        files.append(str(path))
    result = harness.run_cell(fleet_cell(DEVICE, files), 2**33 + 9, 0.1,
                              True, 0.0)
    assert result["device"]["busy_s"] == pytest.approx(4500e-9)
    assert result["device"]["window_s"] == pytest.approx(1e-5)
    assert result["metrics"]["device_idle.fleet"]["value"] == \
        pytest.approx(55.0)
    assert len(result["breakdown"]["idle_gaps"]) == 5
    assert not any((tmp_path / f"worker{i}").exists() for i in range(2))
