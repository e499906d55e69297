"""The benchmark's files: every cell, configuration, traffic mix, limit
and reader is found by name and holds what the harness and the contract
need; a file dropped into its directory is found without editing code."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from sasabench import cells, harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in BENCH[key]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s", "gcell_s"}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS)
        for cell in m["workloads"]:
            reported = {e["name"] for e in cells.cell_metrics(BENCH, cell)[0]}
            assert m["moves"] in reported, (m["name"], cell)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name_and_valid(name):
    cell = cells.load_cell(name)
    assert callable(cell.kind.run) and callable(cell.kind.control)
    assert callable(cell.step)
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    assert all(callable(r) for r in cell.readers.values())
    assert cell.per_layer, "every cell reports a per-layer metric"
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    for key, entry in cell.limits.items():
        assert entry["limit"] >= 0, key
    assert "max_abs_err" in cell.limits
    cfg = cell.config
    assert set(cfg["reduced"]) <= set(cfg) and cfg["assumed"]
    assert all(len(shape) == 2 for shape in cfg["shapes"].values())
    shares = cell.traffic.get("shares", {})
    assert set(shares) <= set(cfg["shapes"])
    assert all(share > 0 for share in shares.values())


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_ops_per_cell_matches_the_dsl(conf):
    """The roofline's work count is the published stencil's operations as
    written: the count over today's parsed DSL tree, before the IR
    optimizer rewrites it."""
    from repro.core import dsl

    cfg = json.loads((ROOT / conf["file"]).read_text())
    spec = dsl.parse(cfg["stencil"]["dsl"])
    assert spec.ops_per_cell == cfg["stencil"]["ops_per_cell"]
    assert spec.num_inputs == cfg["stencil"]["inputs"]
    assert spec.itemsize == cfg["stencil"]["itemsize"]
    assert spec.iterations == cfg["iterations"]
    assert spec.boundary.kind == cfg["stencil"]["boundary"]
    assert cfg["name"] == conf["name"] and len(conf["source"]) <= 200


def test_peaks_table_raises_on_unknown_device():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_s"] == 819e9 and peaks["vpu_f32_op_s"] > 0
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")


NEW_KIND = '''
import contextlib

from sasabench.harness import Outcome


def run(cell, seed, seconds, trace, t_start, log):
    return Outcome(attempted=cell.traffic["requests"], failed=0,
                   end_to_end={"setup_s": 1.5}, compared={"max_abs_err": 0.0},
                   memory_peak_bytes=0, counters={}, work={},
                   window=(0.0, seconds))


@contextlib.contextmanager
def control(cell):
    yield
'''


def test_files_dropped_into_their_directories_are_found(tmp_path):
    """A new cell, traffic mix, kind of traffic or per-layer metric needs
    only new files and BENCHMARK.json entries; the harness runs it."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = dict(BENCH)
    b["configs"] = BENCH["configs"] + [{
        "name": "new-conf", "source": "x", "file": "bench/configs/new-conf.json",
        "reduced": [], "why": "x"}]
    b["workloads"] = BENCH["workloads"] + [{
        "name": "new.cell", "config": "new-conf", "traffic": "new_mix",
        "chips": 1, "why": "x"}]
    b["per_layer"] = BENCH["per_layer"] + [{
        "name": "new_metric.x", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "x", "moves": "setup_s",
        "workloads": ["new.cell"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    d = tmp_path / "bench"
    (d / "configs" / "new-conf.json").write_text(json.dumps({"marker": 7}))
    (d / "configs" / "new-conf.py").write_text("def step(x):\n    return x\n")
    (d / "traffic" / "new_mix.json").write_text(
        json.dumps({"kind": "new_kind", "requests": 9}))
    (d / "kinds" / "new_kind.py").write_text(NEW_KIND)
    (d / "limits" / "new.cell.json").write_text(
        json.dumps({"max_abs_err": {"limit": 0.5}}))
    (d / "layers" / "new_metric.x.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    cell = cells.load_cell("new.cell", root=tmp_path)
    assert cell.config == {"marker": 7}
    assert cell.traffic == {"kind": "new_kind", "requests": 9}
    assert cell.step(3) == 3
    assert [m["name"] for m in cell.per_layer] == ["new_metric.x"]
    assert cell.readers["new_metric.x"](None) == 42.0
    assert {m["name"] for m in cell.end_to_end} == {"setup_s"}
    result = harness.run_cell(cell, 2**33 + 5, 0.5, False, 0.0)
    assert result["correct"] is True and result["attempted"] == 9
    assert result["metrics"] == {"setup_s": {"value": 1.5, "unit": "s"}}
    with pytest.raises(KeyError):
        cells.load_cell("no.such.cell", root=tmp_path)
