"""Finding a cell and everything that belongs to it, by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
rest sits in files of its own under ``bench/``, found by name, so that a
later change adds a cell, a mix or a metric by adding files:

  * ``configs/<config>.json`` -- the deployment (stencil, sizes, server);
    ``configs/<config>.py`` beside it -- its plain reference, ``step(x)``;
  * ``traffic/<mix>.json`` -- the traffic mix: its ``kind`` and the
    parameters that kind reads (rates, shares, sizes of the pools);
  * ``kinds/<kind>.py`` -- the driver of one kind of traffic, shared by
    every mix of that kind: ``run(...)``, which drives one run and returns
    a ``harness.Outcome``, and ``control(cell)``, a context manager that
    puts the control in the program's place.  A kind whose chips belong
    to worker processes that its driver starts (one per chip, as the
    program's router starts its replicas) says so with a module attribute
    ``WORKERS_HOLD_CHIPS = True``.  A process that has opened a JAX
    backend holds every chip it sees, so for such a kind the benchmark's
    process opens none: start-up counts the chips on the host's bus, no
    profiler starts in it, and the driver reports the workers' devices,
    memory and traces in the ``Outcome``.  Its driver may open a backend
    only once its workers have exited (to run the reference for
    ``correct``, say); anything earlier takes the workers' chips;
  * ``limits/<cell>.json`` -- the limits of the comparison that decides
    ``correct``, with the readings they were set from;
  * ``layers/<metric>.py`` -- the reader of one per-layer metric, a
    function ``read(ctx)`` that returns a number or ``None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import types
from pathlib import Path
from typing import Callable

#: The checkout's root: ``BENCHMARK.json`` and ``bench/`` sit here.
ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, Callable]
    step: Callable                 # the configuration's reference iteration
    kind: types.ModuleType         # the driver of the cell's traffic kind

    @property
    def workers_hold_chips(self) -> bool:
        """Whether the chips belong to worker processes that the kind's
        driver starts, and not to this process (``WORKERS_HOLD_CHIPS``)."""
        return bool(getattr(self.kind, "WORKERS_HOLD_CHIPS", False))


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    known = ", ".join(sorted(e["name"] for e in entries))
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json (have {known})")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics a cell reports.  A metric with
    a ``workloads`` list is reported in those cells; a per-layer metric
    without one in every cell that reports the metric it moves."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its files, read from the checkout at ``root``."""
    root = Path(root)
    bench = _json(root / "BENCHMARK.json")
    work = _by_name(bench["workloads"], name, "workload")
    conf = _by_name(bench["configs"], work["config"], "config")
    config_file = root / conf["file"]
    e2e, layer = cell_metrics(bench, name)
    step = load_module(config_file.with_suffix(".py"),
                       f"sasabench_ref_{conf['name']}").step
    readers = {m["name"]: load_module(root / "bench" / "layers" / f"{m['name']}.py",
                                      f"sasabench_layer_{m['name']}").read
               for m in layer}
    traffic = _json(root / "bench" / "traffic" / f"{work['traffic']}.json")
    kind = load_module(root / "bench" / "kinds" / f"{traffic['kind']}.py",
                       f"sasabench_kind_{traffic['kind']}")
    return Cell(
        name=name,
        chips=int(work["chips"]),
        config=_json(config_file),
        traffic=traffic,
        limits=_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=layer,
        readers=readers,
        step=step,
        kind=kind,
    )
