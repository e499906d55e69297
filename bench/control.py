#!/usr/bin/env python3
"""The control of a cell's comparison, put through the benchmark's own run.

    python3 bench/control.py --workload jacobi2d.solve --seeds 1,2,3 \\
        --seconds 10 [--program]

For each seed, one run of the cell at its own size and load, with the
configuration's plain reference, computed in bfloat16, in the program's
place (the cell's traffic kind says where: ``control`` in its
``bench/kinds/<kind>.py``).  The benchmark's own comparison decides
``correct``, which has to come out false.  With ``--program`` each seed's
sound run comes first, in the same process: the lower reading of each
limit.  One JSON line per run.  The benchmark's own runs never run the
control.  Runs on a TPU only.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--program", action="store_true",
                   help="run each seed soundly too, before the control")
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
    from sasabench import cells, startup

    cell = cells.load_cell(args.workload, ROOT)
    if not startup.start(cell.chips, "control", T_START, print,
                         cell.workers_hold_chips):
        return 2
    from sasabench import harness

    runs = (("program", contextlib.nullcontext),) if args.program else ()
    runs += (("control", cell.kind.control),)
    for seed in (int(s) for s in args.seeds.split(",")):
        for what, planted in runs:
            with planted(cell):
                out = harness.run_cell(cell, seed, args.seconds, False,
                                       time.perf_counter())
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "run": what, "correct": out["correct"],
                              "attempted": out["attempted"],
                              "failed": out["failed"],
                              "compared": out["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
