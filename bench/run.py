#!/usr/bin/env python3
"""On-chip benchmark of the SASA stencil framework: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
under a traffic mix, each in files of its own under ``bench/`` (see
``bench/sasabench/cells.py``).  The run sets up (compiling, or loading
from the compile cache in ``.jax_cache/`` of this checkout), measures for
``--seconds``, compares a sample of what the timed path produced with the
configuration's plain reference, and prints one JSON line last: the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window.

It runs on a TPU only: where JAX finds another platform, or fewer chips
than the cell asks for (counted on the host's bus where the cell's
worker processes hold them), it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
    from sasabench import cells, startup

    cell = cells.load_cell(args.workload, ROOT)
    if not startup.start(cell.chips, "bench", T_START, print,
                         cell.workers_hold_chips):
        return 2
    from sasabench import harness

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    # a request that never came has an infinite latency: ``Infinity``
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
