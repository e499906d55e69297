#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest rate at which
the backlog does not grow.

    python3 bench/sweep_knee.py --workload jacobi2d.serve --seed 5 \\
        --seconds 51 --rates 8,10,12,14,16

One process sets the cell up once and fires one window at each rate, in
the order given.  For each it prints the requests due, those answered in
the window and those still open at its close, the latency median, 95th
percentile and maximum, and the growth:
the median latency of the last quarter of the arrivals over that of the
first.  A rate past the knee shows a growth well above 1 and requests
still open at the close.  The cell's traffic file then fixes its rate at
about 0.8 of the knee.  Runs on a TPU only.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True, help="comma-separated, per s")
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
    from sasabench import cells, startup, stats

    cell = cells.load_cell(args.workload, ROOT)
    if not startup.start(cell.chips, "sweep_knee", T_START, print,
                         cell.workers_hold_chips):
        return 2
    serve = cell.kind
    t0 = time.perf_counter()
    session = serve.Session(cell, args.seed)
    print(f"set-up {time.perf_counter() - t0:.3f} s", flush=True)
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            w, _ = session.window(rate, args.seconds, args.seed + 1 + i)
            lat = [w.latency_s[a.index] for a in w.arrivals]
            q = max(len(lat) // 4, 1)
            growth = stats.percentile(lat[-q:], 50) / stats.percentile(lat[:q], 50)
            row = {
                "rate_per_s": rate, "due": len(w.arrivals),
                "answered_in_window": len(w.in_window()),
                "open_at_close": len(w.arrivals) - len(w.in_window()),
                "failed": w.failed,
                "p50_ms": stats.percentile(lat, 50) * 1e3,
                "p95_ms": stats.percentile(lat, 95) * 1e3,
                "max_ms": max(lat) * 1e3, "growth": growth,
                "late_p95_ms": stats.percentile(w.late_s, 95) * 1e3,
            }
            print(json.dumps(row), flush=True)
            session.scheduler.drain(timeout=serve.STRAGGLER_S)
    finally:
        session.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
