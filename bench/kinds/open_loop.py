"""Traffic kind ``open_loop``: clients sending grids to the program's
``StencilServer`` through its ``StencilScheduler``, at a fixed rate.

The traffic file gives ``rate_per_s``, ``shares`` (the share of requests
of each request shape that the configuration's ``shapes`` names),
``pool_per_shape`` (distinct grids of each shape) and ``checked`` (answers
compared).

Set-up registers the configuration's design as its users would, makes
the request pool on the host from the seed, and sends a few requests of
every shape the mix uses, so that every compiled program is built before
the window.  In the window one client thread submits each request at its
scheduled time, whatever the server's progress (an open loop), and stamps
each answer when it sees it.  A request's latency runs from its scheduled
arrival to that stamp, so a stall counts against every request it delays.
Requests still open at the close are waited for, up to a minute more.
After that, a sample of the answers, drawn from the seed and holding
every shape, is compared with the configuration's plain reference.

:func:`control` puts the reference, computed in bfloat16, in the place of
the answers the program serves.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from unittest import mock

import numpy as np

from sasabench import reference, stats, traffic, work
from sasabench.harness import Outcome, memory_peak_bytes, traced

POLL_S = 0.002          # longest the client waits before it looks again
WARMUP_PER_SHAPE = 2    # requests of every shape served in set-up
STRAGGLER_S = 60.0      # how long answers due in the window are waited for


@dataclasses.dataclass
class Window:
    """What the client saw of one window."""

    arrivals: list
    seconds: float
    start: float                         # perf_counter at the window's start
    latency_s: dict[int, float]          # arrival index -> seconds (inf: failed)
    done_at: dict[int, float]            # arrival index -> window-relative stamp
    answers: dict[int, np.ndarray]       # kept answers of checked requests
    late_s: list[float]                  # how late each submission was
    failed: int

    def in_window(self) -> list:
        return [a for a in self.arrivals
                if self.done_at.get(a.index, math.inf) <= self.seconds]


class Session:
    """A registered server and scheduler, warmed on every shape of the
    mix, and the request pool of a seed."""

    def __init__(self, cell, seed: int):
        from repro.core import dsl
        from repro.serve import StencilScheduler, StencilServer

        cfg = cell.config
        srv = cfg["server"]
        self.cell = cell
        self.iterations = int(cfg["iterations"])
        self.shares = {k: float(v) for k, v in cell.traffic["shares"].items()}
        self.shapes = {k: tuple(cfg["shapes"][k]) for k in self.shares}
        spec = dsl.parse(cfg["stencil"]["dsl"])
        (self.input_name,) = spec.inputs
        self.name = cfg["name"]
        self.max_batch = int(srv["max_batch"])
        self.server = StencilServer(max_batch=self.max_batch,
                                    max_inflight=int(srv["max_inflight"]))
        self.server.register(self.name, spec, iterations=self.iterations,
                             bucketing=bool(srv["bucketing"]))
        self.pool = traffic.request_pool(
            self.shapes, int(cell.traffic["pool_per_shape"]), seed)
        self.scheduler = StencilScheduler(
            self.server, gather_window_s=float(srv["gather_window_s"]))
        warm = [self.scheduler.submit(self.request(label, i))
                for label in sorted(self.shapes)
                for i in range(WARMUP_PER_SHAPE)]
        for ticket in warm:
            ticket.result(timeout=900.0)

    def grid(self, label: str, k: int) -> np.ndarray:
        """The ``k``-th request of a shape sends pool grid ``k mod size``."""
        grids = self.pool[label]
        return grids[k % len(grids)]

    def request(self, label: str, k: int):
        from repro.serve import StencilRequest

        return StencilRequest(self.name, {self.input_name: self.grid(label, k)})

    def counters(self) -> dict:
        st = self.server.stats()[self.name]
        return {
            "requests": st["requests"], "batches": st["batches"],
            "exec_count": st["exec_count"], "exec_total_s": st["exec_total_s"],
            "buckets": {b: s["requests"]
                        for b, s in st.get("buckets", {}).items()},
        }

    def window(self, rate_per_s: float, seconds: float, seed: int,
               keep=(), trace: bool = False) -> tuple[Window, list[str]]:
        """Fire one window of the mix at ``rate_per_s`` and wait for its
        answers; returns what the client saw and the trace files, if any."""
        from repro.serve import Backpressure

        arrivals = traffic.open_loop(rate_per_s, seconds, self.shares, seed)
        nth = {label: 0 for label in self.shapes}
        requests = []
        for a in arrivals:
            requests.append((a, self.request(a.label, nth[a.label])))
            nth[a.label] += 1
        keep = set(keep)
        open_, latency, done_at, answers, late = [], {}, {}, {}, []
        failed = 0

        def collect(t0: float) -> None:
            nonlocal open_, failed
            now = time.perf_counter()
            still = []
            for a, ticket, due in open_:
                if not ticket.done():
                    still.append((a, ticket, due))
                elif ticket.exception() is not None:
                    failed += 1
                    latency[a.index] = math.inf
                else:
                    done_at[a.index] = now - t0
                    latency[a.index] = now - due
                    if a.index in keep:
                        answers[a.index] = np.array(ticket.result())
            open_ = still

        def wait(t0: float, timeout: float) -> None:
            if not open_:
                time.sleep(timeout)
            else:
                try:
                    open_[0][1].result(timeout=timeout)
                except Exception:     # not yet, or a fault that collect() records
                    pass
            collect(t0)

        with traced(trace) as tr:
            t0 = time.perf_counter()
            for a, request in requests:
                due = t0 + a.at_s
                while (left := due - time.perf_counter()) > 0:
                    wait(t0, min(left, POLL_S))
                try:
                    open_.append((a, self.scheduler.submit(request), due))
                except Backpressure:
                    failed += 1
                    latency[a.index] = math.inf
                late.append(time.perf_counter() - due)
            while (left := t0 + seconds - time.perf_counter()) > 0:
                wait(t0, min(left, POLL_S))
        stop = t0 + seconds + STRAGGLER_S
        while open_ and (left := stop - time.perf_counter()) > 0:
            wait(t0, min(left, POLL_S))
        for a, _, _ in open_:                 # never came
            failed += 1
            latency[a.index] = math.inf
        return Window(arrivals, seconds, t0, latency, done_at, answers, late,
                      failed), tr.files

    def close(self) -> None:
        self.scheduler.close(timeout=STRAGGLER_S)


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        log) -> Outcome:
    started = time.perf_counter()
    session = Session(cell, seed)
    try:
        before = session.counters()
        setup_s = time.perf_counter() - t_start
        log(f"set-up: driver start at {started - t_start:.3f} s, server "
            f"registered and warmed by {setup_s:.3f} s")
        shapes = session.shapes
        largest = max(shapes, key=lambda k: work.cells(shapes[k]))
        arrivals = traffic.open_loop(float(cell.traffic["rate_per_s"]),
                                     seconds, session.shares, seed)
        checked = traffic.checked_sample(
            arrivals, int(cell.traffic["checked"]), seed, largest)
        w, trace_files = session.window(float(cell.traffic["rate_per_s"]),
                                        seconds, seed, checked, trace)
        session.scheduler.drain(timeout=STRAGGLER_S)
        after = session.counters()
    finally:
        session.close()
    counters = {k: after[k] - before[k] for k in after if k != "buckets"}
    counters["buckets"] = {b: n - before["buckets"].get(b, 0)
                           for b, n in after["buckets"].items()}
    counters["max_batch"] = session.max_batch
    peak = memory_peak_bytes()

    served = [a for a in w.arrivals if a.index in w.done_at]
    cell_its = sum(work.cells(shapes[a.label])
                   for a in w.in_window()) * session.iterations
    lat_ms = [w.latency_s[a.index] * 1e3 for a in w.arrivals]
    log(f"window: {len(w.arrivals)} requests due in {seconds} s at "
        f"{cell.traffic['rate_per_s']}/s; {len(w.in_window())} answered in "
        f"the window, {len(served)} in all, {w.failed} failed or never came")
    log(f"generator lateness: median {stats.percentile(w.late_s, 50) * 1e3:.3f}"
        f" ms, p95 {stats.percentile(w.late_s, 95) * 1e3:.3f} ms, max "
        f"{max(w.late_s) * 1e3:.3f} ms; {len(lat_ms)} latency samples, p50 "
        f"{stats.percentile(lat_ms, 50):.1f} ms, p95 "
        f"{stats.percentile(lat_ms, 95):.1f} ms")
    log(f"program counters over the window: {counters}")
    log_stalls(w, log)
    compared = compare(session, w, checked, log)
    return Outcome(
        attempted=len(w.arrivals), failed=w.failed,
        end_to_end={
            "gcell_s.served": cell_its / seconds / 1e9,
            "p95_ms": stats.percentile(lat_ms, 95),
            "p50_ms": stats.percentile(lat_ms, 50),
            "setup_s": setup_s,
        },
        compared=compared,
        memory_peak_bytes=peak,
        counters=counters,
        work={
            "real_cells_by_label": {k: work.cells(v) for k, v in shapes.items()},
            "served_by_label": {k: sum(a.label == k for a in served)
                                for k in shapes},
        },
        window=(w.start, w.start + seconds),
        trace_files=trace_files,
    )


def log_stalls(w: Window, log) -> None:
    """Where in the window the host stood still: the longest gaps between
    answers, the latest submissions, and the worst latency of each tenth
    of the window (by scheduled arrival)."""
    stamps = sorted(w.done_at.values())
    gaps = sorted(((b - a, a) for a, b in zip(stamps, stamps[1:])),
                  reverse=True)[:3]
    log("longest gaps between answers: " + ", ".join(
        f"{g * 1e3:.0f} ms at {t:.1f} s" for g, t in gaps))
    late = sorted(zip(w.late_s, (a.at_s for a in w.arrivals)),
                  reverse=True)[:3]
    log("latest submissions: " + ", ".join(
        f"{d * 1e3:.0f} ms at {t:.1f} s" for d, t in late))
    worst = [0.0] * 10
    for a in w.arrivals:
        k = min(int(10 * a.at_s / w.seconds), 9)
        worst[k] = max(worst[k], w.latency_s.get(a.index, math.inf))
    log("worst latency by tenth of the window, ms: "
        + " ".join(f"{x * 1e3:.0f}" for x in worst))


def compare(session: Session, w: Window, checked, log) -> dict:
    """Widest gap between the sampled answers and the reference run over
    the same grids; ``inf`` where a sampled answer never came."""
    nth, index = {}, {}
    for a in w.arrivals:
        index[a.index] = nth.get(a.label, 0)
        nth[a.label] = index[a.index] + 1
    by_label: dict[str, list] = {}
    for a in w.arrivals:
        if a.index in checked:
            by_label.setdefault(a.label, []).append(a.index)
    err = 0.0
    for label, idx in sorted(by_label.items()):
        grids = np.stack([session.grid(label, index[i]) for i in idx])
        want = reference.run(session.cell.step, grids, session.iterations)
        for j, i in enumerate(idx):
            got = w.answers.get(i)
            err = max(err, math.inf if got is None
                      else reference.max_abs_err(got, want[j]))
        log(f"checked {len(idx)} {label} answers; max|ref| "
            f"{float(np.max(np.abs(want))):.6g}")
    return {"max_abs_err": err}


@contextlib.contextmanager
def control(cell):
    """Within the block, every batch the server runs is answered with the
    configuration's reference computed in bfloat16 over the requests'
    grids, in place of what the program computed: the control, which the
    comparison has to fail."""
    from repro.serve.engine import StencilServer

    prepare = StencilServer._prepare
    its = int(cell.config["iterations"])

    def planted(self, reg, bucket, chunk):
        runner, stacked, post, pad = prepare(self, reg, bucket, chunk)
        (name,) = reg.spec.inputs

        def post_control(out):
            return {t: reference.run(cell.step,
                                     np.asarray(req.arrays[name])[None], its,
                                     reference.CONTROL_DTYPE)[0]
                    for t, req, _ in chunk}

        return runner, stacked, post_control, pad

    with mock.patch.object(StencilServer, "_prepare", planted):
        yield
