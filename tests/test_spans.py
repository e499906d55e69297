"""The serving path's phase spans and queue-wait counters.

Every batch the engine or the scheduler serves leaves five sibling
profiler spans (``sasa.prepare``, ``sasa.stage``, ``sasa.dispatch``,
``sasa.finalize``, ``sasa.resolve``) on the host thread that ran it, and
the scheduler stamps each ticket at admission and dispatch and sums the
wait into the design's counters.
"""
import collections
import glob
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import stencils
from repro.runtime import DesignCache
from repro.serve import StencilRequest, StencilScheduler, StencilServer

ROOT = Path(__file__).resolve().parents[1]
SPANS = ("sasa.prepare", "sasa.stage", "sasa.dispatch", "sasa.finalize",
         "sasa.resolve")
RNG = np.random.default_rng(13)


def small_server(max_batch=2):
    spec = stencils.jacobi2d(shape=(16, 8), iterations=2)
    srv = StencilServer(max_batch=max_batch, cache=DesignCache(), warmup=True)
    srv.register("jac", spec)
    return srv, spec


def request(spec):
    return StencilRequest("jac", {
        n: RNG.standard_normal(shape).astype(dt)
        for n, (dt, shape) in spec.inputs.items()
    })


def test_scheduler_stamps_and_sums_queue_wait():
    srv, spec = small_server(max_batch=4)
    sched = StencilScheduler(srv, start=False)
    n = 3
    tickets = [sched.submit(request(spec)) for _ in range(n)]
    time.sleep(0.02)
    assert sched.step()
    sched.drain()
    for t in tickets:
        assert t.admitted_at <= t.dispatched_at <= t.completed_at
    st = srv.stats()["jac"]
    assert st["queued_requests"] == n
    assert st["queue_wait_total_s"] >= 0.02 * n
    assert st["queue_wait_total_s"] == pytest.approx(
        sum(t.dispatched_at - t.admitted_at for t in tickets))
    sched.close()


def test_flush_path_leaves_queue_wait_at_zero():
    srv, spec = small_server(max_batch=2)
    srv.serve([request(spec) for _ in range(3)])
    st = srv.stats()["jac"]
    assert st["batches"] == 2
    assert st["queued_requests"] == 0 and st["queue_wait_total_s"] == 0.0


def serve_flush(srv, spec, n):
    srv.serve([request(spec) for _ in range(n)])


def serve_scheduled(srv, spec, n):
    with StencilScheduler(srv, start=False) as sched:
        tickets = [sched.submit(request(spec)) for _ in range(n)]
        sched.drain()
        for t in tickets:
            t.result(timeout=60)


@pytest.mark.parametrize("serve", [serve_flush, serve_scheduled],
                         ids=["flush", "scheduler"])
def test_each_batch_leaves_the_five_spans(tmp_path, serve):
    """Two exact-shape batches traced as the benchmark traces them (no
    Python tracer, a ``bench_window`` span around the window), read back
    through the benchmark's own trace reduction."""
    sys.path[:0] = [str(ROOT / "bench")]
    try:
        from sasabench import trace
    finally:
        sys.path.remove(str(ROOT / "bench"))
    srv, spec = small_server(max_batch=2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            serve(srv, spec, 4)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    summary = trace.summarize(jax.profiler.ProfileData.from_file(path),
                              "bench_window")
    seen = collections.Counter(e.name for e in summary.host
                               if e.name.startswith("sasa."))
    assert srv.stats()["jac"]["batches"] == 2
    assert seen == {name: 2 for name in SPANS}
    spans = sorted((e for e in summary.host if e.name in SPANS),
                   key=lambda e: e.start)
    # siblings on one thread: none starts before the previous one ended
    assert all(a.end <= b.start for a, b in zip(spans, spans[1:]))
