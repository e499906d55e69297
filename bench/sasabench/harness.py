"""One run of one cell: the driver of its traffic's kind, then the
metrics, the comparison that decides ``correct``, and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

#: Name of the span the benchmark records around its measured window; the
#: trace reduction clips device activity to it.
WINDOW_SPAN = "bench_window"

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


@dataclasses.dataclass
class Outcome:
    """What a driver measured in one run.

    The driver of a kind whose chips belong to its worker processes
    (``WORKERS_HOLD_CHIPS``, see ``cells.py``) gives ``device``: the
    ``platform``, ``kind`` and ``count`` of the workers' devices, and
    ``workers``, one dict for each worker with its ``chip``, ``pid`` and
    ``memory_peak_bytes`` (``None`` where the worker reports none).  Its
    own ``memory_peak_bytes`` is then ``None``: this process holds no
    chip.  Its ``trace_files`` are the workers' traces, each clipped to
    ``window_ns`` (``time.time_ns()`` at the window's start and end), as
    none holds a ``bench_window`` span.  It opens a JAX backend in this
    process only after its workers have exited: one opened earlier would
    take their chips.  A driver that gives no ``device`` runs on this
    process's devices.
    """

    attempted: int
    failed: int
    end_to_end: dict[str, float]
    compared: dict[str, float]           # name -> reading (limits are the cell's)
    memory_peak_bytes: int | None        # fullest device of this process
    counters: dict                       # program counters over the window
    work: dict                           # work counts over the window
    window: tuple[float, float]          # perf_counter at its start and end
    trace_files: list[str] = dataclasses.field(default_factory=list)
    window_ns: tuple[int, int] | None = None
    device: dict | None = None


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


@dataclasses.dataclass
class _Trace:
    files: list[str] = dataclasses.field(default_factory=list)
    window_ns: tuple[int, int] | None = None


@contextlib.contextmanager
def traced(on: bool, workers_hold_chips: bool = False):
    """Profile the body (the measured window) when ``on``: the JAX
    profiler's trace with a ``bench_window`` span around the body.  The
    trace is written under a fresh temporary directory, whose
    ``.xplane.pb`` the result's ``files`` name after the block.

    Where the cell's chips belong to worker processes, no profiler starts
    here: it would open a JAX backend and take the workers' chips.  The
    result's ``window_ns`` is then the body's start and end on
    ``time.time_ns()``'s clock, to which the driver clips the traces it
    asks its workers for."""
    import jax

    result = _Trace()
    if not on:
        yield result
        return
    if workers_hold_chips:
        t0 = time.time_ns()
        try:
            yield result
        finally:
            result.window_ns = (t0, time.time_ns())
        return
    out = tempfile.mkdtemp(prefix="sasabench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield result
    finally:
        jax.profiler.stop_trace()
        result.files = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                                 recursive=True)[:1]


def load_peaks(device_kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; an unknown kind raises."""
    with open(PEAKS) as f:
        table = json.load(f)
    try:
        entry = table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (known: {sorted(table['devices'])})") from None
    return {k: v["value"] for k, v in entry.items()}


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def device_block(outcome: Outcome) -> dict:
    """The result line's ``device``: the workers' as the driver reports
    them, where it does, else this process's devices.  With workers,
    ``memory_peak_bytes`` is the fullest worker's, and ``None`` where a
    worker reports no memory: no number is made up for it."""
    if outcome.device is None:
        return dict(device_info(), memory_peak_bytes=outcome.memory_peak_bytes)
    workers = outcome.device["workers"]
    peaks = [w["memory_peak_bytes"] for w in workers]
    if not peaks or None in peaks:
        log("memory_peak_bytes: null, a worker reports no device memory")
        peak = None
    else:
        peak = max(peaks)
    return {"platform": outcome.device["platform"],
            "kind": outcome.device["kind"], "count": outcome.device["count"],
            "memory_peak_bytes": peak, "workers": workers}


def layer_metrics(cell, outcome: Outcome, peaks: dict | None) -> tuple[dict, dict]:
    """Per-layer metrics of a traced run, and the trace's device summary."""
    from sasabench import trace as tracing

    summary = None
    if outcome.trace_files:
        summary = tracing.reduce(outcome.trace_files, WINDOW_SPAN,
                                 outcome.window_ns)
        # each as the profiler lays it out: <dir>/plugins/profile/<run>/
        for path in outcome.trace_files:
            shutil.rmtree(Path(path).parents[3], ignore_errors=True)
    ctx = types.SimpleNamespace(
        trace=summary, counters=outcome.counters, work=outcome.work,
        end_to_end=outcome.end_to_end, config=cell.config,
        traffic=cell.traffic, peaks=peaks, log=log,
    )
    metrics = {}
    for m in cell.per_layer:
        value = cell.readers[m["name"]](ctx)
        if value is None:
            log(f"{m['name']}: nothing to read in this run")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, summary


#: JAX's own events for a trace of a jitted function and a backend compile.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


@contextlib.contextmanager
def compile_events():
    """Collect ``(event, perf_counter)`` of every JAX trace and compile."""
    import jax

    seen = []

    def listener(event, duration_secs, **kwargs):
        if event in COMPILE_EVENTS:
            seen.append((event, time.perf_counter()))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """Run one cell and return its result line as a dict, ``compared``
    last.  The comparison's readings and limits are also the last lines
    written to standard error."""
    with compile_events() as seen:
        outcome = cell.kind.run(cell, seed, seconds, trace, t_start, log)
    w0, w1 = outcome.window
    inside = [e for e, t in seen if w0 <= t <= w1]
    log(f"in the window: {inside.count(COMPILE_EVENTS[0])} traces and "
        f"{inside.count(COMPILE_EVENTS[1])} compiles")
    compared = {
        name: {"value": value, "limit": cell.limits[name]["limit"]}
        for name, value in outcome.compared.items()
        if name in cell.limits
    }
    compared["failed_requests"] = {"value": outcome.failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    result = {"correct": correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    device = device_block(outcome)
    if trace:
        metrics, summary = layer_metrics(cell, outcome,
                                         load_peaks(device["kind"]))
        if summary is not None and summary.devices:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = summary.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {name: {"value": outcome.end_to_end[name], "unit": unit}
                   for name, unit in units.items()}
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = compared
    for name, c in compared.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"compared {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    return result
