"""Reduction of JAX profiler traces (``.xplane.pb``) to device metrics.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the benchmark's window and averaged over the devices;
idle is the rest of the window.  A kernel's time is the summed duration
of the device operations named after it.  Each idle gap is labelled by
the host activity of its own process that overlaps it most.

The window is the ``bench_window`` span of the trace, where the process
that wrote it recorded one.  The traces of worker processes hold none;
they are clipped to a window given on ``time.time_ns()``'s clock.  Each
process's profiler counts its events from the start of its session, which
the trace records as ``profile_start_time`` in nanoseconds of that clock.
Several traces are put on that clock together, and their devices are
kept apart by file: every one-chip worker names its chip
``/device:TPU:0``.

On a TPU an operation's event is named by its whole HLO instruction
(``%stencil_tile_batched.2 = f32[...] custom-call(...)``), whose operands
name other instructions; an operation is known by the instruction name
before `` = `` alone.
"""
from __future__ import annotations

import collections
import dataclasses

#: Device planes are ``/device:TPU:<n>``; their operations are on this line.
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
#: The plane whose ``profile_start_time`` is the session's start on the
#: ``time.time_ns()`` clock, from which the trace counts its events.
SESSION_PLANE = "Task Environment"
SESSION_START = "profile_start_time"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str           # the instruction name, e.g. "stencil_tile_batched.2"
    start: int          # ns
    end: int            # ns
    source: int = 0     # the trace file it came from, by position


@dataclasses.dataclass
class TraceSummary:
    window: tuple[int, int]
    devices: dict[str, list[Event]]      # device plane -> ops in the window
    host: list[Event]                    # host activity in the window
    # device -> the trace file it came from, where there are several
    sources: dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, ops: list[Event]) -> list[tuple[int, int]]:
        merged: list[list[int]] = []
        for e in sorted(ops, key=lambda e: e.start):
            if merged and e.start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e.end)
            else:
                merged.append([e.start, e.end])
        return [(a, b) for a, b in merged]

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        total = sum(b - a for ops in self.devices.values()
                    for a, b in self.busy_intervals(ops))
        return total / len(self.devices) / 1e9

    def op_seconds(self, match: str | None = None) -> float:
        """Summed device time of the operations whose name holds ``match``
        (all operations where ``None``), averaged over devices."""
        if not self.devices:
            return 0.0
        total = sum(e.end - e.start for ops in self.devices.values()
                    for e in ops if match is None or match in e.name)
        return total / len(self.devices) / 1e9

    def top_ops(self, n: int = TOP) -> list[list]:
        per = collections.Counter()
        for ops in self.devices.values():
            for e in ops:
                per[e.name] += e.end - e.start
        k = max(len(self.devices), 1)
        return [[name, t / k / 1e9] for name, t in per.most_common(n)]

    def idle_gaps(self, n: int = TOP) -> list[list]:
        """The longest idle gaps over all devices, each labelled by the
        host event of the device's own process that overlaps it most."""
        gaps = []
        for name, ops in self.devices.items():
            t = self.window[0]
            for a, b in self.busy_intervals(ops) + [(self.window[1],) * 2]:
                if a > t:
                    gaps.append((t, a, self.sources.get(name, 0)))
                t = max(t, b)
        gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
        return [[self._host_label(a, b, source), (b - a) / 1e9]
                for a, b, source in gaps[:n]]

    def _host_label(self, a: int, b: int, source: int) -> str:
        best, best_key = "host idle", None
        for e in self.host:
            if e.source != source:
                continue
            overlap = min(b, e.end) - max(a, e.start)
            if overlap <= 0:
                continue
            key = (overlap, -(e.end - e.start))
            if best_key is None or key > best_key:
                best, best_key = e.name, key
        return best

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _clip(event, window, shift: int = 0) -> Event | None:
    start = max(int(event.start_ns) + shift, window[0])
    end = min(int(event.end_ns) + shift, window[1])
    if end <= start:
        return None
    return Event(event.name.split(" = ", 1)[0].lstrip("%"), start, end)


def reduce(paths, window_span: str,
           window_ns: tuple[int, int] | None = None) -> TraceSummary:
    """The summary of the trace file at ``paths``, or of several files
    together (see :func:`summarize` and :func:`merge`)."""
    from jax.profiler import ProfileData

    if isinstance(paths, str):
        paths = [paths]
    data = [ProfileData.from_file(path) for path in paths]
    if len(data) == 1:
        return summarize(data[0], window_span, window_ns)
    return merge(data, window_span, window_ns)


def merge(data: list, window_span: str,
          window_ns: tuple[int, int] | None = None) -> TraceSummary:
    """The summary of several ``jax.profiler.ProfileData``, one for each
    process, on ``time.time_ns()``'s clock: each is clipped to its own
    ``window_span`` or else to ``window_ns``.  Device ``i:<plane>`` is
    plane ``<plane>`` of the ``i``-th trace."""
    parts = [summarize(d, window_span, window_ns, on_host_clock=True)
             for d in data]
    window = (min(p.window[0] for p in parts), max(p.window[1] for p in parts))
    devices, sources, host = {}, {}, []
    for i, part in enumerate(parts):
        for name, ops in part.devices.items():
            devices[f"{i}:{name}"] = [dataclasses.replace(e, source=i)
                                      for e in ops]
            sources[f"{i}:{name}"] = i
        host += [dataclasses.replace(e, source=i) for e in part.host]
    return TraceSummary(window=window, devices=devices, host=host,
                        sources=sources)


def session_start(data) -> int | None:
    """The profiler session's start on ``time.time_ns()``'s clock, from
    which the trace counts its events; ``None`` where it is not recorded."""
    plane = data.find_plane_with_name(SESSION_PLANE)
    start = None if plane is None else dict(plane.stats).get(SESSION_START)
    return None if start is None else int(start)


def summarize(data, window_span: str,
              window_ns: tuple[int, int] | None = None,
              on_host_clock: bool = False) -> TraceSummary:
    """The summary of a ``jax.profiler.ProfileData`` over the first host
    span named ``window_span``, or where there is none over ``window_ns``
    (``time.time_ns()`` at the window's start and end).  Its times are the
    trace's own, counted from the session's start, or with
    ``on_host_clock`` those of ``time.time_ns()``."""
    planes = list(data.planes)
    spans = [(int(e.start_ns), int(e.end_ns)) for plane in planes
             if plane.name.startswith(HOST_PREFIX)
             for line in plane.lines for e in line.events
             if e.name == window_span]
    if not spans and window_ns is None:
        raise ValueError(f"no {window_span!r} span in the trace, and no "
                         "window given")
    origin = session_start(data) if on_host_clock or not spans else 0
    if origin is None:
        raise ValueError(f"no {SESSION_START!r} in the trace: the clock of "
                         "its events is not known")
    shift = origin if on_host_clock else 0
    if spans:
        window = (min(spans)[0] + shift, min(spans)[1] + shift)
    else:
        window = (window_ns[0] - origin + shift, window_ns[1] - origin + shift)
    devices, host = {}, []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [line for line in plane.lines if line.name == OPS_LINE]
            if not lines:
                continue
            ops = [_clip(e, window, shift) for line in lines for e in line.events]
            devices[plane.name] = [e for e in ops if e is not None]
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_span:
                        continue
                    c = _clip(e, window, shift)
                    if c is not None:
                        host.append(c)
    return TraceSummary(window=window, devices=devices, host=host)
