"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the benchmark's window span and averaged over the
devices; idle is the rest of the window.  A kernel's time is the summed
duration of the device operations named after it.  Each idle gap is
labelled by the host activity that overlaps it most.

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
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str           # the instruction name, e.g. "stencil_tile_batched.2"
    start: int          # ns
    end: int            # ns


@dataclasses.dataclass
class TraceSummary:
    window: tuple[int, int]
    devices: dict[str, list[Event]]      # device plane -> ops in the window
    host: list[Event]                    # host activity in the window

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
        """The longest idle gaps of the first device, each labelled by the
        host event that overlaps it most."""
        if not self.devices:
            return []
        ops = self.devices[sorted(self.devices)[0]]
        gaps, t = [], self.window[0]
        for a, b in self.busy_intervals(ops) + [(self.window[1],) * 2]:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
        return [[self._host_label(a, b), (b - a) / 1e9] for a, b in gaps[:n]]

    def _host_label(self, a: int, b: int) -> str:
        best, best_key = "host idle", None
        for e in self.host:
            overlap = min(b, e.end) - max(a, e.start)
            if overlap <= 0:
                continue
            key = (overlap, -(e.end - e.start))
            if best_key is None or key > best_key:
                best, best_key = e.name, key
        return best

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _clip(event, window) -> Event | None:
    start, end = max(int(event.start_ns), window[0]), min(int(event.end_ns), window[1])
    if end <= start:
        return None
    return Event(event.name.split(" = ", 1)[0].lstrip("%"), start, end)


def reduce(path: str, window_span: str) -> TraceSummary:
    """The summary of the trace file at ``path`` (see :func:`summarize`)."""
    from jax.profiler import ProfileData

    return summarize(ProfileData.from_file(path), window_span)


def summarize(data, window_span: str) -> TraceSummary:
    """The summary of a ``jax.profiler.ProfileData`` over the first host
    span named ``window_span``."""
    planes = list(data.planes)
    spans = [(int(e.start_ns), int(e.end_ns)) for plane in planes
             if plane.name.startswith(HOST_PREFIX)
             for line in plane.lines for e in line.events
             if e.name == window_span]
    if not spans:
        raise ValueError(f"no {window_span!r} span in the trace")
    window = min(spans)
    devices, host = {}, []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [line for line in plane.lines if line.name == OPS_LINE]
            if not lines:
                continue
            ops = [_clip(e, window) for line in lines for e in line.events]
            devices[plane.name] = [e for e in ops if e is not None]
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_span:
                        continue
                    c = _clip(e, window)
                    if c is not None:
                        host.append(c)
    return TraceSummary(window=window, devices=devices, host=host)
