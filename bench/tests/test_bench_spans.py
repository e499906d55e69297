"""The readers of the serving path's phase spans, on a summary with known
host events: what each returns, and that each reads nothing where its
spans, or the device, are missing."""
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from sasabench import cells, trace  # noqa: E402

READERS = cells.load_cell("jacobi2d.serve").readers
SPAN_METRICS = ("sched_busy.serve", "stage_ms.serve", "readback_ms.serve")
MS = 1_000_000      # ns

# A 1 s window with two batches on one thread; every span is whole in it.
#   batch 1: prepare 10, stage 40, dispatch 1, finalize 50, resolve 2 ms
#   batch 2: prepare 30, stage 60, dispatch 3, finalize 70, resolve 4 ms
HOST = [
    ("sasa.prepare", 0, 10), ("sasa.stage", 10, 50), ("sasa.dispatch", 50, 51),
    ("np.asarray", 100, 150), ("sasa.finalize", 100, 150),
    ("sasa.resolve", 150, 152),
    ("sasa.prepare", 200, 230), ("sasa.stage", 230, 290),
    ("sasa.dispatch", 290, 293), ("sasa.finalize", 400, 470),
    ("sasa.resolve", 470, 474), ("shard_args", 230, 290),
]
WANT = {
    "sched_busy.serve": 100.0 * 270 / 1000,      # 270 ms of spans in 1 s
    "stage_ms.serve": (10 + 40 + 30 + 60) / 2,
    "readback_ms.serve": (50 + 70) / 2,
}


def summary(host=HOST, devices=True):
    ops = [trace.Event("stencil_tile_batched.2", 60 * MS, 160 * MS)]
    return trace.TraceSummary(
        window=(0, 1000 * MS),
        devices={"/device:TPU:0": ops} if devices else {},
        host=[trace.Event(n, a * MS, b * MS) for n, a, b in host])


def ctx(t):
    return types.SimpleNamespace(trace=t)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_value_from_known_spans(name):
    assert READERS[name](ctx(summary())) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_reads_nothing_without_its_spans(name):
    no_spans = [e for e in HOST if not e[0].startswith("sasa.")]
    assert READERS[name](ctx(summary(host=no_spans))) is None
    assert READERS[name](ctx(None)) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_reads_nothing_off_the_chip(name):
    """A trace without a device plane (a CPU run) gives no time."""
    assert READERS[name](ctx(summary(devices=False))) is None


def test_readers_count_only_their_own_spans():
    only_finalize = [e for e in HOST if e[0] == "sasa.finalize"]
    t = summary(host=only_finalize)
    assert READERS["stage_ms.serve"](ctx(t)) is None
    assert READERS["readback_ms.serve"](ctx(t)) == pytest.approx(60.0)
    assert READERS["sched_busy.serve"](ctx(t)) == pytest.approx(12.0)
