"""The reduction from a profiler trace to device busy and idle time,
kernel time and the breakdown."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from sasabench import trace  # noqa: E402

# ns offsets of ps-resolution events on one device and the host:
#   window [1000, 11000); ops [500, 2000) fusion, [1500, 4500) kernel,
#   [7000, 8000) kernel, [10500, 12000) copy -> clipped to the window:
#   busy [1000, 4500) + [7000, 8000) + [10500, 11000) = 5000 ns
SYNTHETIC = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 10500000 duration_ps: 1500000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "stencil_tile_batched" } }
  event_metadata { key: 3 value { id: 3 name: "copy.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_batched_fn" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4600000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 2500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "np.stack" } }
  event_metadata { key: 3 value { id: 3 name: "device_put" } }
}
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    return trace.summarize(ProfileData.from_text_proto(SYNTHETIC),
                           "bench_window")


def test_window_and_busy_union(synthetic):
    assert synthetic.window == (1000, 11000)
    assert synthetic.window_s == pytest.approx(1e-5)
    assert synthetic.busy_s == pytest.approx(5000e-9)
    assert list(synthetic.devices) == ["/device:TPU:0"]


def test_kernel_time_by_name(synthetic):
    assert synthetic.op_seconds("stencil_tile_batched") == pytest.approx(4000e-9)
    assert synthetic.op_seconds() == pytest.approx(5500e-9)   # overlap counted
    assert synthetic.op_seconds("no_such_kernel") == 0.0


def test_breakdown(synthetic):
    b = synthetic.breakdown()
    assert b["device_ops"][0] == ["stencil_tile_batched", pytest.approx(4e-6)]
    # idle gaps [4500, 7000) and [8000, 10500), labelled by the host
    assert b["idle_gaps"] == [["np.stack", pytest.approx(2.5e-6)],
                              ["device_put", pytest.approx(2.5e-6)]]


# Two dispatches of jacobi2d.solve's program (64 grids of 9720x1024, two
# rounds of 32 fused iterations each) traced on one TPU v5e.
CHIP_TRACE = Path(__file__).parent / "data" / "solve_window.xplane.pb"


@pytest.fixture(scope="module")
def chip():
    return trace.reduce(str(CHIP_TRACE), "bench_window")


def test_chip_trace_known_numbers(chip):
    assert list(chip.devices) == ["/device:TPU:0"]
    assert chip.window == (45486785, 1680429944)
    assert chip.window_s == pytest.approx(1.634943159, abs=1e-9)
    assert chip.busy_s == pytest.approx(1.631802247, abs=1e-9)
    # four kernel calls; the slices that read the kernel's output are not
    # counted, though their HLO text names it
    assert chip.op_seconds("stencil_tile_batched") == pytest.approx(
        1.567322778, abs=1e-9)
    names = [name for name, _ in chip.breakdown()["device_ops"]]
    assert names[:2] == ["stencil_tile_batched.2", "stencil_tile_batched.3"]
    assert set(names[2:]) == {"pad.8", "pad.9", "slice.3", "slice.4"}


def test_chip_trace_busy_matches_an_independent_count(chip):
    """Busy time by a sweep over interval endpoints of the raw events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(CHIP_TRACE))
    lo, hi = chip.window
    edges = []
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                a, b = max(int(e.start_ns), lo), min(int(e.end_ns), hi)
                if b > a:
                    edges += [(a, 1), (b, -1)]
    depth, last, busy = 0, None, 0
    for t, step in sorted(edges):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert busy / 1e9 == pytest.approx(chip.busy_s, abs=1e-9)
    assert 0 < chip.busy_s <= chip.window_s
