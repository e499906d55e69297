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


def test_chip_trace_exactly_as_before():
    """The one-file reduction clipped to its ``bench_window`` span, as the
    two kinds that profile in the benchmark's process take it, reads the
    numbers it read before several traces could be reduced, to the
    nanosecond, whether or not a host-clock window is given as well."""
    want = {
        "device_ops": [["stencil_tile_batched.2", 0.783681662],
                       ["stencil_tile_batched.3", 0.783641116],
                       ["pad.9", 0.016538369], ["slice.3", 0.016107939],
                       ["slice.4", 0.016105285], ["pad.8", 0.015727876]],
        "idle_gaps": [["solve_dispatch", 0.001852636],
                      ["solve_dispatch", 0.001288264]]
        + [["solve_dispatch", 2e-09]] * 5 + [["solve_dispatch", 1e-09]] * 2,
    }
    for window_ns in (None, (1, 2)):
        s = trace.reduce(str(CHIP_TRACE), "bench_window", window_ns)
        assert s.window == (45486785, 1680429944)
        assert (s.window_s, s.busy_s) == (1.634943159, 1.631802247)
        assert s.op_seconds("stencil_tile_batched") == 1.567322778
        assert len(s.host) == 59
        assert s.breakdown() == want


# SYNTHETIC as a worker writes it: no ``bench_window`` span, and the
# session's start on the host clock, from which its events count.
ORIGIN = 10**18
NO_SPAN = SYNTHETIC.replace(
    "    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }\n",
    "") + """
planes {
  id: 3
  name: "Task Environment"
  stats { metadata_id: 1 uint64_value: %d }
  stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } }
}
"""


def _write(path: Path, text: str) -> str:
    from jax.profiler import ProfileData

    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture
def two_workers(tmp_path):
    """Two processes' traces of one window, [ORIGIN + 1000, ORIGIN + 11000)
    on the host clock.  The second process's session started 2000 ns
    earlier, so in its own clock the window is [3000, 13000): its ops
    clip to [3000, 4500) + [7000, 8000) + [10500, 12000) = 4000 ns busy,
    against the first's 5000 ns."""
    return [_write(tmp_path / "a.xplane.pb", NO_SPAN % ORIGIN),
            _write(tmp_path / "b.xplane.pb", NO_SPAN % (ORIGIN - 2000))]


def test_two_traces_keep_their_devices_apart(two_workers):
    s = trace.reduce(two_workers, "bench_window", (ORIGIN + 1000, ORIGIN + 11000))
    assert list(s.devices) == ["0:/device:TPU:0", "1:/device:TPU:0"]
    assert s.window == (ORIGIN + 1000, ORIGIN + 11000)
    assert s.window_s == pytest.approx(1e-5)
    assert s.busy_s == pytest.approx((5000 + 4000) / 2 * 1e-9)
    # kernel: [1500, 4500) + [7000, 8000) in the first, [3000, 4500) +
    # [7000, 8000) in the second, averaged
    assert s.op_seconds("stencil_tile_batched") == pytest.approx(3250e-9)


def test_two_traces_breakdown_by_hand(two_workers):
    b = trace.reduce(two_workers, "bench_window",
                     (ORIGIN + 1000, ORIGIN + 11000)).breakdown()
    # averaged over the two devices: copy [10500, 11000) and [10500, 12000),
    # fusion [1000, 2000) and nothing
    assert b["device_ops"] == [["stencil_tile_batched", pytest.approx(3.25e-6)],
                               ["copy.2", pytest.approx(1.0e-6)],
                               ["fusion.1", pytest.approx(0.5e-6)]]
    # each device's gaps, labelled by its own process's host: the second's
    # last gap, [12000, 13000) in its clock, overlaps only the first
    # process's device_put, and so is host idle
    assert b["idle_gaps"] == [["np.stack", pytest.approx(2.5e-6)],
                              ["device_put", pytest.approx(2.5e-6)],
                              ["np.stack", pytest.approx(2.5e-6)],
                              ["device_put", pytest.approx(2.5e-6)],
                              ["host idle", pytest.approx(1e-6)]]


def test_a_trace_without_its_window_is_refused(tmp_path):
    no_clock = _write(tmp_path / "c.xplane.pb",
                      NO_SPAN.split("\nplanes {\n  id: 3")[0])
    with_clock = _write(tmp_path / "d.xplane.pb", NO_SPAN % ORIGIN)
    with pytest.raises(ValueError, match="no window given"):
        trace.reduce(with_clock, "bench_window")
    with pytest.raises(ValueError, match="profile_start_time"):
        trace.reduce(no_clock, "bench_window", (ORIGIN, ORIGIN + 1))


def test_host_clock_window_finds_a_span_of_this_process(tmp_path):
    """A window taken with ``time.time_ns()`` around an annotated block
    lands on that block in a real trace: the trace counts its events from
    its session's recorded start on that clock."""
    import glob
    import time

    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = time.time_ns()
        with jax.profiler.TraceAnnotation("clock_probe"):
            time.sleep(0.05)
        t1 = time.time_ns()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    s = trace.reduce(path, "no_such_span", (t0, t1))
    (probe,) = [e for e in s.host if e.name == "clock_probe"]
    # the span is clipped by at most the clocks' disagreement, 1 ms
    assert (probe.end - probe.start) / 1e9 >= 0.05 - 1e-3
    assert s.window_s == pytest.approx((t1 - t0) / 1e9)
