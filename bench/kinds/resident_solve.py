"""Traffic kind ``resident_solve``: a batch solver stepping a
device-resident ensemble of grids.

Set-up ranks and compiles the design through the program's
``DesignCache``, makes the ensemble on the device from the seed, and runs
the first dispatch (which compiles, or loads from the compile cache).
The window then loops ``out = runner.dispatch({input: out})`` with the
traffic file's ``in_flight`` dispatches enqueued, as a solver stepping
state that stays on the device does: the next steps are queued while the
device runs the current one, so a stall of the host shorter than the
queued steps does not idle the device.
The window closes after the first completion past ``seconds``, once the
steps still queued have completed too.  After the window, a sample of the
grids, drawn from the seed, is compared with the configuration's plain
reference run from the same seeded start for every iteration the program
ran, set-up's dispatch included.

:func:`control` puts the reference, computed in bfloat16, in the place of
the program's dispatch.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from sasabench import reference, traffic, work
from sasabench.harness import Outcome, memory_peak_bytes, traced


def state_key(seed: int):
    return jax.random.key(int(np.random.SeedSequence(int(seed))
                              .generate_state(1)[0]))


@functools.partial(jax.jit, static_argnames=("shape",))
def make_grids(key, index, shape):
    """Grids ``index`` of the ensemble: uniform [0, 1) float32, grid ``i``
    from its own stream of the key, so any subset can be made again."""
    return jax.vmap(lambda i: jax.random.uniform(
        jax.random.fold_in(key, i), shape))(index)


def checked_grids(ensemble: int, seed: int) -> list[int]:
    """One grid from each half of the ensemble, drawn from the seed."""
    rng = traffic.rng_for(seed, 4)
    half = ensemble // 2
    if half == 0:
        return [0]
    return [int(rng.integers(half)), int(half + rng.integers(ensemble - half))]


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        log) -> Outcome:
    from repro.core import dsl
    from repro.runtime import DesignCache

    marks = [("start", time.perf_counter())]
    cfg = cell.config
    shape = tuple(cfg["grid"])
    its = int(cfg["iterations"])
    ensemble = int(cfg["ensemble"])
    in_flight = int(cell.traffic["in_flight"])
    spec = dsl.parse(cfg["stencil"]["dsl"])
    cached = DesignCache().get_or_build(spec, iterations=its)
    runner = cached.runner
    name = cached.design.spec.iterate_input
    marks.append(("design", time.perf_counter()))
    c = runner.cfg
    log(f"design: {c.variant} s={c.s} tile_rows={c.tile_rows} "
        f"buffer_depth={c.buffer_depth}, path {runner.path}, backend "
        f"{runner.backend}, interpret {runner.interpret}")

    key = state_key(seed)
    x = make_grids(key, jnp.arange(ensemble), shape).block_until_ready()
    marks.append(("state", time.perf_counter()))
    out = runner.dispatch({name: x})
    del x
    out.block_until_ready()
    marks.append(("first dispatch", time.perf_counter()))
    setup_s = time.perf_counter() - t_start
    log("set-up: " + ", ".join(f"{k} at {t - t_start:.3f} s" for k, t in marks))

    queued, done = collections.deque(), []
    with traced(trace) as tr:
        t0 = time.perf_counter()
        while True:
            out = runner.dispatch({name: out})
            queued.append(out)
            if len(queued) < in_flight:
                continue
            queued.popleft().block_until_ready()
            done.append(time.perf_counter())
            if done[-1] - t0 >= seconds:
                break
        while queued:
            queued.popleft().block_until_ready()
            done.append(time.perf_counter())
        elapsed = time.perf_counter() - t0
    dispatches = len(done)
    cell_its = dispatches * ensemble * work.cells(shape) * its
    log(f"window: {dispatches} dispatches of {ensemble} grids x {its} "
        f"iterations in {elapsed:.6f} s")
    gaps = np.diff(done)
    if len(gaps):
        log(f"seconds between completions: first {gaps[0]:.6f}, median "
            f"{np.median(gaps):.6f}, min {gaps.min():.6f}, max {gaps.max():.6f}")
    peak = memory_peak_bytes()

    idx = checked_grids(ensemble, seed)
    got = np.asarray(out[jnp.asarray(idx)])
    del out
    total_its = (dispatches + 1) * its
    start = make_grids(key, jnp.asarray(idx), shape)
    want = reference.run(cell.step, start, total_its)
    compared = {"max_abs_err": reference.max_abs_err(got, want)}
    log(f"checked grids {idx} after {total_its} iterations; "
        f"max|ref| {float(np.max(np.abs(want))):.6g}")
    return Outcome(
        attempted=dispatches, failed=0,
        end_to_end={"gcell_s": cell_its / elapsed / 1e9, "setup_s": setup_s},
        compared=compared,
        memory_peak_bytes=peak,
        counters={},
        work={
            "ops": work.stencil_ops(cfg, shape, its, dispatches * ensemble),
            "bytes": work.stencil_bytes(cfg, shape, dispatches * ensemble),
        },
        window=(t0, t0 + elapsed),
        trace_files=tr.files,
    )


class _Runner:
    """The program's runner with another ``dispatch``."""

    def __init__(self, runner, dispatch):
        self._runner = runner
        self.dispatch = dispatch

    def __getattr__(self, name):
        return getattr(self._runner, name)


@contextlib.contextmanager
def control(cell):
    """Within the block, every design the solver builds dispatches the
    configuration's reference computed in bfloat16 instead of the
    program's kernel: the control, which the comparison has to fail."""
    from repro.runtime import cache

    build = cache.DesignCache.get_or_build
    its = int(cell.config["iterations"])

    def get_or_build(self, *args, **kwargs):
        cached = build(self, *args, **kwargs)
        name = cached.design.spec.iterate_input
        low = _Runner(cached.runner, lambda staged: reference.iterate(
            cell.step, staged[name], its, reference.CONTROL_DTYPE))
        return dataclasses.replace(cached, runner=low)

    with mock.patch.object(cache.DesignCache, "get_or_build", get_or_build):
        yield
