"""The comparison that decides ``correct``, at sizes a test run can hold.

Each cell runs whole on the CPU, the harness's look for a chip skipped:
a sound run is correct; a run with the control (the reference computed
in bfloat16) in the program's place is not; and a run whose timed path is
broken underneath comes out not correct, once for each fault the cell can
have.
"""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from sasabench import cells, harness  # noqa: E402

SEED = 2**33 + 4242
SOLVE = "jacobi2d.solve"
SERVE = ("jacobi2d.serve",)


def tiny(name: str):
    """The cell with its sizes cut to what the CPU runs in seconds; the
    stencil, boundary, iterations per request and limits stay."""
    cell = cells.load_cell(name)          # its dicts are read afresh
    cfg = cell.config
    dsl = cfg["stencil"]["dsl"]
    dsl = dsl.replace("9720, 1024", "48, 40").replace("iteration: 64",
                                                      "iteration: 8")
    cfg.update(grid=[48, 40], iterations=8, ensemble=4,
               shapes={"grid": [48, 40]})
    cfg["stencil"]["dsl"] = dsl
    if cell.traffic["kind"] == "open_loop":
        cell.traffic.update(rate_per_s=30.0, pool_per_shape=2, checked=6)
    return cell


def run(cell, seconds=0.6):
    return harness.run_cell(cell, SEED, seconds, False, time.perf_counter())


@pytest.mark.parametrize("name", (SOLVE,) + SERVE)
def test_sound_run_is_correct_and_control_is_not(name):
    cell = tiny(name)
    # a short solve window: the tiny grid decays towards its zero boundary
    # far sooner than a 9720x1024 one, and the gap is in absolute terms
    seconds = 0.05 if name == SOLVE else 0.6
    sound = run(cell, seconds)
    limit = cell.limits["max_abs_err"]["limit"]
    assert sound["correct"] is True
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert sound["compared"]["max_abs_err"]["value"] <= limit
    assert set(sound["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in sound["metrics"].values())
    with cell.kind.control(cell):
        control = run(cell, seconds)
    assert control["correct"] is False
    assert control["failed"] == 0 and control["attempted"] > 0
    assert control["compared"]["max_abs_err"]["value"] > limit
    # the control is planted only within its block
    assert run(cell, seconds)["correct"] is True


def _break_dispatch(monkeypatch, fault):
    from repro.runtime import cache

    build = cache.DesignCache.get_or_build

    def get_or_build(self, *args, **kwargs):
        cached = build(self, *args, **kwargs)
        inner = cached.runner.dispatch
        cached.runner.dispatch = lambda staged: fault(inner, staged)
        return cached

    monkeypatch.setattr(cache.DesignCache, "get_or_build", get_or_build)


def _state_unchanged(inner, staged):
    return next(iter(staged.values()))


def _half_batch_left_out(inner, staged):
    import jax.numpy as jnp

    x = next(iter(staged.values()))
    half = x.shape[0] // 2
    return jnp.concatenate([inner(staged)[:half], x[half:]])


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch_left_out])
def test_solve_with_broken_step_is_not_correct(monkeypatch, fault):
    _break_dispatch(monkeypatch, fault)
    result = run(tiny(SOLVE))
    assert result["correct"] is False
    assert result["compared"]["max_abs_err"]["value"] > \
        result["compared"]["max_abs_err"]["limit"]
    assert list(result)[-1] == "compared"


def _answer_altered(res, inputs):
    for r in res.values():
        r.flat[0] += 0.5


def _answers_unchanged(res, inputs):
    for t in res:
        res[t] = np.array(inputs[t])


def _half_batch_left_out(res, inputs):
    """The head of every batch (a single request included) is answered
    with its input, as if the kernel had skipped it."""
    for t in list(res)[: (len(res) + 1) // 2]:
        res[t] = np.array(inputs[t])


@pytest.mark.parametrize("fault", [_answer_altered, _answers_unchanged,
                                   _half_batch_left_out])
@pytest.mark.parametrize("name", SERVE)
def test_serve_with_broken_answers_is_not_correct(monkeypatch, name, fault):
    from repro.serve.engine import StencilServer

    prepare = StencilServer._prepare

    def broken(self, reg, bucket, chunk):
        runner, stacked, post, pad = prepare(self, reg, bucket, chunk)
        (inp,) = reg.spec.inputs
        inputs = {t: req.arrays[inp] for t, req, _ in chunk}

        def post_broken(out):
            res = {t: np.array(r) for t, r in post(out).items()}
            fault(res, inputs)
            return res

        return runner, stacked, post_broken, pad

    monkeypatch.setattr(StencilServer, "_prepare", broken)
    result = run(tiny(name), seconds=1.0)
    assert result["correct"] is False
    assert result["failed"] == 0


REPORTED = {
    "jacobi2d.serve": {"gcell_s.served", "p50_ms", "setup_s"},
}


@pytest.mark.parametrize("name", SERVE)
def test_sound_serve_result_line(name):
    result = run(tiny(name), seconds=1.0)
    assert result["correct"] is True
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert set(result["metrics"]) == REPORTED[name]
    assert result["attempted"] == 30 and result["failed"] == 0


def test_traced_serve_reports_its_counter_metrics(monkeypatch):
    """Off the chip the trace holds no TPU plane: the device readers find
    nothing and are left out; the counter and clock readers report."""
    monkeypatch.setattr(harness, "load_peaks", lambda kind: {})
    result = harness.run_cell(tiny("jacobi2d.serve"), SEED, 1.0, True,
                              time.perf_counter())
    assert result["correct"] is True
    assert set(result["metrics"]) == {"batch_occupancy.serve", "batch_ms.serve",
                                      "p95_ms.serve"}
    assert 0 < result["metrics"]["batch_occupancy.serve"]["value"] <= 100
    assert result["metrics"]["batch_ms.serve"]["value"] > 0
    assert result["metrics"]["p95_ms.serve"]["value"] > 0
    assert "busy_s" not in result["device"]
