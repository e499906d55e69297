"""Cold-start gate: a fresh replica warm-loads the design store >= 10x
faster than re-autotuning and re-jitting, with bitwise-identical output.

The persistent :class:`repro.runtime.DesignStore` is the TPU analogue of
shipping a compiled FPGA bitstream: the expensive artifact (the tuned
ranking + the AOT-compiled executable) outlives the process that built
it.  This benchmark proves the claim end to end, across real process
boundaries:

  1. **cold child** — a fresh subprocess pointed at an *empty* store
     serves one request: pays the full autotune (design-space rank) +
     jit trace/compile + AOT serialize-to-store cost.
  2. **warm child** — a second fresh subprocess pointed at the *same*
     store serves the identical request: must reach its first result
     with **zero autotune invocations and zero jit builds**
     (``autotune_calls == 0 and jit_builds == 0``), >= 10x faster than
     the cold child, and the saved outputs must be **bitwise equal**
     (the warm path replays the very same XLA executable, so this holds
     on every backend, not just CPU).

Both children run with JAX's persistent compilation cache off, so the
cold child compiles from scratch whatever ``JAX_COMPILATION_CACHE_DIR``
says; each prints that it did.

Time-to-first-result is measured *inside* each child from after process
bootstrap (interpreter + jax import) to the first completed result:
import cost is identical on both sides and is not what the store
optimizes away.  The cold child's store writes are inside its timed
region — warm-start wins even after charging cold for populating the
store.

Run directly (``PYTHONPATH=src:. python benchmarks/cold_start.py``) it
asserts the gates; ``--smoke`` uses the same trace (already CI-sized).
``scripts/ci.sh`` runs it via ``serving_throughput.py --smoke``.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent

DSL = """
kernel: JACOBI2D_COLDSTART
iteration: 32
input float: in_1(256, 128)
output float: out_1(0,0) = (in_1(0,1) + in_1(1,0) + in_1(0,0)
    + in_1(0,-1) + in_1(-1,0)) / 5
"""


def _child(store_dir: str, out_npy: str, report_json: str) -> None:
    """One serving replica: store-backed server, one request, one result.

    Runs in a fresh subprocess.  Everything a replica pays between
    "process is up" and "first result returned" is inside the timed
    region: cache construction (store manifest + telemetry load),
    registration (autotune or store ranking hit), and the first dispatch
    (jit+AOT compile or store executable load).
    """
    import jax

    from repro.core.dsl import parse
    from repro.serve import StencilRequest, StencilServer

    # only the design store may warm a child: a compile-cache hit would
    # pass for a store hit in the cold child's timing
    jax.config.update("jax_enable_compilation_cache", False)
    print("cold_start child: persistent compilation cache off", flush=True)
    spec = parse(DSL)
    rng = np.random.default_rng(42)
    arrays = {
        name: rng.standard_normal(shape).astype(dt)
        for name, (dt, shape) in spec.inputs.items()
    }

    t0 = time.perf_counter()
    srv = StencilServer(max_batch=1, store_dir=store_dir)
    srv.register("jacobi2d", spec)
    out = srv.serve([StencilRequest("jacobi2d", arrays)])[0]
    elapsed = time.perf_counter() - t0

    srv.persist_telemetry()
    np.save(out_npy, np.asarray(out))
    st = srv.stats()
    report = {
        "elapsed_s": elapsed,
        "autotune_calls": st["_cache"]["autotune_calls"],
        "jit_builds": st["_cache"]["jit_builds"],
        "store_hits": st["_cache"]["store_hits"],
        "store": st.get("_store", {}),
    }
    with open(report_json, "w") as f:
        json.dump(report, f)


def _spawn(store_dir: str, out_npy: str, report_json: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{ROOT}"
    subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "cold_start.py"),
         "--child", store_dir, out_npy, report_json],
        check=True, env=env, cwd=str(ROOT),
    )
    with open(report_json) as f:
        return json.load(f)


def run_cold_start(rows, check: bool):
    from benchmarks.common import emit

    with tempfile.TemporaryDirectory() as td:
        store = os.path.join(td, "store")
        cold = _spawn(store, os.path.join(td, "cold.npy"),
                      os.path.join(td, "cold.json"))
        warm = _spawn(store, os.path.join(td, "warm.npy"),
                      os.path.join(td, "warm.json"))
        out_cold = np.load(os.path.join(td, "cold.npy"))
        out_warm = np.load(os.path.join(td, "warm.npy"))

    ratio = cold["elapsed_s"] / warm["elapsed_s"]
    emit(rows, "coldstart/cold_first_result", cold["elapsed_s"] * 1e6,
         f"autotune_calls={cold['autotune_calls']}; "
         f"jit_builds={cold['jit_builds']} (fresh store)")
    emit(rows, "coldstart/warm_first_result", warm["elapsed_s"] * 1e6,
         f"autotune_calls={warm['autotune_calls']}; "
         f"jit_builds={warm['jit_builds']}; "
         f"store_hits={warm['store_hits']}")
    emit(rows, "coldstart/speedup", 0.0,
         f"{ratio:.1f}x warm vs cold (subprocess, gate >= 10x)")

    bitwise = bool(np.array_equal(out_cold, out_warm))
    emit(rows, "coldstart/bitwise", 0.0,
         "bitwise-identical" if bitwise else "MISMATCH")

    if check:
        assert bitwise, "warm-start result differs from cold-start result"
        assert warm["autotune_calls"] == 0, (
            f"warm replica re-ran autotune {warm['autotune_calls']}x"
        )
        assert warm["jit_builds"] == 0, (
            f"warm replica re-jitted {warm['jit_builds']}x "
            "(executable deserialization regressed to recompile)"
        )
        assert warm["store_hits"] >= 1, "warm replica never hit the store"
        assert ratio >= 10.0, (
            f"warm start only {ratio:.1f}x faster than cold (gate: 10x)"
        )
    return rows


def run(check: bool = False, smoke: bool = False):
    # the trace is already CI-sized; smoke changes nothing, the flag
    # exists so the harness/CI call-shape matches the other benchmarks
    del smoke
    return run_cold_start([], check)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        _child(*sys.argv[2:5])
        sys.exit(0)
    for row in run(check=True, smoke="--smoke" in sys.argv[1:]):
        print(row)
    print("OK: warm replica reached its first bitwise-identical result "
          ">=10x faster than cold autotune+jit, with zero autotune "
          "invocations and zero jit builds")
