#!/usr/bin/env python3
"""Bring-up smoke of the served stencil path on a TPU.

Drives the path a user's request takes -- DSL, ``analysis.verify``,
``autotune``, ``DesignCache`` over a ``DesignStore``, ``StencilServer``
with a ``StencilScheduler``, the batch-in-grid Pallas kernel compiled
for the chip -- at the paper's grid sizes, and checks every result it
samples against an oracle evaluated on the host CPU, within the
certified bound of ``numerics.tolerance_for``.

    python chip_smoke.py             # one chip: four served deployments,
                                     # then a warm start from the store
    python chip_smoke.py --chips 4   # a four-chip host: four one-chip
                                     # router replicas, then the shard_map
                                     # designs across the four chips

Times are printed for information only; this is no benchmark.  Without a
TPU the script exits non-zero before serving anything.  The last line of
standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
import zlib
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
STORE = ROOT / ".smoke_store"
SEED = 20221
ITERATIONS = 16
MAX_BATCH = 8
CHECKED = 2                  # entries of each kernel compared with the oracle

# (name, stock kernel, request grid shapes, bucketing)
DEPLOYMENTS = (
    ("jacobi2d", "jacobi2d", [(9720, 1024)] * 32, False),
    ("hotspot", "hotspot", [(9720, 1024)] * 16, False),
    ("heat3d", "heat3d", [(9720, 32, 32)] * 16, False),
    ("blur_replicate", "blur_replicate", [
        (9720, 1024), (9000, 1000), (8500, 768), (8193, 600),
        (8192, 1024), (6000, 900), (4097, 700), (4096, 512),
    ], True),
)
# four chips: the router's jacobi2d requests, then the shard_map designs
ROUTER_SHAPE = (9720, 1024)
ROUTER_REQUESTS = 16
SHARDED = (("jacobi2d", (9720, 1024)), ("heat3d_periodic", (9720, 32, 32)))


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


# --------------------------------------------------------------------------
# inputs and the host oracle (made from the seed in any process)
# --------------------------------------------------------------------------


def spec_for(kernel: str, shape):
    from repro.configs import stencils

    return stencils.BENCHMARKS[kernel](shape=tuple(shape),
                                       iterations=ITERATIONS)


def inputs_for(kernel: str, shape, entry: int) -> dict:
    """Request ``entry`` of a kernel: uniform [0, 1) grids from the seed."""
    spec = spec_for(kernel, shape)
    rng = np.random.default_rng((SEED, zlib.crc32(kernel.encode()), entry))
    return {
        n: rng.random(tuple(sh), dtype=np.float32)
        for n, (_, sh) in spec.inputs.items()
    }


def tolerance(kernel: str, shape) -> float:
    """Certified executor-vs-oracle tolerance for every input in [-1, 1]
    (the seed draws from [0, 1)): the static mode of ``tolerance_for``.
    Its data mode is tighter on some kernels but costs minutes of float64
    numpy per full-size entry, which would dominate the run."""
    from repro.core import numerics

    return numerics.tolerance_for(spec_for(kernel, shape), ITERATIONS)


def host_memory() -> str:
    """This machine's memory in use (the cgroup's, where there is one),
    for the log: host memory, not the chip's."""
    for path in ("/sys/fs/cgroup/memory.current",
                 "/sys/fs/cgroup/memory/memory.usage_in_bytes"):
        try:
            return f"{int(Path(path).read_text()) / 2**30:.2f} GiB in use"
        except (OSError, ValueError):
            continue
    return "unknown"


def host_oracle(kernel: str, shape, entry: int) -> np.ndarray:
    """``kernels/ref.py`` evaluated on the host's CPU device."""
    import jax

    from repro.kernels import ref

    with jax.default_device(jax.devices("cpu")[0]):
        run = ref.stencil_run_ref_jit(spec_for(kernel, shape), ITERATIONS)
        return np.asarray(run(inputs_for(kernel, shape, entry)))


def compare(label: str, got: np.ndarray, want: np.ndarray, tol: float):
    err = float(np.max(np.abs(got.astype(np.float64) - want)))
    scale = float(np.max(np.abs(want)))
    ok = got.shape == want.shape and np.isfinite(got).all() and err <= tol
    print(f"  {label}: shape {got.shape}, max|out| {scale:.6g}, "
          f"max|err| {err:.6g} <= tolerance {tol:.6g}: "
          f"{'ok' if ok else 'MISMATCH'}", flush=True)
    check(ok, f"{label} disagrees with the host oracle")


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------


def require_tpu(count: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}, platform {dev.platform}, "
          f"device_kind {dev.device_kind!r}, device count {len(devices)}",
          flush=True)
    check(dev.platform == "tpu",
          f"no TPU: JAX found {dev.platform!r} devices; this smoke runs "
          "on a TPU only")
    check(len(devices) >= count,
          f"needs {count} TPU chip(s), JAX found {len(devices)}")
    return dev


def device_line() -> str:
    import jax

    devices = jax.devices()
    return json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }})


# --------------------------------------------------------------------------
# one chip: served deployments
# --------------------------------------------------------------------------


def kernel_report(name: str, reg) -> None:
    """Print the ranked design(s) of a registration and check that each
    runs the compiled Pallas kernel."""
    runners = (
        {b: e.runner.inner for b, e in reg.cached.buckets.items()}
        if reg.bucketed else {reg.spec.shape: reg.cached.runner}
    )
    for shape, run in runners.items():
        cfg = run.cfg
        texts = [c.as_text() for c in run.executables.values()]
        custom = bool(texts) and all("tpu_custom_call" in t for t in texts)
        print(f"  {name} {'x'.join(map(str, shape))}: design {cfg.variant} "
              f"s={cfg.s} tile_rows={cfg.tile_rows} "
              f"buffer_depth={cfg.buffer_depth} | path {run.path}, backend "
              f"{run.backend}, interpret {run.interpret} | tpu_custom_call "
              f"{custom} in {len(texts)} executable(s)", flush=True)
        check(run.backend == "pallas", f"{name}: backend {run.backend!r}")
        check(run.interpret is False, f"{name}: Pallas interpreter on")
        check(custom, f"{name}: compiled program has no tpu_custom_call")


def serve_deployment(server, scheduler, name, kernel, shapes, bucketing):
    """Register, serve every request through the scheduler, and return
    the outputs of the checked entries (by entry index)."""
    from repro.serve import StencilRequest

    t0 = time.perf_counter()
    reg = server.register(name, spec_for(kernel, shapes[0]),
                          iterations=ITERATIONS, bucketing=bucketing)
    first = time.perf_counter() - t0
    checked = {0, len(shapes) - 1}
    t0 = time.perf_counter()
    tickets = [
        scheduler.submit(StencilRequest(name, inputs_for(kernel, sh, i)))
        for i, sh in enumerate(shapes)
    ]
    outs = {}
    for i in range(len(tickets)):
        out = tickets[i].result(timeout=900.0)
        tickets[i] = None
        check(out.shape == tuple(shapes[i]), f"{name} entry {i}: shape")
        if i in checked or (name == "jacobi2d" and i < MAX_BATCH):
            outs[i] = out.copy()        # not a view pinning its batch
    served = time.perf_counter() - t0
    ctr = reg.counters
    batches = max(server.stats()[name]["batches"], 1)
    print(f"[{name}] {len(shapes)} requests, {batches} batches; register "
          f"incl. compile {first:.3f} s, serving {served / batches:.3f} s "
          f"per batch (host clock, informational); host memory "
          f"{host_memory()}", flush=True)
    check(ctr.failed_requests == 0, f"{name}: failed requests")
    kernel_report(name, reg)
    return outs


def one_chip() -> None:
    from repro.compat import use_compile_cache
    from repro.serve import StencilRequest, StencilScheduler, StencilServer

    print(f"compile cache: {use_compile_cache()}", flush=True)
    require_tpu(1)
    shutil.rmtree(STORE, ignore_errors=True)
    server = StencilServer(max_batch=MAX_BATCH, store_dir=STORE)
    served = {}
    with StencilScheduler(server, gather_window_s=0.05) as scheduler:
        for name, kernel, shapes, bucketing in DEPLOYMENTS:
            served[name] = serve_deployment(
                server, scheduler, name, kernel, shapes, bucketing
            )

    print("host oracle (CPU):", flush=True)
    for name, kernel, shapes, _ in DEPLOYMENTS:
        for i in (0, len(shapes) - 1):
            compare(f"{name} entry {i}", served[name][i],
                    host_oracle(kernel, shapes[i], i),
                    tolerance(kernel, shapes[i]))

    # a second replica over the same store: nothing to tune or compile
    name, kernel, shapes, _ = DEPLOYMENTS[0]
    t0 = time.perf_counter()
    warm = StencilServer(max_batch=MAX_BATCH, store_dir=STORE)
    warm.register(name, spec_for(kernel, shapes[0]), iterations=ITERATIONS)
    outs = warm.serve([
        StencilRequest(name, inputs_for(kernel, shapes[i], i))
        for i in range(MAX_BATCH)
    ])
    dt = time.perf_counter() - t0
    st = warm.stats()["_cache"]
    same = sum(np.array_equal(o, served[name][i]) for i, o in enumerate(outs))
    print(f"[store warm start] {name}: autotune_calls "
          f"{st['autotune_calls']}, jit_builds {st['jit_builds']}, "
          f"store_hits {st['store_hits']}, bitwise equal {same}/"
          f"{len(outs)}; register + first batch {dt:.3f} s "
          "(informational)", flush=True)
    check(st["autotune_calls"] == 0, "warm start re-ran autotune")
    check(st["jit_builds"] == 0, "warm start recompiled")
    check(same == len(outs), "warm start output differs")


# --------------------------------------------------------------------------
# four chips: router replicas, then shard_map designs
# --------------------------------------------------------------------------


def router_phase() -> dict:
    """Four one-chip replicas behind the router, started while this
    process holds no JAX backend (a backend here would hold the chips)."""
    from repro.serve import StencilRequest
    from repro.serve.router import StencilRouter, tpu_host_chips

    n_chips = tpu_host_chips()
    print(f"router: {n_chips} TPU chips on this host", flush=True)
    check(n_chips >= 4, f"no four-chip TPU host: {n_chips} chip(s) found")
    store = STORE / "router"
    shutil.rmtree(store, ignore_errors=True)
    shape = ROUTER_SHAPE
    outs = {}
    with StencilRouter(store, replicas=4, max_batch=MAX_BATCH,
                       spawn_timeout_s=300.0) as router:
        for rep, dev in router.devices.items():
            print(f"  {rep}: {dev}", flush=True)
        chips = {d["chip"] for d in router.devices.values()}
        check(len(chips) == 4
              and all(d["platform"] == "tpu" for d in router.devices.values()),
              f"replicas do not hold four distinct chips: {router.devices}")
        t0 = time.perf_counter()
        router.register("jacobi2d", spec_for("jacobi2d", shape),
                        iterations=ITERATIONS)
        pending = [
            router.submit(StencilRequest(
                "jacobi2d", inputs_for("jacobi2d", shape, i)))
            for i in range(ROUTER_REQUESTS)
        ]
        for i, fut in enumerate(pending):
            out = fut.result(timeout=900.0)
            if i in (0, ROUTER_REQUESTS - 1):
                outs[i] = out
        dt = time.perf_counter() - t0
        done = {n: h.get("scheduler", {}).get("completed")
                for n, h in router.ping().items()}
        print(f"  served {ROUTER_REQUESTS} jacobi2d requests in {dt:.3f} s "
              f"incl. registration (informational); completed per replica "
              f"{done}", flush=True)
    return outs


def sharded_phase(name: str, shape) -> None:
    import jax

    from repro.core import analysis
    from repro.runtime import DesignCache, build_batched_runner

    spec = spec_for(name, shape)
    tuned = DesignCache().design(spec, iterations=ITERATIONS)
    verdicts = analysis.preflight(
        tuned.spec, [p.config for p in tuned.ranking], len(jax.devices()),
        iterations=ITERATIONS, batched=True,
    )
    cfg = next(p.config for p, v in zip(tuned.ranking, verdicts)
               if p.config.k == 4 and v.feasible)
    run = build_batched_runner(tuned.spec, cfg, iterations=ITERATIONS,
                               strict=True)
    check(run.path == "shard_map" and run.n_devices == 4,
          f"{name}: {run.path} on {run.n_devices} device(s)")
    entries = [inputs_for(name, shape, i) for i in range(CHECKED)]
    staged = run.stage({n: np.stack([e[n] for e in entries])
                        for n in spec.inputs})
    first = next(iter(staged.values()))
    shards = sorted((s.device.id, s.index[1].start, s.index[1].stop)
                    for s in first.addressable_shards)
    print(f"[shard_map] {name} {'x'.join(map(str, shape))}: design "
          f"{cfg.variant} k={cfg.k} s={cfg.s}; row shards (device, start, "
          f"stop) {shards}", flush=True)
    t0 = time.perf_counter()
    out = run.finalize(run.dispatch(staged))
    print(f"  compile + first dispatch {time.perf_counter() - t0:.3f} s "
          "(informational)", flush=True)
    for i in range(CHECKED):
        compare(f"{name} entry {i}", out[i], host_oracle(name, shape, i),
                tolerance(name, shape))


def four_chips() -> None:
    from repro.compat import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    routed = router_phase()
    require_tpu(4)        # the fleet is closed: the chips are free
    print("host oracle (CPU) for the routed requests:", flush=True)
    for i, out in routed.items():
        compare(f"router jacobi2d entry {i}", out,
                host_oracle("jacobi2d", ROUTER_SHAPE, i),
                tolerance("jacobi2d", ROUTER_SHAPE))
    for name, shape in SHARDED:
        sharded_phase(name, shape)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: router replicas and shard_map designs on a "
                             "four-chip host, and nothing else")
    args = parser.parse_args(argv)
    sys.path.insert(0, str(SRC))
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)()
    print(f"smoke wall time {time.perf_counter() - t0:.3f} s (host clock, "
          "informational)", flush=True)
    print(device_line(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
