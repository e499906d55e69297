#!/usr/bin/env python3
"""Measure the f32 vector-unit peak of the attached TPU chip.

The f32 rate of a TPU's vector unit (VPU) is not published, and the
stencil kernels run on it, so the peak that ``bench/peaks.json`` divides
by is measured here once, by a fixed Pallas microkernel: each grid step
loads one VMEM-resident block of independent f32 lanes and runs a long
chain of ``x = x * a + b`` on it in registers, so neither HBM nor VMEM
traffic limits it.  Every multiply and every add counts as one operation.
Several block heights and unroll depths are tried and the highest rate
is kept: a peak read too low would let a kernel's roofline share pass
100%.

    python bench/vpu_peak.py      # on one chip; prints one JSON line

It exits non-zero where JAX finds no TPU.
"""
from __future__ import annotations

import json
import math
import sys
import time

STEPS = 2048            # multiply-add pairs per element per grid step
GRID = 1024             # grid steps per call
MIN_S = 0.5             # host-clock seconds each variant is timed over
# (block rows, multiply-adds per loop trip): Mosaic lowers a fori_loop
# either whole or not unrolled, so the body itself holds the chain
VARIANTS = ((64, 8), (128, 8), (256, 8), (256, 16), (512, 8))


def build(rows: int, unroll: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        def body(_, x):
            for _ in range(unroll):
                x = x * 0.999 + 0.001
            return x

        o_ref[...] = jax.lax.fori_loop(0, STEPS // unroll, body, x_ref[...])

    call = pl.pallas_call(
        kernel,
        grid=(GRID,),
        in_specs=[pl.BlockSpec((rows, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((GRID * rows, 128), jnp.float32),
        name="vpu_peak",
    )
    return jax.jit(call), jnp.ones((GRID * rows, 128), jnp.float32)


def ops_per_call(rows: int) -> int:
    """Operations of one call: a multiply and an add per element and step."""
    return 2 * STEPS * GRID * rows * 128


def measure(rows: int, unroll: int) -> float:
    """Operations per second of one variant, over back-to-back calls,
    enqueued together, that span at least MIN_S seconds of the host
    clock."""
    fn, x = build(rows, unroll)
    fn(x).block_until_ready()                      # compile and warm
    t0 = time.perf_counter()
    fn(x).block_until_ready()
    calls = max(math.ceil(MIN_S / (time.perf_counter() - t0)), 2)
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(x)
    out.block_until_ready()
    return calls * ops_per_call(rows) / (time.perf_counter() - t0)


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"vpu_peak: no TPU (JAX found {dev.platform!r})", file=sys.stderr)
        return 1
    rates = {f"{r}x128 unroll {u}": measure(r, u) for r, u in VARIANTS}
    for name, rate in rates.items():
        print(f"  {name}: {rate:.6e} op/s", flush=True)
    print(json.dumps({"device_kind": dev.device_kind,
                      "vpu_f32_op_s": max(rates.values()),
                      "variants": rates}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
