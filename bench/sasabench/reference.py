"""Running a configuration's plain reference, and the comparison that
decides ``correct``.

A configuration's reference is ``bench/configs/<config>.py``: a function
``step(x)`` of one iteration over ``(..., rows, cols)`` arrays, written in
plain ``jax.numpy`` from the published stencil.  It imports nothing of the
program.  Run in float32 it is the reference; run in bfloat16 it is the
control, the lower precision a later change might be tempted by.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Where the reference runs in a lower precision than the configuration's
#: float32, to show that the comparison fails it.
CONTROL_DTYPE = "bfloat16"


@functools.partial(jax.jit, static_argnames=("step", "dtype"))
def _iterate(step, x, iterations, dtype):
    y = jax.lax.fori_loop(0, iterations, lambda _, v: step(v),
                          x.astype(dtype))
    return y.astype(jnp.float32)


def iterate(step, grids, iterations: int, dtype: str = "float32"):
    """``iterations`` steps of the reference over a ``(B, rows, cols)``
    batch of grids, on JAX's default device, computed in ``dtype``; the
    float32 result stays on the device."""
    return _iterate(step, jnp.asarray(grids), jnp.int32(iterations), dtype)


def run(step, grids: np.ndarray, iterations: int,
        dtype: str = "float32") -> np.ndarray:
    """:func:`iterate`, read back to the host."""
    return np.asarray(iterate(step, grids, iterations, dtype))


def max_abs_err(got: np.ndarray, want: np.ndarray) -> float:
    """Widest gap between served and reference values; ``inf`` where the
    shapes differ or the served values are not finite."""
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got.astype(np.float64) - want)))
