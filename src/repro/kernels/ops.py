"""Jit'd public entry points over the stencil executors.

``stencil_run`` is what the SASA executor calls once the auto-tuner has
chosen a configuration; it handles the round structure (ceil(iter/s)
kernel launches, with a smaller fused depth for a ragged last round).
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp

from repro.core.spec import StencilSpec
from repro.kernels import ref as _ref
from repro.kernels.blockops import fused_iterations_dense, wrap_round_fixup
from repro.kernels.stencil import stencil_pallas


@functools.partial(
    jax.jit, static_argnames=("spec", "iterations", "s")
)
def stencil_fused_jnp(
    spec: StencilSpec,
    arrays: Mapping[str, jnp.ndarray],
    iterations: int,
    s: int,
) -> jnp.ndarray:
    """Fused-round execution in pure jnp (fast path on CPU hosts)."""
    return fused_iterations_dense(spec, dict(arrays), iterations, s)


def stencil_run(
    spec: StencilSpec,
    arrays: Mapping[str, jnp.ndarray],
    iterations: int | None = None,
    s: int = 1,
    tile_rows: int = 256,
    backend: str = "jnp",
    interpret: bool | None = None,
    align_cols: int = 1,
) -> jnp.ndarray:
    """Run the stencil to completion with fusion depth ``s``.

    backend: 'ref' (oracle), 'jnp' (fused dense), 'pallas' (TPU kernel;
    ``interpret=None`` compiles it on a TPU and interprets it elsewhere).
    """
    it = spec.iterations if iterations is None else iterations
    if backend == "ref":
        return _ref.stencil_iterations_ref(spec, arrays, it)
    if backend == "jnp":
        return stencil_fused_jnp(spec, dict(arrays), it, min(s, it))
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    env = dict(arrays)
    out = env[spec.iterate_input]
    left = it
    first = True
    while left > 0:
        step = min(s, left)
        if spec.wrap_index_inputs:
            step = min(step, max(spec.wrap_round_depth, 1))
            if not first:
                out = wrap_round_fixup(out, env, spec)
                env[spec.iterate_input] = out
        first = False
        out = stencil_pallas(
            spec, env, step, tile_rows=tile_rows,
            interpret=interpret, align_cols=align_cols,
        )
        env[spec.iterate_input] = out
        left -= step
    return out
