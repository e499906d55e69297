"""Fused multi-iteration stencil execution on a block (trapezoid scheme).

This is the single implementation of truth for "apply ``s`` stencil
iterations to a block under the spec's boundary rule".  It is shared by
three executors so they cannot drift apart (docs/DESIGN.md §Executors):

  * the Pallas TPU kernel body (on VMEM-loaded values),
  * the single-device jnp fallback (whole array as one block),
  * the shard_map spatial/hybrid locals (local shard + exchanged halo).

Trapezoid correctness argument: a block carries ``h`` halo rows on each
side.  Each fused iteration invalidates ``r`` rows at each block edge
(they were computed from in-block zero padding instead of true neighbour
data), so after ``s`` iterations rows at distance >= s*r from the edge are
exact.  Callers must provide ``h >= s*r`` and only consume the safe
interior.

Boundary handling (docs/DESIGN.md §Boundary semantics): cells *outside
the global grid* that live inside a block are re-imposed after every
stage by :func:`boundary_fixup` — zeroed (``zero``), set to the constant
(``constant``), or gathered from the clamped nearest edge cell
(``replicate``).  ``periodic`` is the one mode whose row dimension is not
fixed up in-block: the wrapped rows come in as *data* (host wrap padding
or wraparound ppermute halo exchange) and go stale per the same trapezoid
argument, while the column dimensions — always resident in full — are
re-wrapped in-block each stage.
"""
from __future__ import annotations

import functools
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spec import (
    Boundary,
    Stage,
    StencilSpec,
    ZERO_BOUNDARY,
    eval_expr,
)


def _block_stage(stage: Stage, env: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
    """One stage over a block, zero-padding at block edges (same shape out)."""
    shape = next(iter(env.values())).shape
    r = stage.radius
    padded = {n: jnp.pad(a, [(r, r)] * a.ndim) for n, a in env.items()}

    def get_ref(name, offsets):
        idx = tuple(slice(r + o, r + o + s) for o, s in zip(offsets, shape))
        return padded[name][idx]

    return eval_expr(stage.expr, get_ref).astype(stage.dtype)


def boundary_pad(
    a: jnp.ndarray, pads: Sequence[tuple[int, int]], boundary: Boundary
) -> jnp.ndarray:
    """``jnp.pad`` with the fill the boundary rule prescribes."""
    pads = list(pads)
    k = boundary.kind
    if k == "zero":
        return jnp.pad(a, pads)
    if k == "constant":
        return jnp.pad(a, pads, constant_values=boundary.value)
    if k == "replicate":
        return jnp.pad(a, pads, mode="edge")
    if k == "periodic":
        return jnp.pad(a, pads, mode="wrap")
    raise ValueError(f"unknown boundary kind {k!r}")


def grid_mask(
    block_shape: tuple[int, ...],
    row0,
    grid_shape: tuple[int, ...],
    col_pads: tuple[int, ...],
    dtype,
) -> jnp.ndarray:
    """1.0 where the block cell maps to a real grid cell, else 0.0.

    ``row0`` is the global grid row of block row 0 (may be negative /
    traced).  ``col_pads[d]`` is the padding prepended to non-row dim
    ``d+1``.
    """
    ndim = len(block_shape)
    rows = jax.lax.broadcasted_iota(jnp.int32, block_shape, 0) + row0
    mask = (rows >= 0) & (rows < grid_shape[0])
    for d in range(1, ndim):
        cols = jax.lax.broadcasted_iota(jnp.int32, block_shape, d) - col_pads[d - 1]
        mask &= (cols >= 0) & (cols < grid_shape[d])
    return mask.astype(dtype)


def boundary_fixup(
    block: jnp.ndarray,
    row0,
    grid_shape: tuple[int, ...],
    col_pads: tuple[int, ...],
    boundary: Boundary = ZERO_BOUNDARY,
) -> jnp.ndarray:
    """Re-impose the boundary rule on every out-of-grid cell of a block.

    In-grid cells are returned untouched (for replicate/periodic the
    gather is the identity there), so neighbour-exchanged halo rows — real
    data — survive.  Replicate assumes the block physically contains the
    edge cell its out-of-grid cells clamp to; every tiler in the repo
    guarantees that (Pallas tiles span contiguous rows below ``R``, the
    distribution layer checks each device owns a real row).  Periodic
    never fixes the row dimension (wrapped rows arrive as data, see module
    docstring); columns are re-wrapped in place since blocks always hold
    the full column extent.
    """
    kind = boundary.kind
    shape = block.shape
    if kind == "zero":
        return block * grid_mask(shape, row0, grid_shape, col_pads, block.dtype)
    if kind == "constant":
        mask = grid_mask(shape, row0, grid_shape, col_pads, jnp.bool_)
        return jnp.where(mask, block, jnp.asarray(boundary.value, block.dtype))
    out = block
    if kind == "replicate":
        clip = (
            (lambda v: min(max(v, 0), shape[0] - 1))
            if isinstance(row0, (int, np.integer))
            else (lambda v: jnp.clip(v, 0, shape[0] - 1))
        )
        out = _clamp_copy(
            out, 0, clip(-row0), clip(grid_shape[0] - 1 - row0)
        )
    for d in range(1, len(shape)):
        pad = col_pads[d - 1]
        size = grid_shape[d]
        hi = min(max(pad + size - 1, 0), shape[d] - 1)
        if kind == "replicate":
            out = _clamp_copy(out, d, min(pad, shape[d] - 1), hi)
        else:  # periodic
            cols = np.arange(shape[d])
            tgt = np.clip(np.mod(cols - pad, size) + pad, 0, shape[d] - 1)
            out = _static_shift_copy(out, d, tgt - cols)
    return out


def _clamp_copy(x: jnp.ndarray, axis: int, lo, hi) -> jnp.ndarray:
    """``take(x, clip(i, lo, hi), axis)``: cells before index ``lo`` copy
    the slice at ``lo``, cells after ``hi`` the slice at ``hi``.

    Written as selects over axis reductions because the Mosaic lowering
    has no general gather and no dynamic slice of a value.  ``lo``/``hi``
    are Python ints (read by static slicing) or traced int32 values
    broadcastable against ``x`` (read by a masked max over ``axis``,
    which copies the one selected cell exactly, NaN and -0.0 included).
    """
    coords = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    lowest = jnp.asarray(
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
        else jnp.iinfo(x.dtype).min,
        x.dtype,
    )

    def at(i):
        if isinstance(i, (int, np.integer)):
            return jax.lax.slice_in_dim(x, int(i), int(i) + 1, axis=axis)
        return jnp.max(
            jnp.where(coords == i, x, lowest), axis=axis, keepdims=True
        )

    return jnp.where(coords < lo, at(lo), jnp.where(coords > hi, at(hi), x))


def _static_shift_copy(
    x: jnp.ndarray, axis: int, shifts: np.ndarray
) -> jnp.ndarray:
    """``take(x, arange(n) + shifts, axis)`` for a static per-index shift
    vector: one static roll and select per distinct shift (the periodic
    belt needs two or three), since Mosaic lowers no gather."""
    coords = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    out = x
    for sh in sorted(set(int(v) for v in shifts) - {0}):
        hit = np.flatnonzero(shifts == sh)
        sel = (coords >= int(hit[0])) & (coords <= int(hit[-1]))
        if hit[-1] - hit[0] + 1 != len(hit):      # not one contiguous run
            sel = functools.reduce(
                jnp.logical_or, [coords == int(i) for i in hit]
            )
        out = jnp.where(sel, jnp.roll(x, -sh, axis=axis), out)
    return out


def streamed_halo_fixup(
    block: jnp.ndarray,
    env: Mapping[str, jnp.ndarray],
    spec: StencilSpec,
    row0,
    col_pads: tuple[int, ...],
) -> jnp.ndarray:
    """Re-impose a *streamed* (per-request) boundary on a block.

    ``spec.halo_index_inputs`` names one int32 input per dimension whose
    cells hold the global grid coordinate each cell should copy from
    (identity on the real region, clamp target on the padding belt of a
    bucket design).  The per-axis gather composes exactly like
    ``np.pad``'s per-axis edge extension, so after every stage the belt
    holds the smaller real grid's clamped exterior — in every executor,
    since they all compute through this helper.

    Locality: the gather target is converted to block-local coordinates
    (``- row0`` on the tiled/sharded row dim, ``+ col_pads`` on the fully
    resident column dims) and clipped to the block.  Clamp targets are
    the nearest real edge cells, which every tiler/shard holds in any
    block that owns belt cells within the trapezoid-safe depth (the same
    guarantee the non-bucketed replicate fixup relies on); deeper belt
    cells may gather clipped garbage, but their values never reach the
    safe interior within a round and are re-imposed or sliced off
    outside it.

    Clamp-map contract: every halo-index producer in the repo
    (:func:`repro.runtime.bucketing.halo_index_host` and the all-zero
    filler maps) emits per-axis maps of the form ``clip(identity, lo,
    hi)`` — monotone clamps of the axis coordinate.  Composing with the
    block-local shift and clip above preserves that form, so the gather
    is equivalent to *static slicing*: rows below ``lo`` copy row ``lo``,
    rows above ``hi`` copy row ``hi``, the middle is identity.  That is
    what this helper emits — the clamp's ``lo``/``hi`` read off the map
    by reductions along the axis, then :func:`_clamp_copy`'s selects
    instead of a ``take_along_axis`` gather, which the Mosaic lowering
    does not offer.  All-constant filler maps are the degenerate
    ``lo == hi`` clamp and come out of the same select path.
    """
    names = spec.halo_index_inputs
    out = block
    for d, name in enumerate(names):
        idx = env[name]
        tgt = idx - row0 if d == 0 else idx + col_pads[d - 1]
        tgt = jnp.clip(tgt, 0, out.shape[d] - 1).astype(jnp.int32)
        lo = jnp.min(tgt, axis=d, keepdims=True)
        hi = jnp.max(tgt, axis=d, keepdims=True)
        out = _clamp_copy(out, d, lo, hi)
    return out


def fused_iterations_on_block(
    spec: StencilSpec,
    blocks: Mapping[str, jnp.ndarray],
    s: int,
    row0,
    grid_shape: tuple[int, ...],
    col_pads: tuple[int, ...],
    boundary: Boundary | None = None,
) -> jnp.ndarray:
    """Apply ``s`` fused iterations to a block; returns the iterated array.

    ``blocks`` maps every spec input name to a same-shape block (halo rows
    and column padding already included).  Only the ``iterate_input``
    evolves; other inputs are constant across iterations.  ``boundary``
    defaults to the spec's own rule.  Specs carrying streamed halo-index
    inputs (bucketed replicate serving) additionally re-impose the
    per-request boundary via :func:`streamed_halo_fixup` after every
    stage, *before* the block-level boundary rule so out-of-grid cells
    clamp to the re-imposed belt.
    """
    boundary = spec.boundary if boundary is None else boundary
    env = {n: jnp.asarray(b) for n, b in blocks.items()}

    def fixup(a):
        return boundary_fixup(a, row0, grid_shape, col_pads, boundary)

    # Inputs may carry garbage outside the grid (e.g. unmasked host
    # padding); impose the boundary rule before the first iteration too.
    # Streamed specs also re-impose the per-request belt on entry: a block
    # whose copy of the gather source went stale late in the *previous*
    # round can hand a neighbour stale belt rows (real/belt edge
    # straddling a tile or shard boundary) — the entry gather repairs
    # every consumed belt cell from the committed real values before the
    # first stage reads it.
    streamed = bool(spec.halo_index_inputs)
    if streamed:
        src = dict(env)
        env = {
            n: streamed_halo_fixup(a, src, spec, row0, col_pads)
            for n, a in env.items()
        }
    env = {n: fixup(a) for n, a in env.items()}

    def iteration(_, cur):
        stage_env = dict(env)
        stage_env[spec.iterate_input] = cur
        for stage in spec.stages:
            out = _block_stage(stage, stage_env)
            if streamed:
                out = streamed_halo_fixup(out, stage_env, spec, row0, col_pads)
            out = fixup(out)  # the boundary is re-imposed at every stage
            stage_env[stage.name] = out
        return stage_env[spec.output_name]

    # A rolled loop: the block keeps its shape across iterations, and
    # Mosaic's compile time grows with the unrolled body (a 3D tile at
    # s=4 took 90 s to compile unrolled, against 13 s at s=1).
    cur = env[spec.iterate_input]
    if s == 1:
        return iteration(0, cur)
    return jax.lax.fori_loop(0, s, iteration, cur)


def wrap_round_fixup(
    out: jnp.ndarray,
    env: Mapping[str, jnp.ndarray],
    spec: StencilSpec,
) -> jnp.ndarray:
    """Re-impose a streamed periodic wrap margin on the iterate.

    ``spec.wrap_index_inputs`` names one int32 grid-shaped input per
    dimension holding, for every cell, the coordinate it should copy from
    — identity on the real region, ``margin + ((coord - margin) mod S)``
    on the wrap belt of a bucket design.  Executors call this **between
    fused rounds** (never before the first): a round of depth
    ``wrap_round_depth`` stales at most ``wrap_round_depth * radius``
    margin cells, and this global gather refreshes them from the real
    region the round just committed.  Only the iterate needs it —
    constant inputs' wrapped margins never go stale.

    Unlike the per-stage clamp maps (:func:`streamed_halo_fixup`), wrap
    maps are modular, not monotone, so this stays a ``take_along_axis``
    gather; it runs once per round at grid granularity, outside the tile
    loop.
    """
    for d, name in enumerate(spec.wrap_index_inputs):
        tgt = jnp.clip(
            jnp.asarray(env[name]), 0, out.shape[d] - 1
        ).astype(jnp.int32)
        out = jnp.take_along_axis(out, tgt, axis=d)
    return out


def fused_iterations_dense(
    spec: StencilSpec,
    arrays: Mapping[str, jnp.ndarray],
    iterations: int,
    s: int,
) -> jnp.ndarray:
    """Single-device fused execution: rounds of ceil(iter/s) over the full
    grid held as one block.  Matches ``stencil_iterations_ref`` exactly.

    Non-zero boundaries carry an explicit boundary belt: rows get an
    ``s*r``-deep boundary-padded halo per round (for periodic this is the
    wrapped data the in-block fixup never regenerates), columns an
    ``r``-deep belt the per-stage fixup refreshes.

    Specs carrying streamed wrap inputs cap the fused depth per round at
    ``spec.wrap_round_depth`` and re-wrap the iterate's margin between
    rounds (:func:`wrap_round_fixup`).
    """
    grid_shape = spec.shape
    left = iterations
    cur = dict(arrays)
    out = cur[spec.iterate_input]
    boundary = spec.boundary
    r = spec.radius
    first = True
    while left > 0:
        step = min(s, left)
        if spec.wrap_index_inputs:
            step = min(step, max(spec.wrap_round_depth, 1))
            if not first:
                out = wrap_round_fixup(out, cur, spec)
                cur[spec.iterate_input] = out
        first = False
        if boundary.is_zero:
            out = fused_iterations_on_block(
                spec, cur, step, row0=0, grid_shape=grid_shape,
                col_pads=(0,) * (spec.ndim - 1),
            )
        else:
            h = step * r
            pads = [(h, h)] + [(r, r)] * (spec.ndim - 1)
            padded = {
                n: boundary_pad(jnp.asarray(a), pads, boundary)
                for n, a in cur.items()
            }
            ext = fused_iterations_on_block(
                spec, padded, step, row0=-h, grid_shape=grid_shape,
                col_pads=(r,) * (spec.ndim - 1),
            )
            sl = (slice(h, h + grid_shape[0]),) + tuple(
                slice(r, r + c) for c in grid_shape[1:]
            )
            out = ext[sl]
        cur[spec.iterate_input] = out
        left -= step
    return out
