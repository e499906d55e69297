"""Batch-in-grid tile pipelines: the explicitly pipelined kernel path.

SASA's core trick is explicit placement of stencil streams into HBM banks
with overlapped DMA, so every PE's compute hides its memory traffic.  The
TPU analogue is the Pallas grid plus double-buffered HBM->VMEM copies —
but the vmapped serving path (``jax.vmap`` over whole-grid programs in
:mod:`repro.runtime.batching`) sidesteps it: batch entries never share
VMEM tiles and copy/compute overlap is left to XLA.  This module is the
execution idiom that replaces it (docs/DESIGN.md §Kernel layer):

  * :func:`stencil_pallas_batched` — the Pallas kernel iterates a
    ``(batch, tile)`` grid.  Each grid step DMAs one entry's
    ``(tile_rows + 2sr, C_pad)`` block HBM->VMEM; Pallas's grid pipeline
    double-buffers the copy for step ``(b, i+1)`` behind the compute of
    step ``(b, i)``, which is exactly SODA's FIFO-overlap property with
    VMEM standing in for the reuse buffer.  Streamed service inputs
    (``_mask``, halo-index maps, wrap maps) ride the same grid as
    per-entry block operands.
  * :func:`stencil_jnp_pipeline` — the same tile schedule in pure jnp
    for CPU hosts: a ``fori_loop`` over row tiles whose carry holds the
    *next* tile's prefetched block (software double buffering), with the
    batch folded into the block's leading axis so all entries stream
    through one residency.
  * :func:`stencil_run_batched` — the round loop over either executor
    (ceil(iterations/s) launches), with streamed wrap margins re-imposed
    between rounds (:func:`repro.kernels.blockops.wrap_round_fixup`).

Bitwise contract: both pipelines execute the *same tile program* — same
block geometry, same :func:`fused_iterations_on_block` trapezoid — as
``jax.vmap`` of the corresponding per-entry executor.  For the Pallas
pair the conformance suite holds the results **bitwise identical** on
XLA-CPU: vmap batches a ``pallas_call`` by adding a grid dimension,
which is exactly what :func:`stencil_pallas_batched` declares, so both
sides compile the identical kernel body.  The jnp pair agrees to ULP
scale but not always to the bit — the double-buffer carry makes the
loop body different HLO from the vmapped slice-per-step loop, and
XLA-CPU's instruction selection may round division / mul-add chains
differently per program.  (Tile decomposition itself is *not*
bitwise-stable against a dense whole-grid program either; only
identical programs at identical geometry are.)
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import element_block_spec
from repro.core.spec import StencilSpec
from repro.kernels.blockops import (
    boundary_pad,
    fused_iterations_on_block,
    wrap_round_fixup,
)
from repro.kernels.stencil import (
    COMPILER_PARAMS,
    plan_blocks,
    resolve_interpret,
)


def _pad_host_batched(a: jnp.ndarray, spec: StencilSpec, g: dict):
    """Boundary halo + alignment padding on a (B,)-leading array."""
    h, p = g["h"], g["p"]
    R = g["grid_shape"][0]
    bpads = [(0, 0), (h, h)] + [(p, p) for _ in g["col_dims"]]
    a = boundary_pad(a, bpads, spec.boundary)
    apads = [(0, 0), (0, g["rows_in"] - R - 2 * h)]
    for d, c in enumerate(g["col_dims"]):
        apads.append((0, g["padded_cols"][d] - c - 2 * p))
    return jnp.pad(a, apads)


def _out_slice(spec: StencilSpec, g: dict):
    """Strip alignment + column belt from a (B,)-leading padded output."""
    p = g["p"]
    return (slice(None), slice(0, g["grid_shape"][0])) + tuple(
        slice(p, p + c) for c in g["col_dims"]
    )


@functools.partial(
    jax.jit,
    static_argnames=("spec", "s", "tile_rows", "interpret", "align_cols"),
)
def stencil_pallas_batched(
    spec: StencilSpec,
    arrays: Mapping[str, jnp.ndarray],
    s: int,
    tile_rows: int = 256,
    interpret: bool | None = None,
    align_cols: int = 1,
) -> jnp.ndarray:
    """One round of ``s`` fused iterations over a whole batch, with the
    batch axis folded into the Pallas grid.

    Inputs are ``(B,) + spec.shape``; the kernel runs a ``(B, n_tiles)``
    grid where step ``(b, i)`` owns entry ``b``'s row tile ``i`` as a
    ``(1, tile_rows + 2sr, C_pad)`` VMEM block.  Identical tile geometry
    and kernel body to :func:`repro.kernels.stencil.stencil_pallas`, so
    the result is bitwise-identical to vmapping that kernel over the
    batch — the grid layout changes *scheduling*, not the computation.
    """
    g = plan_blocks(spec, s, tile_rows, align_cols)
    names = list(spec.inputs)
    grid_shape = g["grid_shape"]
    h = g["h"]
    ndim = spec.ndim
    B = int(next(iter(arrays.values())).shape[0])

    padded = [
        _pad_host_batched(jnp.asarray(arrays[n]), spec, g) for n in names
    ]
    col_pads = tuple(g["p"] for _ in g["col_dims"])

    def kernel(*refs):
        in_refs, out_ref = refs[:-1], refs[-1]
        i = pl.program_id(1)
        row0 = i * g["tile_rows"] - h
        blocks = {n: r_[...][0] for n, r_ in zip(names, in_refs)}
        res = fused_iterations_on_block(
            spec, blocks, s, row0, grid_shape, col_pads
        )
        sl = (slice(h, h + g["tile_rows"]),) + tuple(
            slice(0, cp) for cp in g["padded_cols"]
        )
        out_ref[...] = res[sl][None]

    # element-indexed input blocks: one batch entry (block size 1 at
    # element offset b), rows at element offset i*tile_rows.
    in_block = (1, g["in_rows"]) + g["padded_cols"]
    in_index = lambda b, i: (b, i * g["tile_rows"]) + (0,) * (ndim - 1)
    # block-indexed output: batch block 1 -> index b, row block tile_rows
    # -> index i.
    out_block = (1, g["tile_rows"]) + g["padded_cols"]
    out_index = lambda b, i: (b, i) + (0,) * (ndim - 1)

    out_padded = pl.pallas_call(
        kernel,
        grid=(B, g["n_tiles"]),
        in_specs=[element_block_spec(in_block, in_index) for _ in names],
        out_specs=pl.BlockSpec(out_block, out_index),
        out_shape=jax.ShapeDtypeStruct(
            (B, g["rows_padded"]) + g["padded_cols"], jnp.dtype(spec.dtype)
        ),
        interpret=resolve_interpret(interpret),
        compiler_params=COMPILER_PARAMS,
        name="stencil_tile_batched",
    )(*padded)

    return out_padded[_out_slice(spec, g)]


@functools.partial(
    jax.jit, static_argnames=("spec", "s", "tile_rows", "align_cols")
)
def stencil_jnp_tiled(
    spec: StencilSpec,
    arrays: Mapping[str, jnp.ndarray],
    s: int,
    tile_rows: int = 256,
    align_cols: int = 1,
) -> jnp.ndarray:
    """Per-entry tile-loop executor (no batch axis): the vmap reference
    for :func:`stencil_jnp_pipeline`.

    Walks the same ``(tile_rows + 2sr)``-row blocks as the pipelined
    path, single-buffered, via ``fori_loop`` + dynamic slices.  vmapping
    this function and running :func:`stencil_jnp_pipeline` trace to the
    same batched tile program, which is what makes the differential
    bitwise on CPU.
    """
    g = plan_blocks(spec, s, tile_rows, align_cols)
    names = list(spec.inputs)
    h = g["h"]
    one = {n: jnp.asarray(arrays[n])[None] for n in names}
    padded = {n: _pad_host_batched(a, spec, g)[0] for n, a in one.items()}
    col_pads = tuple(g["p"] for _ in g["col_dims"])
    blk_shape = (g["in_rows"],) + g["padded_cols"]
    zeros_nd = (0,) * (spec.ndim - 1)

    def fetch(i):
        start = (i * g["tile_rows"],) + zeros_nd
        return {
            n: jax.lax.dynamic_slice(a, start, blk_shape)
            for n, a in padded.items()
        }

    out0 = jnp.zeros(
        (g["rows_padded"],) + g["padded_cols"], jnp.dtype(spec.dtype)
    )

    def step(i, out):
        blocks = fetch(i)
        row0 = i * g["tile_rows"] - h
        res = fused_iterations_on_block(
            spec, blocks, s, row0, g["grid_shape"], col_pads
        )
        sl = (slice(h, h + g["tile_rows"]),)
        return jax.lax.dynamic_update_slice(
            out, res[sl], (i * g["tile_rows"],) + zeros_nd
        )

    out = jax.lax.fori_loop(0, g["n_tiles"], step, out0)
    return out[tuple(sl for sl in _out_slice(spec, g)[1:])]


@functools.partial(
    jax.jit, static_argnames=("spec", "s", "tile_rows", "align_cols")
)
def stencil_jnp_pipeline(
    spec: StencilSpec,
    arrays: Mapping[str, jnp.ndarray],
    s: int,
    tile_rows: int = 256,
    align_cols: int = 1,
) -> jnp.ndarray:
    """One round of ``s`` fused iterations over a whole batch as a
    software double-buffered tile loop (the jnp analogue of the Pallas
    grid pipeline, for CPU hosts).

    Inputs are ``(B,) + spec.shape``.  The ``fori_loop`` carry holds the
    *prefetched* next tile block — the fetch for tile ``i+1`` is issued
    before the compute of tile ``i`` consumes its buffer, giving the
    scheduler a full tile of copy/compute overlap (SNIPPETS.md Snippet
    2's ``emit_pipeline`` decomposition in miniature).  The batch rides
    the block's leading axis, so all B entries stream through one
    buffer residency per tile; the per-tile compute is
    ``jax.vmap(fused_iterations_on_block)``, the same trapezoid the
    per-entry executors run.
    """
    g = plan_blocks(spec, s, tile_rows, align_cols)
    names = list(spec.inputs)
    h = g["h"]
    B = int(next(iter(arrays.values())).shape[0])
    padded = {
        n: _pad_host_batched(jnp.asarray(arrays[n]), spec, g) for n in names
    }
    col_pads = tuple(g["p"] for _ in g["col_dims"])
    blk_shape = (B, g["in_rows"]) + g["padded_cols"]
    zeros_nd = (0,) * (spec.ndim - 1)

    def fetch(i):
        # double-buffer prefetch: clamped at the last tile (the fetched
        # block is discarded)
        i = jnp.minimum(i, g["n_tiles"] - 1)
        start = (0, i * g["tile_rows"]) + zeros_nd
        return {
            n: jax.lax.dynamic_slice(a, start, blk_shape)
            for n, a in padded.items()
        }

    compute = jax.vmap(
        lambda blocks, row0: fused_iterations_on_block(
            spec, blocks, s, row0, g["grid_shape"], col_pads
        ),
        in_axes=(0, None),
    )

    out0 = jnp.zeros(
        (B, g["rows_padded"]) + g["padded_cols"], jnp.dtype(spec.dtype)
    )

    def step(i, carry):
        buf, out = carry
        nxt = fetch(i + 1)           # issue next copy before this compute
        row0 = i * g["tile_rows"] - h
        res = compute(buf, row0)
        out = jax.lax.dynamic_update_slice(
            out, res[:, h:h + g["tile_rows"]],
            (0, i * g["tile_rows"]) + zeros_nd,
        )
        return (nxt, out)

    _, out = jax.lax.fori_loop(0, g["n_tiles"], step, (fetch(0), out0))
    return out[_out_slice(spec, g)]


def stencil_run_batched(
    spec: StencilSpec,
    arrays: Mapping[str, jnp.ndarray],
    iterations: int | None = None,
    s: int = 1,
    tile_rows: int = 256,
    backend: str = "jnp",
    interpret: bool | None = None,
    align_cols: int = 1,
) -> jnp.ndarray:
    """Run the stencil to completion over a batch through the tile
    pipeline: ceil(iterations/s) rounds of the batch-in-grid executor.

    backend: 'jnp' (software double-buffered tile loop), 'pallas'
    (batch-in-grid Pallas kernel; ``interpret=None`` lets the backend
    decide, see :func:`repro.kernels.stencil.resolve_interpret`).
    Specs with streamed wrap margins cap the per-round fused depth at
    ``spec.wrap_round_depth`` and re-wrap the iterate between rounds.
    """
    it = spec.iterations if iterations is None else iterations
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown tile-pipeline backend {backend!r}")
    env = dict(arrays)
    out = env[spec.iterate_input]
    rewrap = jax.vmap(lambda o, e: wrap_round_fixup(o, e, spec))
    left = it
    first = True
    while left > 0:
        step = min(s, left)
        if spec.wrap_index_inputs:
            step = min(step, max(spec.wrap_round_depth, 1))
            if not first:
                out = rewrap(out, {
                    n: jnp.asarray(env[n]) for n in spec.wrap_index_inputs
                })
                env[spec.iterate_input] = out
        first = False
        if backend == "pallas":
            out = stencil_pallas_batched(
                spec, env, step, tile_rows=tile_rows,
                interpret=interpret, align_cols=align_cols,
            )
        else:
            out = stencil_jnp_pipeline(
                spec, env, step, tile_rows=tile_rows, align_cols=align_cols,
            )
        env[spec.iterate_input] = out
        left -= step
    return out
