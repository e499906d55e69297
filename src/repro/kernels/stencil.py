"""Generic Pallas TPU stencil kernel with temporal fusion (SASA single-PE,
TPU-native re-design).

FPGA -> TPU hardware adaptation (docs/DESIGN.md §FPGA-to-TPU mapping has
the full narrative):

  * SODA's 512-bit coalesced reuse FIFO becomes a VMEM-resident row tile:
    one (tile_rows + 2*s*r, C_pad) block is DMA'd HBM->VMEM per grid step,
    all reuse happens in VMEM registers/slices instead of FIFO taps.
  * The cascade of ``s`` temporal PEs becomes ``s`` fused iterations over
    the VMEM tile (temporal blocking): HBM traffic drops by ~s at the cost
    of a 2*s*r-row compute trapezoid per tile — the same redundant-compute
    vs. reuse trade the paper's hybrid designs make, moved down one level
    of the memory hierarchy.
  * Fine-grained parallelism U (16 PUs on a 512b AXI word) becomes the
    8x128 VPU lanes; we keep the full (padded) column dimension in the
    block so the lane dimension is dense and 128-aligned.

The kernel is generated from the same :class:`StencilSpec` the reference
executor consumes, and computes with the shared trapezoid helper in
:mod:`repro.kernels.blockops`, so kernel and oracle cannot drift.

Boundary conditions (docs/DESIGN.md §Boundary semantics): host padding is
boundary-aware — the row halo and column belt are filled with zeros, the
constant, the clamped edge, or the wrapped opposite edge — and the kernel
body re-imposes the rule per stage through the shared
:func:`~repro.kernels.blockops.boundary_fixup`.  For ``periodic`` the
wrap-filled row halo *is* the opposite edge's data and goes stale across
fused iterations exactly like a neighbour tile's halo (same trapezoid
safety argument); each round re-pads from the full updated grid.
"""
from __future__ import annotations

import functools
import math
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import element_block_spec
from repro.core.platform import DEFAULT_TPU
from repro.core.spec import StencilSpec
from repro.kernels.blockops import boundary_pad, fused_iterations_on_block


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def plan_blocks(
    spec: StencilSpec, s: int, tile_rows: int, align_cols: int = 1
) -> dict:
    """Static geometry for the fused kernel.

    ``align_cols`` pads the innermost dim up to a multiple (128 on real
    TPU for lane alignment; 1 in tests to keep interpret-mode shapes small).

    The input window of a tile is ``in_rows`` deep: the ``tile_rows +
    2h`` rows the trapezoid needs, rounded up to a multiple of 8, the
    sublane tiling the Mosaic lowering demands of a block's second-minor
    dim.  The extra rows sit below the bottom halo, where the trapezoid
    argument keeps them from reaching the tile's output.  ``rows_in`` is
    the row count of the padded input array the windows stride over.
    """
    r = spec.radius
    h = s * r                      # inter-tile row halo
    p = r                          # zero column pad (mask re-zeros each iter)
    grid_shape = spec.shape
    R = grid_shape[0]
    col_dims = tuple(grid_shape[1:])
    padded_cols = tuple(c + 2 * p for c in col_dims)
    if padded_cols:
        padded_cols = padded_cols[:-1] + (
            _round_up(padded_cols[-1], align_cols),
        )
    n_tiles = max(math.ceil(R / tile_rows), 1)
    rows_padded = n_tiles * tile_rows
    in_rows = _round_up(tile_rows + 2 * h, 8)
    return dict(
        r=r, h=h, p=p, grid_shape=grid_shape, col_dims=col_dims,
        padded_cols=padded_cols, n_tiles=n_tiles, rows_padded=rows_padded,
        in_rows=in_rows, rows_in=rows_padded - tile_rows + in_rows,
        tile_rows=tile_rows,
    )


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode for a ``pallas_call``: an explicit choice wins,
    otherwise the default backend decides (compiled on a TPU, the
    interpreter everywhere else).  Every kernel entry point resolves its
    ``interpret=None`` default here, so no caller on a TPU gets the
    interpreter by omission."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


#: Scoped-VMEM limit of every stencil ``pallas_call``: the same budget the
#: analytical model ranks tiles against (``TPUPlatform.vmem_bytes``).
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=DEFAULT_TPU.vmem_bytes)


def vmem_bytes_estimate(spec: StencilSpec, s: int, tile_rows: int) -> int:
    """Per-grid-step VMEM working set (used by the analytical model's
    resource bound and reported in the Fig. 8 analogue benchmark)."""
    g = plan_blocks(spec, s, tile_rows, align_cols=128)
    cols = 1
    for c in g["padded_cols"]:
        cols *= c
    block = g["in_rows"] * cols * spec.itemsize
    out = g["tile_rows"] * cols * spec.itemsize
    # inputs + iterate working copy + one stage temp + output, double-buffered
    return 2 * ((spec.num_inputs + 2) * block + out)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "s", "tile_rows", "interpret", "align_cols"),
)
def stencil_pallas(
    spec: StencilSpec,
    arrays: Mapping[str, jnp.ndarray],
    s: int,
    tile_rows: int = 256,
    interpret: bool | None = None,
    align_cols: int = 1,
) -> jnp.ndarray:
    """Run ``s`` fused stencil iterations over the full grid via pallas_call."""
    g = plan_blocks(spec, s, tile_rows, align_cols)
    names = list(spec.inputs)
    grid_shape = g["grid_shape"]
    R = grid_shape[0]
    h, p = g["h"], g["p"]
    ndim = spec.ndim

    # ---- host-side padding: rows by (h, h + tile alignment), cols by p.
    # The boundary halo is laid down first (wrap/edge/constant fills need
    # real-data adjacency), then the lane/tile alignment zeros go outside
    # it, where the trapezoid argument keeps them from reaching the grid.
    def pad_host(a):
        bpads = [(h, h)] + [(p, p) for _ in g["col_dims"]]
        a = boundary_pad(a, bpads, spec.boundary)
        apads = [(0, g["rows_in"] - R - 2 * h)]
        for d, c in enumerate(g["col_dims"]):
            apads.append((0, g["padded_cols"][d] - c - 2 * p))
        return jnp.pad(a, apads)

    padded = [pad_host(jnp.asarray(arrays[n])) for n in names]
    col_pads = tuple(p for _ in g["col_dims"])

    def kernel(*refs):
        in_refs, out_ref = refs[:-1], refs[-1]
        i = pl.program_id(0)
        row0 = i * g["tile_rows"] - h  # global grid row of block row 0
        blocks = {n: r_[...] for n, r_ in zip(names, in_refs)}
        res = fused_iterations_on_block(
            spec, blocks, s, row0, grid_shape, col_pads
        )
        sl = (slice(h, h + g["tile_rows"]),) + tuple(
            slice(0, cp) for cp in g["padded_cols"]
        )
        out_ref[...] = res[sl]

    in_block = (g["in_rows"],) + g["padded_cols"]
    in_index = lambda i: (i * g["tile_rows"],) + (0,) * (ndim - 1)
    out_block = (g["tile_rows"],) + g["padded_cols"]
    out_index = lambda i: (i,) + (0,) * (ndim - 1)

    out_padded = pl.pallas_call(
        kernel,
        grid=(g["n_tiles"],),
        in_specs=[element_block_spec(in_block, in_index) for _ in names],
        out_specs=pl.BlockSpec(out_block, out_index),
        out_shape=jax.ShapeDtypeStruct(
            (g["rows_padded"],) + g["padded_cols"], jnp.dtype(spec.dtype)
        ),
        interpret=resolve_interpret(interpret),
        compiler_params=COMPILER_PARAMS,
        name="stencil_tile",
    )(*padded)

    sl = (slice(0, R),) + tuple(slice(p, p + c) for c in g["col_dims"])
    return out_padded[sl]
