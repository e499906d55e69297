"""Multi-device stencil execution: the five SASA parallelisms on a TPU mesh.

FPGA -> TPU mapping (Sec. 3 of the paper re-derived for ICI-connected
chips; docs/DESIGN.md §FPGA-to-TPU mapping carries the full narrative):

  temporal    cascaded PEs, tiles streamed PE->PE     cross-device software
              through FIFOs, one HBM bank touched     pipeline: row tiles flow
                                                      through a ppermute chain,
                                                      device j applies iter j.
  spatial_r   row partitions + redundant halo         one up-front ppermute of
              compute, no inter-PE wires              iter*r rows, then local
                                                      trapezoid, no further comm.
  spatial_s   row partitions + border streaming       r-row ppermute halo
              wires each iteration                    exchange each iteration.
  hybrid_r    k spatial groups x s temporal stages,   up-front iter*r exchange,
              no sync (growing trapezoids)            rounds of s fused
                                                      (VMEM-blocked) iterations.
  hybrid_s    k groups x s stages, first stage        s*r-row ppermute per round,
              exchanges halo*s rows per round         rounds of s fused iters.

Every runner is a jit(shard_map(...)) program over a 1-D ("sp",) device
mesh, numerically equivalent to :func:`repro.kernels.ref.stencil_iterations_ref`
(tests enforce this on 8 forced host devices).

Boundary semantics (docs/DESIGN.md §Boundary semantics): for the default
``zero`` boundary ppermute conveniently zero-fills non-participating edge
devices, exactly the exterior-zero rule.  ``periodic`` boundaries map
onto a *wraparound* ppermute ring — device 0's upper halo arrives from
device k-1 — which is the ICI analogue of the paper's border-streaming
wires closed into a torus.  ``constant``/``replicate`` are re-imposed by
the shared per-stage boundary fixup inside each local trapezoid; the
non-row dimensions, resident in full on every device, carry an explicit
boundary belt the fixup refreshes.
"""
from __future__ import annotations

import math
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import axis_size, pvary, shard_map
from repro.core.model import InfeasibleDesign, ParallelismConfig
from repro.core.spec import StencilSpec
from repro.kernels.blockops import boundary_pad, fused_iterations_on_block

AXIS = "sp"


# --------------------------------------------------------------------------
# Halo exchange primitives (the "border streaming" wires)
# --------------------------------------------------------------------------


def exchange_halo(local: jnp.ndarray, h: int, axis: str = AXIS,
                  wrap: bool = False):
    """Return (up_halo, down_halo): h rows from the previous / next device.

    With ``wrap=False`` edge devices receive zeros (exterior-zero boundary
    for the global grid; padded-row shards are additionally handled by the
    boundary fixup).  With ``wrap=True`` the permutation closes into a
    ring — device 0 receives device k-1's bottom rows and vice versa — the
    wraparound halo exchange periodic boundaries need; on a single device
    the ring degenerates to the shard's own opposite edge.
    """
    k = axis_size(axis)
    if h == 0 or (k == 1 and not wrap):
        zeros = jnp.zeros((h,) + local.shape[1:], local.dtype)
        return zeros, zeros
    if k == 1:
        return local[-h:], local[:h]
    if wrap:
        down_perm = [(i, (i + 1) % k) for i in range(k)]
        up_perm = [(i, (i - 1) % k) for i in range(k)]
    else:
        down_perm = [(i, i + 1) for i in range(k - 1)]  # my bottom rows -> next
        up_perm = [(i, i - 1) for i in range(1, k)]     # my top rows -> previous
    up_halo = lax.ppermute(local[-h:], axis, down_perm)   # from device i-1
    down_halo = lax.ppermute(local[:h], axis, up_perm)    # from device i+1
    return up_halo, down_halo


def _extend(local, h, axis=AXIS, wrap=False):
    up, down = exchange_halo(local, h, axis, wrap)
    return jnp.concatenate([up, local, down], axis=0)


# --------------------------------------------------------------------------
# shard_map local programs
# --------------------------------------------------------------------------


def _local_rows(R_pad: int, k: int) -> int:
    return R_pad // k


def _spatial_s_local(spec, iterations, grid_shape, R_k, col_pads, wrap):
    r = spec.radius

    def fn(arrays: dict):
        idx = lax.axis_index(AXIS)
        row0 = idx * R_k - r
        consts = {
            n: _extend(a, r, wrap=wrap) for n, a in arrays.items()
            if n != spec.iterate_input
        }
        cur = arrays[spec.iterate_input]
        for _ in range(iterations):
            ext = dict(consts)
            ext[spec.iterate_input] = _extend(cur, r, wrap=wrap)
            out = fused_iterations_on_block(
                spec, ext, 1, row0, grid_shape, col_pads
            )
            cur = out[r:r + R_k]
        return cur

    return fn


def _spatial_r_local(spec, iterations, grid_shape, R_k, col_pads, wrap):
    r = spec.radius
    H = min(iterations * r, R_k)

    def fn(arrays: dict):
        idx = lax.axis_index(AXIS)
        row0 = idx * R_k - H
        ext = {n: _extend(a, H, wrap=wrap) for n, a in arrays.items()}
        cur = ext[spec.iterate_input]
        # one HBM round trip per iteration (faithful Spatial_R: the fused
        # trapezoid depth is 1; the halo just shrinks by r per iteration)
        for _ in range(iterations):
            ext[spec.iterate_input] = cur
            cur = fused_iterations_on_block(
                spec, ext, 1, row0, grid_shape, col_pads
            )
        return cur[H:H + R_k]

    return fn


def _hybrid_local(spec, iterations, grid_shape, R_k, s, streaming: bool,
                  col_pads, wrap):
    """hybrid_s (streaming=True): exchange s*r rows per round.
    hybrid_r (streaming=False): exchange iter*r rows once, then rounds."""
    r = spec.radius

    def fn(arrays: dict):
        idx = lax.axis_index(AXIS)
        if streaming:
            consts = {
                n: a for n, a in arrays.items() if n != spec.iterate_input
            }
            cur = arrays[spec.iterate_input]
            left = iterations
            while left > 0:
                step = min(s, left)
                h = step * r
                row0 = idx * R_k - h
                ext = {n: _extend(a, h, wrap=wrap) for n, a in consts.items()}
                ext[spec.iterate_input] = _extend(cur, h, wrap=wrap)
                out = fused_iterations_on_block(
                    spec, ext, step, row0, grid_shape, col_pads
                )
                cur = out[h:h + R_k]
                left -= step
            return cur
        # hybrid_r: single up-front exchange of the full run's halo
        H = min(iterations * r, R_k)
        row0 = idx * R_k - H
        ext = {n: _extend(a, H, wrap=wrap) for n, a in arrays.items()}
        cur = ext[spec.iterate_input]
        left = iterations
        while left > 0:
            step = min(s, left)
            ext[spec.iterate_input] = cur
            cur = fused_iterations_on_block(
                spec, ext, step, row0, grid_shape, col_pads
            )
            left -= step
        return cur[H:H + R_k]

    return fn


def _temporal_pipeline_local(spec, iterations, grid_shape, tile_rows, k,
                             col_pads):
    """SODA-analogue temporal pipeline: row tiles stream through the device
    chain, device j applies stencil iteration j of the current round.

    Per round of up-to-k iterations, the loop runs T + k - 1 steps (the
    paper's d*(s_t-1) pipeline-fill delay, Eq. 4).  Input is replicated
    (one logical HBM, as on the FPGA where temporal designs touch a single
    bank); device k-1 materialises the output, which is then broadcast.
    """
    r = spec.radius
    h = k * r
    R = grid_shape[0]
    T = math.ceil(R / tile_rows)
    boundary = spec.boundary

    def _row_pad(a):
        """Boundary halo around the real rows, then tile-alignment zeros.

        The replicated array may carry host row padding past ``R``; the
        boundary fill (wrap/edge/constant) must be laid against the real
        grid edge, so the halo is applied to the first ``R`` rows and the
        alignment padding re-appended outside it.
        """
        zeros = [(0, 0)] * (spec.ndim - 1)
        if boundary.is_zero:
            return jnp.pad(a, [(h, h)] + zeros)
        padded = boundary_pad(a[:R], [(h, h)] + zeros, boundary)
        return jnp.pad(padded, [(0, a.shape[0] - R)] + zeros)

    def one_round(arrays, active):
        """active: number of live stages this round (idle PEs pass through)."""
        j = lax.axis_index(AXIS)
        cur_global = arrays[spec.iterate_input]  # replicated (R_pad, C...)
        consts = {n: a for n, a in arrays.items() if n != spec.iterate_input}
        padded = _row_pad(cur_global)
        consts_padded = {n: _row_pad(a) for n, a in consts.items()}
        tile_shape = (tile_rows + 2 * h,) + tuple(cur_global.shape[1:])
        # carries become device-varying after the first ppermute; mark the
        # initial zeros as varying so the fori_loop carry types match
        out0 = pvary(jnp.zeros_like(cur_global), (AXIS,))
        buf0 = pvary(jnp.zeros(tile_shape, cur_global.dtype), (AXIS,))

        def step(n, state):
            buf, out = state
            tile_idx = n - j
            safe_idx = jnp.clip(tile_idx, 0, T - 1)
            start = (safe_idx * tile_rows,) + (0,) * (spec.ndim - 1)
            loaded = lax.dynamic_slice(padded, start, tile_shape)
            # stage 0 ingests from "HBM"; later stages use the pipelined buf
            buf = jnp.where(j == 0, loaded, buf)
            const_tiles = {
                n: lax.dynamic_slice(a, start, tile_shape)
                for n, a in consts_padded.items()
            }
            row0 = safe_idx * tile_rows - h
            env = dict(const_tiles)
            env[spec.iterate_input] = buf
            applied = fused_iterations_on_block(
                spec, env, 1, row0, grid_shape, col_pads
            )
            applied = jnp.where(j < active, applied, buf)  # idle stage
            # last live stage commits the tile's valid center to the output
            center = lax.dynamic_slice(
                applied, (h,) + (0,) * (spec.ndim - 1),
                (tile_rows,) + tuple(cur_global.shape[1:]),
            )
            valid = (tile_idx >= 0) & (tile_idx < T) & (j == active - 1)
            prev = lax.dynamic_slice(out, start[:1] + (0,) * (spec.ndim - 1),
                                     center.shape)
            out = lax.dynamic_update_slice(
                out, jnp.where(valid, center, prev),
                (safe_idx * tile_rows,) + (0,) * (spec.ndim - 1),
            )
            # stream the tile to the next stage
            k_ = axis_size(AXIS)
            if k_ > 1:
                buf = lax.ppermute(
                    applied, AXIS, [(i, i + 1) for i in range(k_ - 1)]
                )
            else:
                buf = applied
            return buf, out

        _, out = lax.fori_loop(0, T + k - 1, step, (buf0, out0))
        # only the last live stage holds real output rows; broadcast it
        contrib = jnp.where(j == active - 1, out, jnp.zeros_like(out))
        return lax.psum(contrib, AXIS)

    def fn(arrays: dict):
        cur = arrays[spec.iterate_input]
        left = iterations
        env = dict(arrays)
        while left > 0:
            active = min(k, left)
            env[spec.iterate_input] = cur
            cur = one_round(env, active)
            left -= active
        return cur

    return fn


def _with_col_belt(local, spec: StencilSpec, boundary, p: int):
    """Wrap a local program with a boundary belt on the non-row dims.

    Columns are resident in full on every device, so the belt is filled
    locally (edge/wrap/constant of the shard's own columns equals the
    global rule) and sliced back off after the local trapezoid; the
    per-stage fixup inside the trapezoid keeps it current.
    """
    cpads = [(0, 0)] + [(p, p)] * (spec.ndim - 1)

    def fn(arrays: dict):
        ext = {n: boundary_pad(a, cpads, boundary) for n, a in arrays.items()}
        out = local(ext)
        sl = (slice(None),) + tuple(
            slice(p, p + c) for c in spec.shape[1:]
        )
        return out[sl]

    return fn


# --------------------------------------------------------------------------
# Public runner builder
# --------------------------------------------------------------------------


def build_runner(
    spec: StencilSpec,
    cfg: ParallelismConfig,
    iterations: int | None = None,
    devices=None,
    tile_rows: int = 64,
    batched: bool = False,
):
    """Build a jitted multi-device runner for a parallelism configuration.

    Returns ``(run, mesh)`` where ``run(arrays_host) -> np.ndarray`` places
    inputs with the configuration's sharding, executes, and gathers.

    With ``batched=True`` the runner takes arrays with a leading batch
    axis — ``(B,) + spec.shape`` — and evaluates B independent grids in
    one dispatch: the local shard program is vmapped over the batch axis
    while rows stay sharded over the mesh, so one compiled design serves
    many grids (the serving hot path; see :mod:`repro.runtime.batching`).
    """
    it = spec.iterations if iterations is None else iterations
    if spec.wrap_index_inputs:
        # TODO(distribute): re-imposing a streamed wrap margin between
        # rounds needs a collective gather across shards (the wrap source
        # rows live on the opposite device).  Until that lands, shard_map
        # serving keeps the wide iterations*radius periodic margin and
        # narrow-margin specs stay single-device; the auto-tuner's
        # feasibility retry falls back to the next candidate.
        raise InfeasibleDesign(
            "streamed wrap margins (wrap_index_inputs) are single-device "
            "only; shard_map designs require the wide periodic margin"
        )
    n_dev = cfg.devices_needed
    if devices is None:
        devices = jax.devices()[:n_dev]
    k = len(devices)
    mesh = Mesh(np.array(devices), (AXIS,))
    R = spec.rows
    grid_shape = spec.shape
    boundary = spec.boundary
    wrap = boundary.kind == "periodic"
    # non-zero boundaries carry an explicit column belt the per-stage
    # fixup refreshes (zero keeps the seed's implicit zero-pad columns)
    p_col = 0 if boundary.is_zero else spec.radius
    col_pads = (p_col,) * (spec.ndim - 1)

    if cfg.variant == "temporal":
        R_pad = math.ceil(R / tile_rows) * tile_rows
        local = _temporal_pipeline_local(
            spec, it, grid_shape, tile_rows, k, col_pads
        )
        in_spec = P()   # replicated: one logical HBM bank
        out_spec = P()
    else:
        R_pad = math.ceil(R / k) * k
        R_k = R_pad // k
        if cfg.variant in ("spatial_r", "hybrid_r") and it * spec.radius > R_k:
            raise InfeasibleDesign(
                f"{cfg.variant} needs iter*r <= rows/device "
                f"({it}*{spec.radius} > {R_k}); the auto-tuner excludes "
                "such configs (halo would span multiple neighbours)"
            )
        if wrap and R_pad != R:
            raise InfeasibleDesign(
                f"periodic boundary needs rows divisible by the spatial "
                f"degree ({R} rows over k={k} devices leaves "
                f"{R_pad - R} padding rows that would break the "
                "wraparound halo adjacency); the auto-tuner falls back to "
                "the next candidate"
            )
        if boundary.kind == "replicate" and (k - 1) * R_k > R - 1:
            raise InfeasibleDesign(
                f"replicate boundary needs every device to own at least "
                f"one real grid row ({R} rows over k={k} devices leaves "
                "an all-padding shard that cannot clamp to the edge); "
                "the auto-tuner falls back to the next candidate"
            )
        if cfg.variant == "spatial_s":
            local = _spatial_s_local(
                spec, it, grid_shape, R_k, col_pads, wrap
            )
        elif cfg.variant == "spatial_r":
            local = _spatial_r_local(
                spec, it, grid_shape, R_k, col_pads, wrap
            )
        elif cfg.variant == "hybrid_s":
            local = _hybrid_local(
                spec, it, grid_shape, R_k, max(cfg.s, 1), True, col_pads,
                wrap,
            )
        elif cfg.variant == "hybrid_r":
            local = _hybrid_local(
                spec, it, grid_shape, R_k, max(cfg.s, 1), False, col_pads,
                wrap,
            )
        else:
            raise ValueError(cfg.variant)
        in_spec = P(AXIS)
        out_spec = P(AXIS)

    if p_col:
        local = _with_col_belt(local, spec, boundary, p_col)

    names = list(spec.inputs)
    if batched:
        # batch axis is unsharded and invisible to the local program.
        # With cfg.batch_tile the batch is folded into a sequential grid
        # of batch_tile-wide vmapped chunks (the shard_map analogue of
        # the batch-in-grid tile pipeline): entries stream through the
        # same local-program residency instead of widening every
        # intermediate by the whole batch.  Falls back to one plain vmap
        # when the batch does not tile evenly.
        vm = jax.vmap(local)
        bt = cfg.batch_tile

        def local_batched(arrays: dict):
            B = next(iter(arrays.values())).shape[0]
            if bt and B > bt and B % bt == 0:
                chunked = {
                    n: a.reshape((B // bt, bt) + a.shape[1:])
                    for n, a in arrays.items()
                }
                out = jax.lax.map(vm, chunked)
                return out.reshape((B,) + out.shape[2:])
            return vm(arrays)

        local = local_batched
        if in_spec != P():
            in_spec = P(None, *in_spec)
            out_spec = P(None, *out_spec)
    row_axis = 1 if batched else 0

    @jax.jit
    def sharded_fn(arrays: dict):
        return shard_map(
            local, mesh=mesh,
            in_specs=({n: in_spec for n in names},),
            out_specs=out_spec,
        )(arrays)

    # The three dispatch phases are exposed separately so serving layers can
    # overlap host staging of micro-batch N+1 with device execution of
    # micro-batch N (async double-buffered submit): ``stage`` does host->
    # device placement, ``dispatch`` enqueues the computation without
    # blocking, ``finalize`` blocks (np.asarray) and strips row padding.
    def stage(arrays_host: Mapping[str, jnp.ndarray]) -> dict:
        padded = {}
        for n in names:
            a = jnp.asarray(arrays_host[n])
            if R_pad != R:
                pads = [(0, 0)] * a.ndim
                pads[row_axis] = (0, R_pad - R)
                a = jnp.pad(a, pads)
            padded[n] = jax.device_put(
                a, NamedSharding(mesh, in_spec)
            )
        return padded

    def dispatch(staged: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        return sharded_fn(dict(staged))

    def finalize(out: jnp.ndarray) -> np.ndarray:
        out = np.asarray(out)
        return out[:, :R] if batched else out[:R]

    def run(arrays_host: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        return finalize(dispatch(stage(arrays_host)))

    run.stage = stage
    run.dispatch = dispatch
    run.finalize = finalize
    run.mesh = mesh
    run.sharded_fn = sharded_fn
    run.R_pad = R_pad
    run.batched = batched
    return run
