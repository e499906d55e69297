"""Batched stencil execution: one compiled design, many independent grids.

This is the serving analogue of SASA/SODA amortizing a single FPGA
bitstream across many invocations: the expensive artefact (an auto-tuned,
jitted design) is built once and then fed batches of grids, with the batch
axis threaded through whichever executor the design uses:

  * single-device designs with pipeline knobs (``cfg.buffer_depth >= 2``)
    run the batch-in-grid tile pipeline (:mod:`repro.kernels.pipeline`):
    the batch axis is folded into the kernel grid with explicitly
    double-buffered HBM->VMEM copies, so all B grids stream through one
    VMEM-tile residency with scheduled copy/compute overlap;
  * plain single-device designs run the single-PE fused kernel under
    ``jax.vmap`` (the legacy one-shot path, still the differential
    reference: both paths run the same tile program, bitwise-identical
    on a fixed backend);
  * multi-device designs run the same shard_map local programs vmapped
    over the batch axis (see ``build_runner(batched=True)``; with
    ``cfg.batch_tile`` the batch is chunked into a sequential grid of
    vmapped tiles), so rows stay sharded across the mesh while B grids
    ride one collective schedule.

Batch-axis semantics: every array in a batch call is ``(B,) + spec.shape``
and batch entries are fully independent — there is no halo exchange or any
other coupling across the batch axis, and the spec's boundary rule applies
per grid.

Every runner exposes three dispatch phases for the async serving loop —
``run.stage(arrays)`` (host -> device placement), ``run.dispatch(staged)``
(enqueue without blocking), ``run.finalize(out)`` (block + gather to
numpy) — with ``run(arrays)`` the validated synchronous composition.
On single-device runners each phase is a profiler span on the host
thread that runs it (``sasa.stage``, ``sasa.dispatch``,
``sasa.finalize``; see :mod:`repro.serve.engine`); the shard_map
runners' phases carry none.  Single-device runners
(``run.stages_grids``) also stage a batch given as per-input lists of
grids: each grid crosses to the device once, ``pad`` slots repeat the
first grid's device buffer, and the ``(B,) + grid`` operand is stacked
on the device, so batch padding never crosses the host link and the
host never copies the batch.

:func:`build_bucket_runner` wraps a runner compiled for a padded canonical
**bucket** shape so it serves any grid that fits inside the bucket, with
the real grid's boundary rule — zero, constant, replicate, or periodic —
re-imposed from per-request streamed inputs (mask, halo-index maps, or
host-streamed wrap margins; see :mod:`repro.runtime.bucketing`); results
are bit-identical to executing the same design unpadded.
"""
from __future__ import annotations

import warnings
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.distribute import build_runner
from repro.core.model import InfeasibleDesign, ParallelismConfig
from repro.core.spec import StencilSpec
from repro.kernels import ops, pipeline
from repro.kernels.stencil import resolve_interpret
from repro.runtime.bucketing import bucket_plan


@jax.jit
def _stack_grids(grids: list) -> jnp.ndarray:
    """One ``(B,) + grid`` operand from B device-resident grids (jit
    caches one program per batch width, grid shape and dtype)."""
    return jnp.stack(grids)


class DegradedDesignWarning(RuntimeWarning):
    """A design is executing with less parallelism than its config claims."""


def is_degraded(cfg: ParallelismConfig, n_avail: int) -> bool:
    """True when a pool of ``n_avail`` devices cannot realise ``cfg``'s
    parallelism.  The one sanctioned exception is a temporal design on a
    one-device host: the PE cascade degenerates to fused rounds on one
    chip with the fusion depth (and the analytical model's single-chip
    prediction) preserved."""
    n_dev = min(cfg.devices_needed, n_avail)
    return n_dev < cfg.devices_needed and not (
        cfg.variant == "temporal" and n_dev <= 1
    )


def degraded_message(cfg: ParallelismConfig, n_avail: int) -> str:
    n_dev = min(cfg.devices_needed, n_avail)
    return (
        f"design {cfg.variant}(k={cfg.k}, s={cfg.s}) needs "
        f"{cfg.devices_needed} device(s) but only {n_avail} are available; "
        f"executing on {n_dev} loses the configured parallelism while "
        f"run.cfg still claims it"
    )


def devices_needed(cfg: ParallelismConfig) -> int:
    """Device count a config occupies (see ParallelismConfig.devices_needed)."""
    return cfg.devices_needed


def resolve_backend(backend: str) -> str:
    """'auto' picks the Pallas kernel on TPU, the jnp executor elsewhere
    (interpret-mode Pallas is a validation tool, not a serving path)."""
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def validate_batch(
    spec: StencilSpec,
    arrays: Mapping[str, np.ndarray],
    exact: bool = True,
) -> tuple[int, tuple[int, ...]]:
    """Check a batched input dict against ``spec``; returns ``(B, grid)``.

    Unknown array names raise (a typo'd input would otherwise be silently
    dropped and the stencil served with the wrong data), as do missing
    inputs and inconsistent batch shapes.  ``exact=True`` pins the grid
    to ``spec.shape``; ``exact=False`` (the bucket runner) accepts any
    uniform grid shape of the right rank and returns it.
    """
    unknown = sorted(set(arrays) - set(spec.inputs))
    if unknown:
        raise ValueError(
            f"unknown input(s) {unknown} for spec {spec.name!r} "
            f"(spec inputs: {sorted(spec.inputs)})"
        )
    full = None
    for n in spec.inputs:
        if n not in arrays:
            raise ValueError(
                f"batched runner missing input {n!r} "
                f"(spec inputs: {sorted(spec.inputs)})"
            )
        shape = tuple(jnp.shape(arrays[n]))
        if exact and (
            len(shape) != spec.ndim + 1 or shape[1:] != tuple(spec.shape)
        ):
            raise ValueError(
                f"batched runner expects {n} shaped (B,) + {spec.shape}, "
                f"got {shape}"
            )
        if full is None:
            if len(shape) != spec.ndim + 1:
                raise ValueError(
                    f"batched runner expects {n} shaped (B,) + grid, "
                    f"got {shape}"
                )
            full = shape
        elif shape != full:
            raise ValueError(
                f"inconsistent batch shapes: {n} is {shape}, "
                f"expected {full}"
            )
    return full[0], full[1:]


def build_batched_runner(
    spec: StencilSpec,
    cfg: ParallelismConfig,
    iterations: int | None = None,
    devices=None,
    tile_rows: int = 64,
    backend: str = "auto",
    interpret: bool | None = None,
    align_cols: int = 1,
    strict: bool = False,
):
    """Compile a runner mapping ``{name: (B,) + spec.shape}`` -> ``(B,) +
    spec.shape`` for a chosen parallelism configuration.

    Single-device configs use the single-PE kernel; multi-device configs
    use the batched shard_map runner.  A config needing more devices than
    the pool provides is **degraded**: it executes, but with less
    parallelism than ``run.cfg`` claims.  Degradation warns
    (:class:`DegradedDesignWarning`) or raises under ``strict=True``; the
    one sanctioned silent case is a temporal design on a one-device host,
    where the PE cascade degenerates to fused rounds on one chip with the
    fusion depth (and the analytical model's single-chip prediction)
    preserved.  The returned callable carries ``.path`` ("single_pe",
    "tile_pipeline", or "shard_map"), ``.backend``, ``.interpret``
    (whether a Pallas kernel runs in the interpreter), ``.n_devices``,
    ``.devices_requested``,
    and ``.degraded`` for reporting and cache keying.
    """
    it = spec.iterations if iterations is None else iterations
    avail = list(devices) if devices is not None else jax.devices()
    need = devices_needed(cfg)
    n_dev = min(need, len(avail))
    degraded = is_degraded(cfg, len(avail))
    if degraded:
        msg = degraded_message(cfg, len(avail))
        if strict:
            raise InfeasibleDesign(msg)
        warnings.warn(msg, DegradedDesignWarning, stacklevel=2)

    if n_dev <= 1:
        bk = resolve_backend(backend)
        interp = resolve_interpret(interpret) if bk == "pallas" else None
        s = max(min(cfg.s, it), 1)
        tile = cfg.tile_rows or tile_rows

        if cfg.buffer_depth >= 2:
            # Batch-in-grid tile pipeline: the batch axis is folded into
            # the kernel grid with explicitly double-buffered HBM->VMEM
            # copies (Pallas grid pipeline on TPU, software-prefetched
            # fori_loop on CPU hosts) instead of vmapping whole-grid
            # programs.  Same tile program as the vmapped path, so
            # results are bitwise-identical on a fixed backend.
            def batched_fn(arrays: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
                return pipeline.stencil_run_batched(
                    spec, arrays, it, s=s, tile_rows=tile, backend=bk,
                    interpret=interp, align_cols=align_cols,
                )

            fn = jax.jit(batched_fn)
            path = "tile_pipeline"
        else:

            def one_grid(arrays: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
                return ops.stencil_run(
                    spec, arrays, it, s=s, tile_rows=tile, backend=bk,
                    interpret=interp, align_cols=align_cols,
                )

            fn = jax.jit(jax.vmap(one_grid))
            path = "single_pe"

        def stage(arrays: Mapping[str, jnp.ndarray], pad: int = 0) -> dict:
            """Place one batch on the device.  An input given as one
            array is the whole batch; one given as a list of grids is
            sent grid by grid, ``pad`` slots repeating its first grid's
            device buffer, and stacked on the device."""
            with jax.profiler.TraceAnnotation("sasa.stage"):
                staged = {}
                for n in spec.inputs:
                    a = arrays[n]
                    if isinstance(a, list):
                        grids = jax.device_put(a)
                        staged[n] = _stack_grids(grids + grids[:1] * pad)
                    else:
                        staged[n] = jax.device_put(jnp.asarray(a))
                return staged

        def dispatch(staged: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
            with jax.profiler.TraceAnnotation("sasa.dispatch"):
                return fn(dict(staged))

        def finalize(out: jnp.ndarray) -> np.ndarray:
            with jax.profiler.TraceAnnotation("sasa.finalize"):
                return np.asarray(out)

        mesh, n_used, jitted, stages_grids = None, 1, fn, True
    else:
        bk, interp = "shard_map", None      # XLA programs, no Pallas kernel
        inner = build_runner(
            spec, cfg, iterations=it, devices=avail[:n_dev],
            tile_rows=tile_rows, batched=True,
        )
        stage, dispatch, finalize = inner.stage, inner.dispatch, inner.finalize
        path, mesh, n_used = "shard_map", inner.mesh, n_dev
        jitted = None   # shard_map programs are not AOT-persistable (yet)
        stages_grids = False    # its stage row-pads one host array per input

    def run(arrays: Mapping[str, jnp.ndarray]) -> np.ndarray:
        validate_batch(spec, arrays)
        return finalize(dispatch(stage(arrays)))

    run.spec = spec
    run.cfg = cfg
    run.iterations = it
    run.path = path
    run.backend = bk
    run.interpret = interp
    run.mesh = mesh
    run.n_devices = n_used
    run.devices_requested = need
    run.degraded = degraded
    run.stage = stage
    run.dispatch = dispatch
    run.finalize = finalize
    # stage() also takes per-input grid lists and a pad count (see the
    # module docstring); False: it takes one host array per input only
    run.stages_grids = stages_grids
    # non-blocking completion poll over a dispatch()'s output: the
    # continuous-batching scheduler reaps finished micro-batches without
    # stalling its admission loop (falls back to "ready" = blocking reap
    # on jax versions without Array.is_ready)
    run.ready = compat.is_ready
    # the underlying jit-wrapped batched program (single-device paths):
    # what the persistent design store AOT-lowers, compiles, and
    # serializes per input signature (None = not AOT-persistable)
    run.jitted = jitted
    return run


def build_bucket_runner(
    spec: StencilSpec,
    bucket_shape: Sequence[int],
    cfg: ParallelismConfig,
    iterations: int | None = None,
    devices=None,
    tile_rows: int = 64,
    backend: str = "auto",
    interpret: bool | None = None,
    align_cols: int = 1,
    strict: bool = False,
    inner=None,
    wrap_rounds: int | None = None,
):
    """Streamed-boundary wrapper: a design compiled for ``bucket_shape``
    serving any fitting grid with the spec's exact boundary semantics.

    The compiled artefact is a batched runner for the **streamed bucket
    spec** (:func:`repro.runtime.bucketing.bucket_spec`); the wrapper
    stages each request through the bucket's host plan
    (:class:`repro.runtime.bucketing.BucketPlan`): inputs are laid into
    the bucket with the boundary-appropriate margin fill (zeros/constant,
    clamped edge, or the wrapped periodic halo computed from the *real*
    shape at pad time) alongside the per-request streamed service inputs
    — the ``_mask`` woven into every stage and, for replicate, the
    per-dimension halo-index maps the in-kernel per-stage gather
    consumes.  Interior results are bit-identical to executing the same
    design unpadded, for every boundary mode.

    ``run(arrays)`` takes one uniform-shape batch ``{name: (B,) + grid}``
    with ``grid + 2 * margins <= bucket_shape`` per dimension and returns
    ``(B,) + grid``.  Serving layers that mix grid shapes inside one
    micro-batch stage each entry through ``run.plan`` and drive
    ``run.stage`` / ``run.dispatch`` / ``run.finalize`` directly, slicing
    each entry's region out of the bucket-shaped output.

    Pass ``inner`` to wrap an already-compiled batched runner for the
    streamed bucket spec (the design-cache path) instead of compiling
    here.  ``wrap_rounds`` (periodic only) serves from the narrow
    ``wrap_rounds * radius`` margin with streamed wrap maps re-imposing
    the wrap between fused rounds — single-device executors only.
    """
    bucket_shape = tuple(int(b) for b in bucket_shape)
    plan = bucket_plan(
        spec, bucket_shape, iterations=iterations, wrap_rounds=wrap_rounds
    )
    mspec = plan.mspec
    if inner is None:
        inner = build_batched_runner(
            mspec, cfg, iterations=iterations, devices=devices,
            tile_rows=tile_rows, backend=backend, interpret=interpret,
            align_cols=align_cols, strict=strict,
        )

    def run(arrays: Mapping[str, np.ndarray]) -> np.ndarray:
        B, grid = validate_batch(spec, arrays, exact=False)
        padded = {
            n: plan.place_entry(np.asarray(arrays[n]), batched=True)
            for n in spec.inputs
        }
        for sname, svc in plan.service_entry(grid).items():
            padded[sname] = np.broadcast_to(
                svc[None], (B,) + bucket_shape
            )
        out = inner(padded)
        return out[(slice(None),) + plan.out_index(grid)]

    run.spec = spec
    run.masked_spec = mspec
    run.mask_name = plan.mask_name
    run.bucket_shape = bucket_shape
    run.plan = plan
    run.wrap_rounds = plan.wrap_rounds
    run.inner = inner
    run.cfg = inner.cfg
    run.iterations = inner.iterations
    run.path = inner.path
    run.backend = inner.backend
    run.interpret = inner.interpret
    run.n_devices = inner.n_devices
    run.devices_requested = inner.devices_requested
    run.degraded = inner.degraded
    run.stage = inner.stage
    run.dispatch = inner.dispatch
    run.finalize = inner.finalize
    run.ready = getattr(inner, "ready", compat.is_ready)
    run.jitted = getattr(inner, "jitted", None)
    return run
