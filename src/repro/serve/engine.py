"""Stencil serving engine: micro-batched, bucketed, async-dispatched
execution of cached compiled designs.

The production-facing front of the runtime subsystem.  A server owns a
:class:`repro.runtime.DesignCache`; clients register stencil designs (DSL
text or :class:`StencilSpec`) and then submit grids.  The serving flow is

  register(name, dsl)  ── autotune (ranking cached) ── compile batched
                          runner (jit cached) ── optional warmup dispatch
  submit(name, arrays) ── validated, queued (thread-safe)
  flush()              ── queued requests grouped by design (and, with
                          bucketing, by bucket shape), chunked into
                          micro-batches of ``max_batch`` grids, staged to
                          device, dispatched through a bounded in-flight
                          queue, unpadded

**Shape bucketing** (``bucketing=True`` or a
:class:`repro.runtime.ShapeBucketer`): a registered design is a *logical*
kernel that serves any grid shape its bucketer accepts, under **any**
boundary mode.  Each request is routed to a padded canonical bucket; one
streamed-boundary design per bucket is auto-tuned and compiled on first
use (all memoized in the shared cache), and grids of different sizes
sharing a bucket ride the same micro-batch, each carrying its own
streamed service inputs — the exterior mask, replicate halo-index maps,
or host-streamed periodic wrap margins (docs/DESIGN.md §Boundaries ×
bucketed serving).  Without bucketing, requests must match the
registered spec's exact shape (the pre-bucketing contract).

**Async double-buffered dispatch** (``async_dispatch=True``, the
default): each micro-batch is staged (to the device, see below) and
dispatched without blocking; the host then stages micro-batch N+1
while the device executes micro-batch N, and only blocks
(``jax.block_until_ready`` via the runner's ``finalize``) when the
bounded in-flight queue (``max_inflight``) is full or the flush drains.
``async_dispatch=False`` restores strictly synchronous dispatch for
debugging/benchmark baselines; results are identical either way.

**Batch-axis semantics** (shared with :mod:`repro.runtime.batching`): one
dispatch evaluates ``(B,) + bucket_shape`` arrays where the B grids are
fully independent — no halo exchange, reduction, or any other coupling
crosses the batch axis, and the spec's boundary rule applies per grid
(per *real* grid under bucketing, via the streamed inputs).  Requests for
different designs never share a batch.  Short final chunks are padded up
to the compiled batch size (so a design compiles exactly one batched
program) and the padding's outputs are discarded.

**Staging.** In exact-shape mode the host copies nothing: each request's
grid crosses to the device once, the padding slots repeat the first
grid's device buffer, and the runner stacks the batch on the device
(``runner.stages_grids``; see :mod:`repro.runtime.batching`).  A runner
that cannot (the shard_map runners, a plain callable) gets one host
array per input, stacked by :func:`host_batch`.  Under bucketing the
host lays each grid into its bucket and stacks the batch.

Per-design counters (``stats()``): requests served, batches dispatched,
design-cache hit/miss for the register call, compile/warmup seconds,
execution latency (``exec_*``: count / total / mean / max seconds of a
batch from the start of its staging to the end of its readback; under
async dispatch that includes the time the batch waits behind the other
batch in flight, and overlapping batches' latencies overlap too), queue
wait (``queued_requests``, ``queue_wait_total_s``: seconds from admission
to the start of staging, summed over requests; only
:class:`repro.serve.StencilScheduler` counts it, the flush path's queue
is a barrier and leaves both at 0), requests lost to dispatch faults
(whose tickets resolve via ``failures``), batches stacked on the device
(``device_batches``) and host-to-device bytes sent by staging
(``staged_bytes``), and — for bucketed designs — per-bucket
hit/miss/request counters.

The phases of a batch are profiler spans (``jax.profiler.TraceAnnotation``)
on the host thread that runs them, so a profiler trace puts every device
idle gap beside the program phase around it: ``sasa.prepare``
(:meth:`StencilServer._prepare`: the grid lists, or under bucketing the
host placement and stack), ``sasa.stage`` (the runner's ``device_put``
and device stack), ``sasa.dispatch`` (the
enqueue; a lazy compile lands inside it), ``sasa.finalize`` (readback to
numpy) and ``sasa.resolve`` (unpadding the batch and resolving its
tickets).  The five are siblings, none inside another, and split what
``exec_*`` times.  With no profiler running a span costs about a
microsecond.

The LM token-serving engine lives in :mod:`repro.serve.lm`; its classes
are re-exported here for backward compatibility.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Mapping

import jax
import numpy as np

# backward-compatible re-exports (pre-runtime engine.py held the LM engine)
from repro.serve.lm import Request, ServeEngine  # noqa: F401
from repro.runtime.bucketing import ShapeBucketer
from repro.runtime.cache import (
    BucketedDesign,
    DesignCache,
    default_cache,
    structural_fingerprint,
)


@dataclasses.dataclass
class StencilRequest:
    """One grid to evaluate under a registered design."""

    design: str
    arrays: Mapping[str, np.ndarray]   # each shaped like one grid


@dataclasses.dataclass
class DesignCounters:
    cache_hit: bool = False            # register() served fully from cache
    build_time_s: float = 0.0          # ranking + jit trace time (0 on hit)
    warmup_time_s: float = 0.0
    requests: int = 0
    batches: int = 0
    padded_grids: int = 0              # throwaway grids added for batch pad
    failed_requests: int = 0           # requests lost to dispatch faults
    exec_count: int = 0
    exec_total_s: float = 0.0
    exec_max_s: float = 0.0
    queued_requests: int = 0           # requests whose queue wait is summed
    queue_wait_total_s: float = 0.0    # admission to staging (scheduler)
    device_batches: int = 0            # batches stacked on the device
    staged_bytes: int = 0              # host-to-device bytes sent by stage

    @property
    def exec_mean_s(self) -> float:
        return self.exec_total_s / self.exec_count if self.exec_count else 0.0

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["exec_mean_s"] = self.exec_mean_s
        return d


def host_batch(batch: Mapping, pad: int) -> dict:
    """One host array per input of a prepared batch: a grid list is
    stacked with its first grid repeated ``pad`` times; an array is
    already the batch."""
    return {
        n: np.stack(v + v[:1] * pad) if isinstance(v, list) else v
        for n, v in batch.items()
    }


def _chained(runner) -> bool:
    """Whether ``runner`` has the stage/dispatch/finalize phases (else
    it is a plain callable over a host batch)."""
    return all(callable(getattr(runner, p, None))
               for p in ("stage", "dispatch", "finalize"))


@dataclasses.dataclass
class _Registered:
    name: str
    cached: object          # runtime CachedDesign, or BucketedDesign
    counters: DesignCounters
    iterations: int | None = None      # as passed at register time
    # static-analysis findings from registration-time verification
    # (repro.core.analysis.Diagnostic tuples; empty = clean)
    diagnostics: tuple = ()

    @property
    def bucketed(self) -> bool:
        return isinstance(self.cached, BucketedDesign)

    @property
    def spec(self):
        return self.cached.spec if self.bucketed else self.cached.design.spec

    @property
    def config(self):
        """The chosen config (exact mode) or per-bucket configs (bucketed)."""
        if not self.bucketed:
            return self.cached.design.config
        return {b: e.config for b, e in self.cached.buckets.items()}

    def bucket_for(self, shape):
        return self.cached.bucket_for(shape)


@dataclasses.dataclass
class _InFlight:
    """A dispatched, not-yet-materialised micro-batch."""

    reg: _Registered
    items: list                       # [(ticket, request, shape), ...]
    out: object                       # device array (possibly still computing)
    finalize: object                  # runner.finalize: device -> np, blocks
    post: object                      # np batch -> {ticket: np grid}
    pad: int
    t0: float


class StencilServer:
    """Micro-batching server over cached, batched stencil designs.

    ``max_batch`` bounds grids per dispatch.  ``warmup=True`` (default)
    pushes one zero batch through a freshly compiled design at register
    time so the first real request never pays the compile.  ``bucketing``
    (True / a :class:`ShapeBucketer`) turns registrations into
    multi-geometry logical kernels; ``max_buckets`` caps each bucketed
    registration's ladder with LRU eviction of the least-recently-hit
    bucket design; ``async_dispatch`` + ``max_inflight`` control the
    double-buffered dispatch loop; ``strict`` refuses (rather than warns
    about) designs degraded by a too-small device pool and refuses
    registrations carrying error-severity static-analysis findings
    (:mod:`repro.core.analysis`).  ``store_dir`` points the server at a
    persistent :class:`repro.runtime.DesignStore` (the FPGA-bitstream
    analogue on disk): rankings, compiled executables, and serving
    telemetry survive the process, so a restarted replica — or a fresh
    replica sharing the directory — cold-starts to its first
    bitwise-identical result without re-autotuning or re-jitting
    (docs/DESIGN.md §Persistent design store).
    """

    def __init__(
        self,
        max_batch: int = 8,
        platform=None,
        devices=None,
        cache: DesignCache | None = None,
        warmup: bool = True,
        backend: str = "auto",
        tile_rows: int = 64,
        bucketing: bool | ShapeBucketer | None = None,
        async_dispatch: bool = True,
        max_inflight: int = 2,
        strict: bool = False,
        max_buckets: int | None = None,
        store_dir=None,
    ):
        assert max_batch >= 1
        assert max_inflight >= 1
        self.max_batch = max_batch
        self.platform = platform
        self.devices = devices
        if store_dir is not None:
            # a persistent replica: own store-backed cache (rankings +
            # executables read/written through disk, telemetry restored).
            # A shared in-process cache and a store-backed one are
            # configured through cache= directly — passing both here
            # would be ambiguous about which memoization the server owns.
            if cache is not None:
                raise ValueError(
                    "pass either cache= (optionally DesignCache(store=...)) "
                    "or store_dir=, not both"
                )
            cache = DesignCache(store=store_dir)
        self.cache = cache if cache is not None else default_cache()
        self.warmup = warmup
        self.backend = backend
        self.tile_rows = tile_rows
        self.bucketing = bucketing
        self.async_dispatch = async_dispatch
        self.max_inflight = max_inflight
        self.strict = strict
        self.max_buckets = max_buckets
        self._designs: dict[str, _Registered] = {}
        self._queue: list[tuple[int, StencilRequest, tuple]] = []
        self._lock = threading.Lock()
        self.failures: dict[int, Exception] = {}   # ticket -> dispatch fault
        self.completed: dict[int, np.ndarray] = {}  # ticket -> result
        self._next_ticket = 0

    # ------------------------------------------------------------------
    # design registration
    # ------------------------------------------------------------------

    def _bucketer_for(self, bucketing) -> ShapeBucketer | None:
        b = self.bucketing if bucketing is None else bucketing
        if not b:
            return None
        return b if isinstance(b, ShapeBucketer) else ShapeBucketer()

    def register(
        self,
        name: str,
        source_or_spec,
        iterations: int | None = None,
        bucketing: bool | ShapeBucketer | None = None,
    ) -> _Registered:
        """Auto-tune + compile (both through the design cache) and warm up.

        With bucketing (per-call override of the server default), the
        registration is a logical kernel: only the bucket containing the
        spec's declared shape is compiled/warmed now, further buckets
        lazily on first request.  Re-registering a name with the same
        design and iterations is idempotent; re-registering it with a
        different one raises.

        Registration runs the static verifier
        (:func:`repro.core.analysis.verify`): findings are attached to
        the returned registration's ``diagnostics``, and under
        ``strict`` any error-severity finding refuses the registration
        with a :class:`repro.core.analysis.VerificationError` before
        anything compiles.
        """
        bucketer = self._bucketer_for(bucketing)
        if name in self._designs:
            existing = self._designs[name]
            from repro.runtime.cache import _as_spec, spec_fingerprint

            spec = _as_spec(source_or_spec)
            # bucketed designs are shape-agnostic: compare structure only
            fp = (structural_fingerprint(spec) if existing.bucketed
                  else spec_fingerprint(spec))
            have = (existing.cached.structural if existing.bucketed
                    else existing.cached.fingerprint)
            policy_changed = (
                existing.bucketed != bool(bucketer)
                or (existing.bucketed
                    and existing.cached.bucketer != bucketer)
            )
            if fp != have or iterations != existing.iterations \
                    or policy_changed:
                raise ValueError(
                    f"design {name!r} is already registered with a "
                    "different spec, iteration count, or bucketing "
                    "policy; pick a new name"
                )
            return existing

        from repro.core import analysis
        from repro.runtime.cache import _as_spec

        spec0 = _as_spec(source_or_spec)
        fn = analysis.verify_or_raise if self.strict else analysis.verify
        diags = tuple(fn(
            spec0, iterations=iterations, bucketed=bucketer is not None,
        ))
        # every registration carries its certified rounding-error bound
        from repro.core import numerics

        diags += (numerics.bound_diagnostic(spec0, iterations=iterations),)

        if bucketer is not None:
            bucketed = self.cache.bucketed(
                source_or_spec, bucketer=bucketer, platform=self.platform,
                iterations=iterations, devices=self.devices,
                tile_rows=self.tile_rows, backend=self.backend,
                strict=self.strict, max_buckets=self.max_buckets,
            )
            entry = bucketed.runner_for(bucketed.spec.shape, count=0)
            ctr = DesignCounters(
                cache_hit=entry.stats.cache_hit,
                build_time_s=entry.stats.build_time_s,
            )
            reg = _Registered(
                name=name, cached=bucketed, counters=ctr,
                iterations=iterations, diagnostics=diags,
            )
            if self.warmup:
                spec = bucketed.spec
                zeros = {
                    n: np.zeros((self.max_batch,) + tuple(shape), dtype=dt)
                    for n, (dt, shape) in spec.inputs.items()
                }
                t0 = time.perf_counter()
                entry.runner(zeros)
                ctr.warmup_time_s = time.perf_counter() - t0
            self._designs[name] = reg
            return reg

        cached = self.cache.get_or_build(
            source_or_spec, platform=self.platform, iterations=iterations,
            devices=self.devices, tile_rows=self.tile_rows,
            backend=self.backend, strict=self.strict,
        )
        ctr = DesignCounters(
            cache_hit=cached.hit,
            build_time_s=0.0 if cached.hit else cached.build_time_s,
        )
        reg = _Registered(
            name=name, cached=cached, counters=ctr, iterations=iterations,
            diagnostics=diags,
        )
        # Warm even on a design-cache hit: the compiled program is shaped
        # (max_batch, ...) and THIS server's bucket size may be new.  When
        # the shape is already jit-cached the warmup dispatch is ~free.
        if self.warmup:
            runner = cached.runner
            grids = {
                n: [np.zeros(tuple(shape), dtype=dt)]
                for n, (dt, shape) in reg.spec.inputs.items()
            }
            t0 = time.perf_counter()
            if getattr(runner, "stages_grids", False):
                # the path requests take, device stack included
                runner.finalize(runner.dispatch(
                    runner.stage(grids, pad=self.max_batch - 1)
                ))
            else:
                runner(host_batch(grids, self.max_batch - 1))
            ctr.warmup_time_s = time.perf_counter() - t0
        self._designs[name] = reg
        return reg

    def design(self, name: str) -> _Registered:
        return self._designs[name]

    # ------------------------------------------------------------------
    # request path
    # ------------------------------------------------------------------

    def submit(self, request: StencilRequest, claim=None) -> int:
        """Queue one grid; returns a ticket resolved by a later flush().

        Requests are validated here (input names + grid shapes against
        the registered spec, bucketability under bucketing), so a
        malformed request is rejected at submit time instead of poisoning
        a later batch.  Safe to call from multiple threads.

        ``claim`` makes ticket ownership explicit **at submit time**: a
        ticket submitted under a claim token is invisible to plain
        ``flush()`` calls and is only drained by ``flush(claim=token)``.
        This is what lets concurrent ``serve()`` callers share one
        server without one caller's flush stealing (and racing the
        resolution of) another caller's tickets.
        """
        shape = self._validate(request)
        with self._lock:
            ticket = self._next_ticket
            self._next_ticket += 1
            self._queue.append((ticket, request, shape, claim))
        return ticket

    def _validate(self, request: StencilRequest) -> tuple:
        """Validate one request against its registration; returns the
        request's grid shape.  Raises on unknown designs, unknown/missing
        inputs, shape mismatches, and unbucketable shapes — shared by
        :meth:`submit` and the continuous scheduler's admission path."""
        if request.design not in self._designs:
            raise KeyError(
                f"design {request.design!r} is not registered "
                f"(have {sorted(self._designs)})"
            )
        reg = self._designs[request.design]
        spec = reg.spec
        unknown = sorted(set(request.arrays) - set(spec.inputs))
        if unknown:
            raise ValueError(
                f"request for {request.design!r} has unknown input(s) "
                f"{unknown} (spec inputs: {sorted(spec.inputs)})"
            )
        shape = None
        for n, (_, declared) in spec.inputs.items():
            if n not in request.arrays:
                raise ValueError(
                    f"request for {request.design!r} is missing input {n!r}"
                )
            got = tuple(np.shape(request.arrays[n]))
            if reg.bucketed:
                if shape is None:
                    if len(got) != spec.ndim:
                        raise ValueError(
                            f"request for {request.design!r}: {n} must be a "
                            f"{spec.ndim}-D grid, got shape {got}"
                        )
                    shape = got
                elif got != shape:
                    raise ValueError(
                        f"request for {request.design!r}: inconsistent grid "
                        f"shapes ({n} is {got}, expected {shape})"
                    )
            elif got != tuple(declared):
                raise ValueError(
                    f"request for {request.design!r}: {n} must be shaped "
                    f"{tuple(declared)}, got {got}"
                )
            else:
                shape = got
        if reg.bucketed:
            try:
                reg.bucket_for(shape)     # raises if unservable
            except ValueError as e:
                raise ValueError(
                    f"request for {request.design!r} is not bucketable: {e}"
                ) from e
        return shape

    def flush(self, claim=None) -> dict[int, np.ndarray]:
        """Dispatch queued requests, micro-batched per design/bucket.

        ``flush()`` claims exactly the **unclaimed** tickets queued at
        call time; ``flush(claim=token)`` claims exactly the tickets
        submitted under ``token``.  Either way the claimed set is fixed
        under one lock acquisition and nothing outside it is touched —
        tickets another caller claimed at submit time can never be
        drained (or have their resolution raced) by this call.

        The dispatch loop is double-buffered: while the device executes
        one micro-batch, the host stages the next; completed batches are
        only materialised when the bounded in-flight queue is full or the
        queue drains.  A dispatch fault in one micro-batch never drops
        other requests: every chunk is attempted, successful results are
        returned (and retained in ``self.completed`` until claimed), and
        the failed chunk's tickets land in ``self.failures`` (ticket ->
        exception) instead of resolving.
        """
        with self._lock:
            queue = [e for e in self._queue if e[3] == claim]
            self._queue = [e for e in self._queue if e[3] != claim]
        groups: dict[tuple, list] = {}
        for ticket, req, shape, _ in queue:
            reg = self._designs[req.design]
            bucket = reg.bucket_for(shape) if reg.bucketed else None
            groups.setdefault((req.design, bucket), []).append(
                (ticket, req, shape)
            )
        results: dict[int, np.ndarray] = {}
        inflight: collections.deque[_InFlight] = collections.deque()
        for (name, bucket), items in groups.items():
            reg = self._designs[name]
            for lo in range(0, len(items), self.max_batch):
                chunk = items[lo:lo + self.max_batch]
                while len(inflight) >= self.max_inflight:
                    self._resolve(inflight.popleft(), results)
                t0 = time.perf_counter()
                try:
                    runner, batch, post, pad = self._prepare(
                        reg, bucket, chunk
                    )
                    if bucket is None and not _chained(runner):
                        # legacy / monkeypatched runner: plain callable
                        out = np.asarray(runner(host_batch(batch, pad)))
                        self._complete(reg, chunk, pad, t0, post, out,
                                       results)
                    elif self.async_dispatch:
                        out = runner.dispatch(
                            self._stage(reg, runner, batch, pad)
                        )
                        inflight.append(_InFlight(
                            reg=reg, items=chunk, out=out,
                            finalize=runner.finalize, post=post, pad=pad,
                            t0=t0,
                        ))
                    else:
                        out = runner.finalize(runner.dispatch(
                            self._stage(reg, runner, batch, pad)
                        ))
                        self._complete(reg, chunk, pad, t0, post, out,
                                       results)
                except Exception as e:
                    self._fail(reg, chunk, e)
        while inflight:
            self._resolve(inflight.popleft(), results)
        self.completed.update(results)
        self.persist_telemetry()
        return results

    def persist_telemetry(self) -> None:
        """Write serving counters through to the cache's persistent store
        (no-op without one), so a restarted replica resumes its per-key
        and per-bucket statistics instead of zeroing them."""
        if self.cache.store is None:
            return
        for reg in self._designs.values():
            if reg.bucketed:
                reg.cached.persist_stats()
        self.cache.flush_telemetry()

    def serve(self, requests: list[StencilRequest]) -> list[np.ndarray]:
        """submit() + flush(), preserving request order; claims only THIS
        call's tickets from ``self.completed``.

        Each call submits under its own claim token, so concurrent
        serve() calls (and concurrent plain flush() callers) on one
        server never drain each other's tickets.

        Raises if any of this call's requests failed to dispatch — other
        tickets' results (and this call's successful ones) stay claimable
        in ``self.completed``.
        """
        claim = object()
        tickets = [self.submit(r, claim=claim) for r in requests]
        self.flush(claim=claim)
        failed = [t for t in tickets if t in self.failures]
        if failed:
            raise RuntimeError(
                f"{len(failed)}/{len(tickets)} requests failed to dispatch"
            ) from self.failures[failed[0]]
        return [self.completed.pop(t) for t in tickets]

    # ------------------------------------------------------------------
    # dispatch internals
    # ------------------------------------------------------------------

    def _prepare(self, reg: _Registered, bucket, chunk):
        """Host side of one micro-batch; returns (runner, batch, post, pad
        count).  In exact-shape mode ``batch`` maps each input to the
        chunk's own grids, uncopied; under bucketing to one host array
        (grids laid into the bucket, stacked, padded, with the streamed
        service inputs)."""
        with jax.profiler.TraceAnnotation("sasa.prepare"):
            spec = reg.spec
            n = len(chunk)
            pad = self.max_batch - n
            if bucket is None:
                # exact-shape mode: staging pads the batch to max_batch by
                # repeating the first grid (one compiled program per design)
                runner = reg.cached.runner
                grids = {
                    name: [np.asarray(req.arrays[name]) for _, req, _ in chunk]
                    for name in spec.inputs
                }

                def post(out):
                    return {t: out[i] for i, (t, _, _) in enumerate(chunk)}

                return runner, grids, post, pad

            entry = reg.cached.entry_for_bucket(bucket, count=n)
            runner = entry.runner
            plan = runner.plan
            stacked = {}
            for name in spec.inputs:
                grids = [
                    plan.place_entry(np.asarray(req.arrays[name]))
                    for _, req, _ in chunk
                ]
                grids += [plan.filler_entry(name)] * pad
                stacked[name] = np.stack(grids)
            # per-entry streamed service arrays (mask and/or halo-index maps):
            # grids of different shapes share the batch, each re-imposing its
            # own real boundary in-kernel; batch-padding entries carry the
            # plan's throwaway filler (their outputs are discarded by post())
            service = [plan.service_entry(shape) for _, _, shape in chunk]
            filler = plan.service_filler()
            for sname in plan.service_names:
                stacked[sname] = np.stack(
                    [e[sname] for e in service] + [filler[sname]] * pad
                )

            def post(out):
                return {
                    t: out[i][plan.out_index(shape)]
                    for i, (t, _, shape) in enumerate(chunk)
                }

            return runner, stacked, post, pad

    def _stage(self, reg: _Registered, runner, batch, pad: int):
        """``runner.stage`` over one prepared micro-batch, counted in
        ``device_batches`` and ``staged_bytes``.  Grid lists go as they
        are to a runner that stacks on the device; any other runner gets
        :func:`host_batch`."""
        ctr = reg.counters
        lists = any(isinstance(v, list) for v in batch.values())
        if lists and getattr(runner, "stages_grids", False):
            staged = runner.stage(batch, pad=pad)
            ctr.device_batches += 1
        else:
            batch = host_batch(batch, pad)
            staged = runner.stage(batch)
        ctr.staged_bytes += sum(
            sum(g.nbytes for g in v) if isinstance(v, list) else v.nbytes
            for v in batch.values()
        )
        return staged

    def _resolve(self, infl: _InFlight, results: dict) -> None:
        """Block on one in-flight micro-batch and resolve its tickets."""
        try:
            jax.block_until_ready(infl.out)
            out = infl.finalize(infl.out)
            self._complete(infl.reg, infl.items, infl.pad, infl.t0,
                           infl.post, out, results)
        except Exception as e:
            self._fail(infl.reg, infl.items, e)

    def _complete(self, reg: _Registered, chunk, pad: int, t0: float,
                  post, out, results: dict) -> None:
        """Count a read-back batch, then unpad it into ``results``."""
        self._account(reg, chunk, pad, time.perf_counter() - t0)
        with jax.profiler.TraceAnnotation("sasa.resolve"):
            results.update(post(out))

    def _account(self, reg: _Registered, chunk, pad: int, dt: float) -> None:
        ctr = reg.counters
        ctr.requests += len(chunk)
        ctr.batches += 1
        ctr.padded_grids += pad
        ctr.exec_count += 1
        ctr.exec_total_s += dt
        ctr.exec_max_s = max(ctr.exec_max_s, dt)

    def _fail(self, reg: _Registered, chunk, exc: Exception) -> None:
        reg.counters.failed_requests += len(chunk)
        for ticket, _, _ in chunk:
            self.failures[ticket] = exc

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def stats(self) -> dict[str, dict]:
        """Per-design counters plus the shared cache's global hit/miss."""
        out = {}
        for n, r in self._designs.items():
            d = r.counters.as_dict()
            if r.bucketed:
                d["buckets"] = {
                    "x".join(map(str, b)): s
                    for b, s in r.cached.stats().items()
                }
                d["compiled_buckets"] = r.cached.num_buckets
            out[n] = d
        out["_cache"] = {
            "hits": self.cache.hits,
            "misses": self.cache.misses,
            "entries": len(self.cache),
            "runner_evictions": self.cache.runner_evictions,
            "autotune_calls": self.cache.autotune_calls,
            "jit_builds": self.cache.jit_builds,
            "store_hits": self.cache.store_hits,
        }
        if self.cache.store is not None:
            out["_store"] = self.cache.store.stats.as_dict()
        return out
