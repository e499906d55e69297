"""Compiled-design cache: skip re-ranking and re-jitting across calls.

SASA's costly artefact on the FPGA is the synthesized bitstream; the
paper (and SODA before it) amortizes it by reusing one design across many
invocations.  The TPU analogue of the bitstream is the (ranking, jitted
executor) pair: re-running ``autotune`` re-enumerates the design space and
re-traces/re-compiles the shard_map/Pallas program, which at serving rates
dwarfs the stencil itself.  ``DesignCache`` memoizes both levels:

  * the *design* level — ``(structural fingerprint, shape, platform,
    iterations)`` -> ranked predictions + chosen :class:`ParallelismConfig`;
  * the *runner* level — ``(structural fingerprint, shape, config,
    device pool, devices actually used, execution options)`` -> a compiled
    (optionally batched) runner.

Keys split the spec's **structural fingerprint** (everything but the grid
shape) from the shape itself, so shape-bucketed serving — where one
logical kernel owns a ladder of bucket designs (:class:`BucketedDesign`)
— shares cache entries across registrations that differ only in declared
grid size.  The device count a runner actually executes on is part of the
key: a design built degraded on a small pool is never served to a larger
pool (or vice versa) as if it owned its configured parallelism.

Hits and misses are counted per key so serving surfaces can report cache
behaviour (see ``StencilServer.stats``).

With a :class:`repro.runtime.store.DesignStore` attached
(``DesignCache(store=...)``), both levels read through disk on a miss
and write through on a build: rankings are persisted whole, and
single-device batched runners persist their compiled executables per
input signature via :func:`repro.compat.aot_serialize` — so a fresh process
pointed at a warm store serves its first result without autotuning,
tracing, or compiling anything (docs/DESIGN.md §Persistent design
store).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import time
from typing import Mapping, Sequence

import jax

from repro import compat
from repro.core import analysis, dsl
from repro.core.analysis import Diagnostic, require_bucketable
from repro.core.autotune import TunedDesign, autotune
from repro.core.distribute import build_runner
from repro.core.model import InfeasibleDesign, ParallelismConfig
from repro.core.platform import TPUPlatform, platform_for
from repro.core.spec import StencilSpec
from repro.runtime.batching import (
    build_batched_runner,
    build_bucket_runner,
    degraded_message,
    is_degraded,
    resolve_backend,
    validate_batch,
)
from repro.runtime.bucketing import (
    ShapeBucketer,
    bucket_spec,
    padded_request_shape,
)
from repro.runtime.store import (
    DesignStore,
    as_store,
    batch_signature,
    design_key,
    runner_key,
    subtract_counters,
)


def structural_fingerprint(spec: StencilSpec) -> str:
    """Content hash of everything about a spec *except* its grid shape.

    Two specs with equal structural fingerprints describe the same stencil
    on (possibly) different grid sizes and can share bucket designs.  The
    boundary rule is structural: a periodic and a zero-boundary variant of
    the same expression tree are different kernels.
    """
    payload = repr((
        spec.name,
        spec.iterations,
        spec.ndim,
        tuple((k, v[0]) for k, v in spec.inputs.items()),
        spec.stages,
        spec.iterate_input,
        spec.boundary,
        spec.halo_index_inputs,
        spec.wrap_index_inputs,
        spec.wrap_round_depth,
    ))
    return hashlib.sha1(payload.encode()).hexdigest()[:16]


def spec_fingerprint(spec: StencilSpec) -> str:
    """Stable (process-independent) content hash of a full stencil spec."""
    payload = repr((structural_fingerprint(spec), tuple(spec.shape)))
    return hashlib.sha1(payload.encode()).hexdigest()[:16]


def _as_spec(source_or_spec) -> StencilSpec:
    if isinstance(source_or_spec, StencilSpec):
        return source_or_spec
    return dsl.parse(source_or_spec)


def _resolve_platform(platform, devices, clip: bool) -> TPUPlatform:
    """Mirror ``autotune``'s platform handling: an explicit platform is
    clipped to the actual device pool only when an executor will be built
    (``clip``); ranking-only studies keep the hypothetical chip count."""
    if platform is None:
        return platform_for(devices)
    if clip:
        n_avail = len(devices) if devices is not None else len(jax.devices())
        return platform.with_chips(min(platform.num_chips, n_avail))
    return platform


@dataclasses.dataclass
class KeyStats:
    hits: int = 0
    misses: int = 0
    build_time_s: float = 0.0
    store_hits: int = 0     # misses served warm from the persistent store


@dataclasses.dataclass
class CachedDesign:
    """A cache entry: tuned design + compiled batched runner + provenance."""

    design: TunedDesign
    runner: object                 # build_batched_runner result
    fingerprint: str
    key: tuple
    build_time_s: float
    hit: bool                      # whether THIS lookup was served from cache

    @property
    def config(self) -> ParallelismConfig:
        return self.design.config


class DesignCache:
    """In-process memoization of rankings and compiled runners.

    ``max_designs`` caps the number of *compiled runners* the cache
    memoizes (the expensive artefacts — rankings are cheap and uncapped):
    every runner hit marks its entry most-recently-used, and an insert
    past the cap evicts the least-recently-hit runner
    (``runner_evictions`` counts them; per-key hit/miss stats survive, so
    an evict-then-rehit shows up as a rebuild miss on the same key).
    This is the cache-level capacity management that used to be a ROADMAP
    item: bucket-ladder eviction (``max_buckets``) only drops a
    registration's reference, while this bounds the shared memoization
    itself.

    ``store`` (a :class:`repro.runtime.store.DesignStore` or a path)
    makes the cache **persistent**: rankings are read through from /
    written through to disk (a warm process never re-autotunes), and
    single-device batched runners persist their compiled executables per
    input signature through :func:`repro.compat.aot_serialize`, so a warm
    replica's first dispatch deserializes instead of tracing+compiling.
    ``autotune_calls`` counts actual design-space enumerations and
    ``jit_builds`` counts actual AOT trace+compile events — both stay 0
    on a fully warm path (the cold-start gate asserts this).  An
    LRU-evicted runner (``max_designs``) rebuilds from the store:
    re-jitting only happens when the executable entry is gone too.
    """

    def __init__(
        self,
        max_designs: int | None = None,
        store: "DesignStore | str | None" = None,
    ):
        if max_designs is not None and max_designs < 1:
            raise ValueError(
                f"max_designs must be >= 1, got {max_designs}"
            )
        self.max_designs = max_designs
        self.store = as_store(store)
        self.runner_evictions = 0
        self.autotune_calls = 0    # design-space enumerations actually run
        self.jit_builds = 0        # AOT trace+compile events actually run
        self._designs: dict[tuple, TunedDesign] = {}
        self._runners: "collections.OrderedDict[tuple, tuple[object, float]]" = (
            collections.OrderedDict()
        )
        self._failed: dict[tuple, str] = {}    # infeasible-config memo
        self._stats: dict[tuple, KeyStats] = {}
        # restored-telemetry baselines: flush_telemetry persists only the
        # progress made by THIS cache (current - baseline), so restored
        # history is never written back and double-counted by the store's
        # multi-writer merge
        self._tel_baseline: dict[tuple, dict] = {}
        self._tel_buckets: dict[tuple, dict] = {}
        if self.store is not None:
            self._restore_telemetry()

    def _restore_telemetry(self) -> None:
        """Seed per-key counters from the store so a restart resumes the
        telemetry the measurement-calibrated cost model consumes."""
        tel = self.store.get_telemetry()
        if tel is None:
            return
        fields = {f.name for f in dataclasses.fields(KeyStats)}
        for key, d in tel.get("keys", {}).items():
            try:
                self._stats[key] = KeyStats(
                    **{k: v for k, v in d.items() if k in fields}
                )
            except (TypeError, ValueError):
                continue   # stale telemetry shape: skip, don't crash
            self._tel_baseline[key] = dataclasses.asdict(self._stats[key])

    def flush_telemetry(self, buckets: dict | None = None) -> None:
        """Write-through the per-key counters (and optionally per-bucket
        counters) to the attached store; no-op without one.

        What is persisted is this writer's contribution only: per-key
        deltas against the restored baselines, plus every per-bucket dict
        any registration has handed in so far (bucket callers subtract
        their own baselines before calling).  The store merges writers on
        read, so totals across replicas/restarts stay exact.
        """
        if self.store is None:
            return
        if buckets:
            self._tel_buckets.update(buckets)
        keys = {}
        for k, s in self._stats.items():
            d = dataclasses.asdict(s)
            base = self._tel_baseline.get(k)
            keys[k] = subtract_counters(d, base) if base else d
        self.store.put_telemetry(keys, self._tel_buckets)

    # ------------------------------------------------------------------
    # design level (ranking only, no executor build)
    # ------------------------------------------------------------------

    def design(
        self,
        source_or_spec,
        platform: TPUPlatform | None = None,
        iterations: int | None = None,
        devices=None,
        clip_to_devices: bool = False,
    ) -> TunedDesign:
        """Cached ``autotune(..., build=False)``: ranked configs for a spec.

        With a store attached the miss path reads through disk before
        autotuning: a persisted ranking (written by any process sharing
        the store) is rehydrated without enumerating the design space,
        and a fresh autotune result is written through for the next
        replica.
        """
        spec = _as_spec(source_or_spec)
        plat = _resolve_platform(platform, devices, clip_to_devices)
        structural = structural_fingerprint(spec)
        key = (
            "design", structural, tuple(spec.shape),
            plat, iterations,
        )
        st = self._stats.setdefault(key, KeyStats())
        if key in self._designs:
            st.hits += 1
            return self._designs[key]
        skey = None
        if self.store is not None:
            skey = design_key(structural, spec.shape, plat, iterations)
            got = self.store.get_design(skey)
            if got is not None:
                from repro.core import numerics

                stored_spec, ranking = got
                # the store persists spec + ranking only; the certified
                # bound is cheap static analysis, so recompute on warm
                # start rather than widening the store schema
                tuned = TunedDesign(
                    stored_spec, ranking[0], list(ranking), None,
                    diagnostics=(numerics.bound_diagnostic(
                        stored_spec, iterations=iterations,
                    ),),
                )
                st.store_hits += 1
                self._designs[key] = tuned
                # a store hit is already a disk event: persist the counter
                # so fleet telemetry sees warm starts, not just builds
                self.flush_telemetry()
                return tuned
        st.misses += 1
        self.autotune_calls += 1
        t0 = time.perf_counter()
        tuned = autotune(
            spec, platform=plat, iterations=iterations, devices=devices,
            build=False,
        )
        st.build_time_s += time.perf_counter() - t0
        self._designs[key] = tuned
        if skey is not None:
            # persist the lowered spec + full ranking: warm starts skip
            # both the IR lowering and the design-space enumeration
            self.store.put_design(skey, tuned.spec, tuned.ranking)
            self.flush_telemetry()
        return tuned

    # ------------------------------------------------------------------
    # runner level (compiled executor for a specific config)
    # ------------------------------------------------------------------

    def runner(
        self,
        spec: StencilSpec,
        cfg: ParallelismConfig,
        iterations: int | None = None,
        devices=None,
        tile_rows: int = 64,
        backend: str = "auto",
        align_cols: int = 1,
        batched: bool = True,
        strict: bool = False,
    ):
        """Cached runner for ``(spec, cfg, platform, options)``.

        ``batched=True`` compiles the serving runner (leading batch axis);
        ``batched=False`` compiles the classic per-grid runner with the
        ``autotune`` contract.  The key includes the device count the
        runner will actually occupy, so a degraded build (pool smaller
        than the config) is re-examined — not silently reused — when the
        pool changes.  ``strict`` is enforced *before* the lookup (it only
        changes behaviour for degraded configs), so strict and non-strict
        callers share cache entries.
        """
        n_avail = len(devices) if devices is not None else len(jax.devices())
        n_used = min(cfg.devices_needed, n_avail)
        if strict and is_degraded(cfg, n_avail):
            raise InfeasibleDesign(degraded_message(cfg, n_avail))
        dev_key = (
            tuple(str(d) for d in devices) if devices is not None
            else ("default", n_avail, jax.default_backend())
        )
        key = (
            "runner", structural_fingerprint(spec), tuple(spec.shape), cfg,
            dev_key, n_used, iterations, tile_rows, backend, align_cols,
            batched,
        )
        st = self._stats.setdefault(key, KeyStats())
        if key in self._runners:
            st.hits += 1
            self._runners.move_to_end(key)      # most recently hit
            return self._runners[key][0]
        if key in self._failed:
            # known-infeasible: re-raising from the memo is a cache hit,
            # so the feasibility retry loop stays free on repeat calls
            st.hits += 1
            raise InfeasibleDesign(self._failed[key])
        st.misses += 1
        t0 = time.perf_counter()
        try:
            if batched:
                run = build_batched_runner(
                    spec, cfg, iterations=iterations, devices=devices,
                    tile_rows=tile_rows, backend=backend,
                    align_cols=align_cols,
                )
            else:
                run = build_runner(
                    spec, cfg, iterations=iterations, devices=devices,
                    tile_rows=tile_rows,
                )
        except InfeasibleDesign as e:
            self._failed[key] = str(e)
            raise
        if self.store is not None and getattr(run, "jitted", None) is not None:
            skey = runner_key(
                structural_fingerprint(spec), spec.shape, cfg, n_used,
                iterations, tile_rows, resolve_backend(backend),
                align_cols, batched,
            )
            run = self._attach_store(run, skey)
        dt = time.perf_counter() - t0
        st.build_time_s += dt
        self._runners[key] = (run, dt)
        if self.max_designs is not None:
            while len(self._runners) > self.max_designs:
                self._runners.popitem(last=False)   # least recently hit
                self.runner_evictions += 1
        return run

    def _attach_store(self, run, store_key: str):
        """Persistence layer over a batched runner's dispatch phase.

        jit compiles lazily per batch signature, so executables are
        intercepted where they materialize: on each new input signature
        the dispatch path tries the store first (deserializing a
        persisted executable in milliseconds), and only on a store miss
        AOT-compiles explicitly — counting ``jit_builds`` — and writes
        the serialized executable through for the next replica.  All
        phases and reporting attributes of the wrapped runner are
        preserved; results are bitwise-identical either way (the
        executable IS the program that would have been compiled).
        """
        store, spec, jitted = self.store, run.spec, run.jitted
        inner_stage, inner_finalize = run.stage, run.finalize
        executables: dict[str, object] = {}

        def dispatch(staged):
            # replaces the inner dispatch, so it carries the span itself;
            # a store load or compile lands inside it
            with jax.profiler.TraceAnnotation("sasa.dispatch"):
                staged = dict(staged)
                sig = batch_signature(staged)
                comp = executables.get(sig)
                if comp is None:
                    comp = store.get_executable(store_key, sig)
                    if comp is None:
                        comp = compat.aot_compile(jitted, staged)
                        self.jit_builds += 1
                        store.put_executable(
                            store_key, sig, *compat.aot_serialize(comp)
                        )
                    executables[sig] = comp
                return comp(staged)

        def persistent_run(arrays):
            validate_batch(spec, arrays)
            return inner_finalize(dispatch(inner_stage(arrays)))

        for attr in (
            "spec", "cfg", "iterations", "path", "backend", "interpret",
            "mesh", "n_devices", "devices_requested", "degraded", "jitted",
            "stages_grids",
        ):
            setattr(persistent_run, attr, getattr(run, attr))
        persistent_run.stage = inner_stage
        persistent_run.dispatch = dispatch
        persistent_run.finalize = inner_finalize
        persistent_run.ready = getattr(run, "ready", compat.is_ready)
        persistent_run.store_key = store_key
        # input signature -> executable, compiled or loaded from the store
        persistent_run.executables = executables
        return persistent_run

    # ------------------------------------------------------------------
    # combined entry point (what serving calls)
    # ------------------------------------------------------------------

    def get_or_build(
        self,
        source_or_spec,
        platform: TPUPlatform | None = None,
        iterations: int | None = None,
        devices=None,
        tile_rows: int = 64,
        backend: str = "auto",
        align_cols: int = 1,
        batched: bool = True,
        strict: bool = False,
    ) -> CachedDesign:
        """Rank (cached) then compile (cached) the best feasible design.

        ``CachedDesign.hit`` is True iff both levels were served from the
        cache — i.e. the call did no ranking and no re-jitting.
        """
        spec = _as_spec(source_or_spec)
        fp = spec_fingerprint(spec)
        before_miss = self.misses
        before_build_s = self._total_build_s()
        tuned = self.design(
            spec, platform=platform, iterations=iterations, devices=devices,
            clip_to_devices=True,   # an executor is built: rank what fits
        )
        # feasibility retry loop (paper's "build next best design"): the
        # static preflight mirrors the runtime guards, so known-infeasible
        # candidates are skipped without touching the runner level (and
        # recorded as diagnostics); the cached runner level memoizes
        # per-config, so a config that built once keeps winning.  The
        # runner compiles ``tuned.spec`` — the IR-lowered trees the model
        # ranked — not the raw input spec.
        n_pool = len(devices) if devices is not None else len(jax.devices())
        verdicts = analysis.preflight(
            tuned.spec, [p.config for p in tuned.ranking], n_pool,
            iterations=iterations, batched=batched,
            k_override=(
                len(devices)
                if devices is not None and not batched else None
            ),
        )
        diags: list[Diagnostic] = []
        last_err = None
        run = None
        chosen = None
        for pred, verdict in zip(tuned.ranking, verdicts):
            if not verdict.feasible:
                diags.append(verdict.diagnostic("info"))
                last_err = verdict.reason
                continue
            try:
                run = self.runner(
                    tuned.spec, pred.config, iterations=iterations,
                    devices=devices, tile_rows=tile_rows, backend=backend,
                    align_cols=align_cols, batched=batched, strict=strict,
                )
                chosen = pred
                break
            except InfeasibleDesign as e:
                diags.append(Diagnostic(
                    "SASA308", "info",
                    f"candidate {pred.config} refused at build time: {e}",
                ))
                last_err = e
        if run is None:
            raise RuntimeError(f"no feasible configuration: {last_err}")
        # carry the certified bound (SASA500) through from the cached
        # design; preflight skip diags are freshly collected above, so
        # only the numerics finding would otherwise be lost
        carried = tuple(
            d for d in tuned.diagnostics if d.code == "SASA500"
        )
        design = TunedDesign(
            tuned.spec, chosen, tuned.ranking, run, tuned.lowering,
            carried + tuple(diags),
        )
        return CachedDesign(
            design=design, runner=run, fingerprint=fp,
            key=("combined", fp),
            build_time_s=self._total_build_s() - before_build_s,
            hit=(self.misses == before_miss),
        )

    # ------------------------------------------------------------------
    # bucketed registration (multi-geometry serving)
    # ------------------------------------------------------------------

    def bucketed(
        self,
        source_or_spec,
        bucketer: ShapeBucketer | None = None,
        platform: TPUPlatform | None = None,
        iterations: int | None = None,
        devices=None,
        tile_rows: int = 64,
        backend: str = "auto",
        align_cols: int = 1,
        strict: bool = False,
        max_buckets: int | None = None,
    ) -> "BucketedDesign":
        """Register one logical kernel served across many grid shapes.

        The returned :class:`BucketedDesign` lazily owns a ladder of
        bucket designs (one auto-tuned, compiled, masked design per bucket
        shape actually requested), all memoized through this cache — so a
        second registration of a structurally identical kernel, even with
        a different declared grid size, reuses every compiled bucket.

        ``max_buckets`` caps the ladder with an LRU policy: when a new
        bucket would exceed the cap, the least-recently-hit bucket design
        is evicted (its counters survive and resume if the bucket is ever
        re-registered).  Every boundary mode is accepted — zero/constant
        via the streamed mask, replicate via streamed halo-index gathers,
        periodic via host-streamed wrap margins (docs/DESIGN.md
        §Boundaries × bucketed serving); only kernels no streamed bucket
        transform can serve bit-exactly (a divisor interval containing
        zero) are refused here, at registration time (see
        :func:`repro.core.analysis.require_bucketable`).  With
        ``strict`` the full static verification suite runs too and any
        error-severity diagnostic refuses the registration.
        """
        spec = _as_spec(source_or_spec)
        require_bucketable(spec)  # refuse un-bucketable kernels loudly, now
        if strict:
            analysis.verify_or_raise(spec, iterations=iterations)
        return BucketedDesign(
            cache=self,
            spec=spec,
            bucketer=bucketer if bucketer is not None else ShapeBucketer(),
            platform=platform,
            iterations=iterations,
            devices=devices,
            tile_rows=tile_rows,
            backend=backend,
            align_cols=align_cols,
            strict=strict,
            max_buckets=max_buckets,
        )

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def _total_build_s(self) -> float:
        return sum(s.build_time_s for s in self._stats.values())

    @property
    def hits(self) -> int:
        return sum(s.hits for s in self._stats.values())

    @property
    def misses(self) -> int:
        return sum(s.misses for s in self._stats.values())

    def stats(self) -> Mapping[tuple, KeyStats]:
        return dict(self._stats)

    def __len__(self) -> int:
        return len(self._designs) + len(self._runners)

    def clear(self) -> None:
        """Drop the in-memory memoization (the persistent store, if any,
        is untouched: a cleared cache re-warms from disk)."""
        self._designs.clear()
        self._runners.clear()
        self._failed.clear()
        self._stats.clear()
        self._tel_baseline.clear()
        self._tel_buckets.clear()
        self.runner_evictions = 0
        self.autotune_calls = 0
        self.jit_builds = 0

    @property
    def store_hits(self) -> int:
        return sum(s.store_hits for s in self._stats.values())


# --------------------------------------------------------------------------
# Bucketed registration: one logical kernel, a ladder of bucket designs
# --------------------------------------------------------------------------


@dataclasses.dataclass
class BucketStats:
    """Per-bucket serving counters of one logical registration."""

    hits: int = 0              # runner_for calls served by an existing bucket
    misses: int = 0            # runner_for calls that had to build the bucket
    requests: int = 0          # grids routed to this bucket
    build_time_s: float = 0.0  # rank + jit time paid by this registration
    cache_hit: bool = False    # the bucket's design came fully from the cache

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class BucketEntry:
    """One rung of a registration's bucket ladder."""

    bucket: tuple[int, ...]
    runner: object             # build_bucket_runner result (pad+mask wrapper)
    cached: CachedDesign       # the underlying masked bucket design
    stats: BucketStats

    @property
    def config(self) -> ParallelismConfig:
        return self.cached.design.config


class BucketedDesign:
    """One logical kernel registration owning a ladder of bucket designs.

    ``runner_for(shape)`` maps a grid shape (plus its streamed-halo
    margins) to its bucket via the :class:`ShapeBucketer` policy,
    auto-tunes and compiles that bucket's streamed-boundary design on
    first use (both levels memoized in the shared :class:`DesignCache`),
    and returns the :class:`BucketEntry` whose staging runner serves the
    shape.  Per-bucket hit counters live in ``BucketEntry.stats`` /
    :meth:`stats`.

    ``max_buckets`` bounds the ladder of a long-lived registration (the
    ROADMAP's bucket-eviction item): every ``runner_for`` marks its bucket
    most-recently-used, and building a bucket past the cap evicts the
    least-recently-hit entry.  An evicted bucket's counters are archived
    and resume when the bucket is rebuilt, so serving statistics survive
    eviction/re-registration cycles.  Eviction drops this registration's
    reference to the compiled design; while the shared
    :class:`DesignCache` still memoizes it a rebuild is a dictionary
    lookup, but under ``DesignCache(max_designs=)`` the runner itself
    may have been LRU-evicted, in which case the rebuild re-jits from
    the still-cached ranking.
    """

    def __init__(
        self, cache: DesignCache, spec: StencilSpec,
        bucketer: ShapeBucketer, platform=None, iterations=None,
        devices=None, tile_rows: int = 64, backend: str = "auto",
        align_cols: int = 1, strict: bool = False,
        max_buckets: int | None = None,
    ):
        if max_buckets is not None and max_buckets < 1:
            raise ValueError(f"max_buckets must be >= 1, got {max_buckets}")
        self.cache = cache
        self.spec = spec
        self.bucketer = bucketer
        self.platform = platform
        self.iterations = iterations
        self.devices = devices
        self.tile_rows = tile_rows
        self.backend = backend
        self.align_cols = align_cols
        self.strict = strict
        self.max_buckets = max_buckets
        self.structural = structural_fingerprint(spec)
        # insertion/access order = LRU order (oldest first)
        self._entries: "collections.OrderedDict[tuple[int, ...], BucketEntry]" = (
            collections.OrderedDict()
        )
        self._evicted_stats: dict[tuple[int, ...], BucketStats] = {}
        # restored per-bucket baselines: persist_stats() writes deltas
        # against these, so restored history isn't double-counted by the
        # store's multi-writer telemetry merge
        self._tel_baseline: dict[tuple[int, ...], dict] = {}
        self.evictions: int = 0
        self._wrap_rounds = ...   # undecided until first routing
        if cache.store is not None:
            # restart continuity: persisted per-bucket counters land in
            # the archived-stats map, so the first (re)build of each
            # bucket resumes them through the existing eviction-resume
            # path instead of zeroing the ladder's history
            tel = cache.store.get_telemetry()
            fields = {f.name for f in dataclasses.fields(BucketStats)}
            for bkey, d in (tel or {}).get("buckets", {}).items():
                try:
                    structural, bucket = bkey
                except (TypeError, ValueError):
                    continue
                if structural != self.structural:
                    continue
                try:
                    self._evicted_stats[tuple(bucket)] = BucketStats(
                        **{k: v for k, v in d.items() if k in fields}
                    )
                except (TypeError, ValueError):
                    continue
                self._tel_baseline[tuple(bucket)] = dataclasses.asdict(
                    self._evicted_stats[tuple(bucket)]
                )

    @property
    def wrap_rounds(self) -> int | None:
        """The narrow-margin wrap depth this registration serves with.

        Decided once at first routing and pinned for the registration's
        lifetime (margins are baked into bucket routing, so it cannot
        change per request): ``None`` — the legacy wide
        ``iterations * radius`` margin — unless the boundary is periodic
        *and* the device pool is a single device (the between-round
        re-wrap needs the whole grid resident; shard_map keeps the wide
        margin until the collective re-wrap lands — see the TODO in
        :mod:`repro.core.distribute`).  Otherwise the design-level
        ranking for the declared shape picks the fusion depth ``s`` the
        bucket designs will run, and the margin shrinks to
        ``s * radius``.
        """
        if self._wrap_rounds is ...:
            self._wrap_rounds = self._decide_wrap_rounds()
        return self._wrap_rounds

    def _decide_wrap_rounds(self) -> int | None:
        if self.spec.boundary.kind != "periodic":
            return None
        n_avail = (
            len(self.devices) if self.devices is not None
            else len(jax.devices())
        )
        if n_avail > 1:
            return None
        it = (
            self.spec.iterations if self.iterations is None
            else self.iterations
        )
        tuned = self.cache.design(
            self.spec, platform=self.platform, iterations=self.iterations,
            devices=self.devices, clip_to_devices=True,
        )
        return max(min(tuned.ranking[0].config.s, it), 1)

    def bucket_for(self, shape: Sequence[int]) -> tuple[int, ...]:
        """The bucket serving a *request* grid of ``shape``.

        Routing fits the grid plus its per-dimension halo margins
        (non-zero only for periodic specs, whose wrapped exterior is
        streamed into the margin as data; sized by this registration's
        :attr:`wrap_rounds` — see
        :func:`repro.runtime.bucketing.bucket_margins`).
        """
        return self.bucketer.bucket_for(
            padded_request_shape(
                self.spec, shape, self.iterations, self.wrap_rounds
            )
        )

    def runner_for(self, shape: Sequence[int], count: int = 1) -> BucketEntry:
        """The bucket entry serving request grids of ``shape`` (built and
        memoized on first use); ``count`` grids are attributed to the
        bucket's counters."""
        return self.entry_for_bucket(self.bucket_for(shape), count=count)

    def entry_for_bucket(
        self, bucket: tuple[int, ...], count: int = 1
    ) -> BucketEntry:
        """The entry for an already-routed bucket shape (what the server's
        flush loop calls after grouping requests per bucket; routing a
        bucket shape through :meth:`bucket_for` again would re-add halo
        margins)."""
        bucket = tuple(int(b) for b in bucket)
        entry = self._entries.get(bucket)
        if entry is not None:
            entry.stats.hits += 1
            entry.stats.requests += count
            self._entries.move_to_end(bucket)      # most recently hit
            return entry
        bspec = bucket_spec(self.spec, bucket, self.wrap_rounds)
        t0 = time.perf_counter()
        cached = self.cache.get_or_build(
            bspec, platform=self.platform, iterations=self.iterations,
            devices=self.devices, tile_rows=self.tile_rows,
            backend=self.backend, align_cols=self.align_cols,
            strict=self.strict,
        )
        wrapped = build_bucket_runner(
            self.spec, bucket, cached.design.config,
            iterations=self.iterations, inner=cached.runner,
            wrap_rounds=self.wrap_rounds,
        )
        # a previously evicted bucket resumes its archived counters
        stats = self._evicted_stats.pop(bucket, None) or BucketStats()
        stats.misses += 1
        stats.requests += count
        stats.build_time_s += 0.0 if cached.hit else time.perf_counter() - t0
        stats.cache_hit = cached.hit
        entry = BucketEntry(
            bucket=bucket, runner=wrapped, cached=cached, stats=stats
        )
        self._entries[bucket] = entry
        if self.max_buckets is not None:
            while len(self._entries) > self.max_buckets:
                old_bucket, old = self._entries.popitem(last=False)
                self._evicted_stats[old_bucket] = old.stats
                self.evictions += 1
        self.persist_stats()
        return entry

    def persist_stats(self) -> None:
        """Write-through this registration's per-bucket counters to the
        cache's persistent store (no-op without one); restarts restore
        them through the archived-stats map.  Counters restored from the
        store are subtracted back out before writing, so only this
        registration's own progress lands in its writer's telemetry file
        (the store merges writers on read)."""
        if self.cache.store is None:
            return
        live = {b: e.stats.as_dict() for b, e in self._entries.items()}
        live.update({b: s.as_dict() for b, s in self._evicted_stats.items()})
        buckets = {}
        for b, d in live.items():
            base = self._tel_baseline.get(b)
            buckets[(self.structural, b)] = (
                subtract_counters(d, base) if base else d
            )
        self.cache.flush_telemetry(buckets)

    def run(self, shape, arrays) -> "np.ndarray":
        """Convenience: serve one uniform-shape batch through its bucket."""
        return self.runner_for(shape).runner(arrays)

    @property
    def buckets(self) -> dict[tuple[int, ...], BucketEntry]:
        return dict(self._entries)

    @property
    def num_buckets(self) -> int:
        return len(self._entries)

    def stats(self) -> dict[tuple[int, ...], dict]:
        """Per-bucket counters, evicted rungs included (marked evicted)."""
        out = {b: e.stats.as_dict() for b, e in self._entries.items()}
        for b, s in self._evicted_stats.items():
            d = s.as_dict()
            d["evicted"] = True
            out[b] = d
        return out


_DEFAULT_CACHE = DesignCache()


def default_cache() -> DesignCache:
    """The process-wide cache used when callers don't bring their own."""
    return _DEFAULT_CACHE
