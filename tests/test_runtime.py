"""Serving runtime: design cache semantics + batched execution correctness.

Single-device paths run in-process; the batched shard_map path is covered
by the 8-device subprocess checks in ``_multidevice_main.py``.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.configs import stencils
from repro.core import autotune, soda_baseline
from repro.core.model import InfeasibleDesign, ParallelismConfig
from repro.kernels import ref
from repro.runtime import (
    DegradedDesignWarning,
    DesignCache,
    build_batched_runner,
    devices_needed,
    spec_fingerprint,
)

RNG = np.random.default_rng(3)


def batch_for(spec, B):
    return {
        n: RNG.standard_normal((B,) + tuple(shape)).astype(dt)
        for n, (dt, shape) in spec.inputs.items()
    }


def per_grid_oracle(spec, arrays_b, iters, b):
    one = {n: jnp.asarray(a[b]) for n, a in arrays_b.items()}
    return np.asarray(ref.stencil_iterations_ref(spec, one, iters))


# ---------------------------------------------------------------------------
# batched execution
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,shape", [
    ("jacobi2d", (24, 17)), ("hotspot", (24, 17)), ("heat3d", (16, 6, 6)),
])
@pytest.mark.parametrize("s", [1, 2])
def test_batched_single_pe_matches_oracle(name, shape, s):
    iters = 4
    spec = stencils.get(name, shape=shape, iterations=iters)
    cfg = ParallelismConfig("temporal", k=1, s=s)
    run = build_batched_runner(spec, cfg, tile_rows=8)
    arrays = batch_for(spec, B=3)
    out = run(arrays)
    assert out.shape == (3,) + tuple(shape)
    for b in range(3):
        np.testing.assert_allclose(
            out[b], per_grid_oracle(spec, arrays, iters, b),
            rtol=2e-4, atol=2e-4,
        )


def test_batched_pallas_backend_matches_oracle():
    iters = 3
    spec = stencils.jacobi2d(shape=(24, 17), iterations=iters)
    cfg = ParallelismConfig("temporal", k=1, s=3)
    run = build_batched_runner(
        spec, cfg, tile_rows=8, backend="pallas", interpret=True
    )
    arrays = batch_for(spec, B=2)
    out = run(arrays)
    for b in range(2):
        np.testing.assert_allclose(
            out[b], per_grid_oracle(spec, arrays, iters, b),
            rtol=2e-4, atol=2e-4,
        )


def test_batched_runner_rejects_bad_shapes():
    spec = stencils.jacobi2d(shape=(16, 8), iterations=2)
    run = build_batched_runner(spec, ParallelismConfig("temporal", k=1, s=2))
    with pytest.raises(ValueError, match="batched runner expects"):
        run({"in_1": np.zeros((16, 8), np.float32)})      # missing batch axis
    with pytest.raises(ValueError, match="batched runner expects"):
        run({"in_1": np.zeros((2, 8, 16), np.float32)})   # transposed grid


def test_batch_entries_are_independent():
    """Zero grids stay zero next to non-zero neighbours in the batch."""
    spec = stencils.jacobi2d(shape=(16, 8), iterations=3)
    run = build_batched_runner(spec, ParallelismConfig("temporal", k=1, s=3))
    arrays = batch_for(spec, B=3)
    arrays["in_1"][1] = 0.0
    out = run(arrays)
    np.testing.assert_array_equal(out[1], np.zeros((16, 8), np.float32))
    assert np.abs(out[0]).max() > 0


def test_devices_needed():
    assert devices_needed(ParallelismConfig("temporal", k=1, s=4)) == 4
    assert devices_needed(ParallelismConfig("spatial_s", k=8, s=1)) == 8
    assert devices_needed(ParallelismConfig("hybrid_s", k=2, s=3)) == 2


# ---------------------------------------------------------------------------
# degraded designs (device pool smaller than the config claims)
# ---------------------------------------------------------------------------


def test_degraded_design_warns_and_is_flagged():
    """hybrid_r(k=8) on a 1-device host must not *silently* degrade."""
    spec = stencils.jacobi2d(shape=(64, 8), iterations=2)
    cfg = ParallelismConfig("hybrid_r", k=8, s=2)
    with pytest.warns(DegradedDesignWarning, match="needs 8 device"):
        run = build_batched_runner(spec, cfg, tile_rows=8)
    assert run.degraded
    assert run.cfg.k == 8                 # the config still claims k=8 ...
    assert run.n_devices == 1             # ... but execution is single-PE
    assert run.devices_requested == 8
    arrays = batch_for(spec, B=2)
    out = run(arrays)                     # degraded, but still correct
    np.testing.assert_allclose(
        out[0], per_grid_oracle(spec, arrays, 2, 0), rtol=2e-4, atol=2e-4,
    )


def test_degraded_design_raises_under_strict():
    spec = stencils.jacobi2d(shape=(64, 8), iterations=2)
    cfg = ParallelismConfig("spatial_s", k=4, s=1)
    with pytest.raises(ValueError, match="needs 4 device"):
        build_batched_runner(spec, cfg, strict=True)


def test_strict_and_lax_callers_share_cache_entries():
    """strict only matters for degraded configs: on a feasible config a
    strict lookup must hit the entry a non-strict caller built."""
    cache = DesignCache()
    spec = stencils.jacobi2d(shape=(16, 8), iterations=2)
    cfg = ParallelismConfig("temporal", k=1, s=2)
    first = cache.runner(spec, cfg, tile_rows=8)
    misses = cache.misses
    again = cache.runner(spec, cfg, tile_rows=8, strict=True)
    assert again is first and cache.misses == misses
    # ... while a degraded config still refuses under strict, pre-cache
    bad = ParallelismConfig("hybrid_s", k=2, s=2)
    with pytest.raises(ValueError, match="needs 2 device"):
        cache.runner(spec, bad, tile_rows=8, strict=True)


def test_temporal_on_one_device_is_not_degraded():
    """The sanctioned degenerate case: a temporal cascade on one chip runs
    as fused rounds with the fusion depth (and the model's single-chip
    prediction) preserved — no warning, no degraded flag."""
    import warnings as _warnings

    spec = stencils.jacobi2d(shape=(16, 8), iterations=4)
    with _warnings.catch_warnings():
        _warnings.simplefilter("error", DegradedDesignWarning)
        run = build_batched_runner(
            spec, ParallelismConfig("temporal", k=1, s=4), tile_rows=8
        )
    assert not run.degraded


def test_batched_runner_rejects_unknown_inputs():
    """A typo'd array name must fail loudly, not serve garbage-by-omission."""
    spec = stencils.jacobi2d(shape=(16, 8), iterations=2)
    run = build_batched_runner(spec, ParallelismConfig("temporal", k=1, s=2))
    good = np.zeros((2, 16, 8), np.float32)
    with pytest.raises(ValueError, match="unknown input"):
        run({"in_1": good, "in_2": good})


def test_pool_change_rebuilds_degraded_runner(monkeypatch):
    """A runner cached while degraded (pool < config) must not be reused
    when the device pool grows: the actual device count is in the key."""
    import repro.runtime.cache as cache_mod

    cache = DesignCache()
    spec = stencils.jacobi2d(shape=(64, 8), iterations=2)
    cfg = ParallelismConfig("hybrid_s", k=2, s=2)
    with pytest.warns(DegradedDesignWarning):
        first = cache.runner(spec, cfg, tile_rows=8)   # degraded: 1 device
    assert first.degraded

    built = []

    def fake_build(spec_, cfg_, **kw):
        built.append(kw)
        return object()      # stand-in runner; never executed

    monkeypatch.setattr(cache_mod, "build_batched_runner", fake_build)
    # same pool: pure hit, no rebuild even through the fake builder
    again = cache.runner(spec, cfg, tile_rows=8)
    assert again is first and not built
    # pool grows to 2 devices: the degraded entry must NOT be served
    monkeypatch.setattr(
        cache_mod.jax, "devices", lambda: [object(), object()]
    )
    rebuilt = cache.runner(spec, cfg, tile_rows=8)
    assert len(built) == 1
    assert rebuilt is not first


# ---------------------------------------------------------------------------
# soda_baseline fallback behaviour
# ---------------------------------------------------------------------------


def test_soda_baseline_empty_candidates_raises(monkeypatch):
    import sys

    at = sys.modules["repro.core.autotune"]
    monkeypatch.setattr(at.model, "choose_best", lambda *a, **k: [])
    spec = stencils.jacobi2d(shape=(16, 8), iterations=2)
    with pytest.raises(RuntimeError, match="no temporal candidate"):
        soda_baseline(spec)


def test_soda_baseline_retries_infeasible_configs(monkeypatch):
    """An infeasible top temporal config must fall back to the next
    candidate, mirroring autotune()'s retry loop."""
    import sys

    at = sys.modules["repro.core.autotune"]
    spec = stencils.jacobi2d(shape=(20, 10), iterations=4)
    real = at.build_runner
    calls = {"n": 0}

    def flaky(spec_, cfg, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise InfeasibleDesign("synthetic infeasible temporal config")
        return real(spec_, cfg, **kw)

    monkeypatch.setattr(at, "build_runner", flaky)
    design = soda_baseline(spec, tile_rows=8)
    assert calls["n"] == 2                 # first failed, second built
    assert design.config.variant == "temporal"
    assert design.config == design.ranking[1].config
    x = RNG.standard_normal((20, 10)).astype(np.float32)
    want = np.asarray(
        ref.stencil_iterations_ref(spec, {"in_1": jnp.asarray(x)}, 4)
    )
    np.testing.assert_allclose(
        design.runner({"in_1": x}), want, rtol=2e-4, atol=2e-4
    )


def test_soda_baseline_all_infeasible_raises(monkeypatch):
    import sys

    at = sys.modules["repro.core.autotune"]

    def broken(*a, **k):
        raise InfeasibleDesign("synthetic: nothing fits")

    monkeypatch.setattr(at, "build_runner", broken)
    spec = stencils.jacobi2d(shape=(16, 8), iterations=2)
    with pytest.raises(RuntimeError, match="no feasible temporal"):
        soda_baseline(spec)


def test_soda_baseline_build_false_skips_executor():
    spec = stencils.jacobi2d(shape=(16, 8), iterations=2)
    design = soda_baseline(spec, build=False)
    assert design.runner is None
    assert design.config.variant == "temporal"


# ---------------------------------------------------------------------------
# design cache
# ---------------------------------------------------------------------------


def test_spec_fingerprint_stable_and_discriminating():
    a = stencils.jacobi2d(shape=(16, 8), iterations=2)
    b = stencils.jacobi2d(shape=(16, 8), iterations=2)
    c = stencils.jacobi2d(shape=(16, 9), iterations=2)
    assert spec_fingerprint(a) == spec_fingerprint(b)
    assert spec_fingerprint(a) != spec_fingerprint(c)


def test_cache_hit_skips_rebuild():
    cache = DesignCache()
    spec = stencils.jacobi2d(shape=(16, 8), iterations=2)
    c1 = cache.get_or_build(spec)
    misses_after_first = cache.misses
    c2 = cache.get_or_build(spec)
    assert not c1.hit and c2.hit
    assert c2.runner is c1.runner
    assert cache.misses == misses_after_first  # nothing rebuilt
    assert cache.hits > 0


def test_cache_distinguishes_specs_and_options():
    cache = DesignCache()
    a = stencils.jacobi2d(shape=(16, 8), iterations=2)
    b = stencils.jacobi2d(shape=(24, 8), iterations=2)
    ra = cache.get_or_build(a).runner
    rb = cache.get_or_build(b).runner
    assert ra is not rb
    ra2 = cache.get_or_build(a, tile_rows=16).runner
    assert ra2 is not ra  # different execution options -> different runner


def test_infeasible_configs_are_memoized(monkeypatch):
    """An InfeasibleDesign-raising config must not cost a rebuild attempt (or a
    cache miss) on repeat calls — hit stays True for identical lookups."""
    import repro.runtime.cache as cache_mod

    cache = DesignCache()
    spec = stencils.jacobi2d(shape=(16, 8), iterations=2)
    real = cache_mod.build_batched_runner
    calls = {"n": 0}

    def flaky_build(spec_, cfg, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise InfeasibleDesign("synthetic infeasible top config")
        return real(spec_, cfg, **kw)

    monkeypatch.setattr(cache_mod, "build_batched_runner", flaky_build)
    c1 = cache.get_or_build(spec)            # top config "fails", next builds
    assert not c1.hit
    builds_after_first = calls["n"]
    c2 = cache.get_or_build(spec)            # both levels + the failure memo
    assert c2.hit
    assert calls["n"] == builds_after_first  # no re-attempt of the failure


@pytest.mark.parametrize("entry", ["cache", "autotune"])
def test_build_errors_other_than_infeasibility_propagate(monkeypatch, entry):
    """A plain ValueError from a build (what a Pallas lowering refusal
    raises) is a fault, not a skipped candidate: it surfaces as itself
    instead of being folded into "no feasible configuration"."""
    import sys

    import repro.runtime.cache as cache_mod

    at = sys.modules["repro.core.autotune"]

    def refused(*a, **k):
        raise ValueError("synthetic lowering refusal")

    spec = stencils.jacobi2d(shape=(16, 8), iterations=2)
    if entry == "cache":
        monkeypatch.setattr(cache_mod, "build_batched_runner", refused)
        call = lambda: DesignCache().get_or_build(spec)       # noqa: E731
    else:
        monkeypatch.setattr(at, "build_runner", refused)
        call = lambda: autotune(spec, tile_rows=8)            # noqa: E731
    with pytest.raises(ValueError, match="synthetic lowering refusal"):
        call()


def test_cached_design_runs_correctly():
    cache = DesignCache()
    iters = 3
    spec = stencils.jacobi2d(shape=(20, 10), iterations=iters)
    cached = cache.get_or_build(spec)
    arrays = batch_for(spec, B=2)
    out = cached.runner(arrays)
    for b in range(2):
        np.testing.assert_allclose(
            out[b], per_grid_oracle(spec, arrays, iters, b),
            rtol=2e-4, atol=2e-4,
        )


def test_autotune_cache_kwarg_reuses_runner():
    cache = DesignCache()
    spec = stencils.jacobi2d(shape=(20, 10), iterations=2)
    d1 = autotune(spec, cache=cache)
    d2 = autotune(spec, cache=cache)
    assert d2.runner is d1.runner
    assert d2.config == d1.config
    # the cached runner still honours the unbatched autotune contract
    x = RNG.standard_normal((20, 10)).astype(np.float32)
    want = np.asarray(ref.stencil_iterations_ref(spec, {"in_1": jnp.asarray(x)}, 2))
    np.testing.assert_allclose(d1.runner({"in_1": x}), want, rtol=2e-4, atol=2e-4)


def test_autotune_cache_build_false_caches_ranking():
    cache = DesignCache()
    spec = stencils.jacobi2d(shape=(20, 10), iterations=2)
    d1 = autotune(spec, cache=cache, build=False)
    assert d1.runner is None
    before = cache.misses
    d2 = autotune(spec, cache=cache, build=False)
    assert cache.misses == before
    assert d2.config == d1.config


# ---------------------------------------------------------------------------
# cache-level capacity management (max_designs LRU over compiled runners)
# ---------------------------------------------------------------------------


def test_max_designs_validation():
    with pytest.raises(ValueError, match="max_designs"):
        DesignCache(max_designs=0)


def test_max_designs_lru_evicts_and_rebuilds_on_rehit():
    """The shared cache itself is now capacity-managed: past the cap the
    least-recently-hit compiled runner is dropped, an evict-then-rehit is
    a rebuild miss on the same key, and counters record the churn."""
    cache = DesignCache(max_designs=1)
    a = stencils.jacobi2d(shape=(16, 8), iterations=2)
    b = stencils.jacobi2d(shape=(24, 8), iterations=2)
    ca = cache.get_or_build(a)
    assert cache.runner_evictions == 0
    cb = cache.get_or_build(b)            # evicts a's runner
    assert cache.runner_evictions == 1
    assert not ca.hit and not cb.hit
    # rankings stay cached, so the rehit re-jits but does not re-rank
    misses_before = cache.misses
    ca2 = cache.get_or_build(a)
    assert cache.runner_evictions == 2    # b evicted in turn
    assert not ca2.hit                    # the combined call was not free
    assert cache.misses == misses_before + 1   # exactly the runner rebuild
    # the rebuilt runner still serves traffic correctly
    arrays = batch_for(a, B=2)
    out = ca2.runner(arrays)
    for i in range(2):
        np.testing.assert_allclose(
            out[i], per_grid_oracle(a, arrays, 2, i), rtol=2e-4, atol=2e-4,
        )


def test_max_designs_lru_order_follows_hits():
    cache = DesignCache(max_designs=2)
    a = stencils.jacobi2d(shape=(16, 8), iterations=2)
    b = stencils.jacobi2d(shape=(24, 8), iterations=2)
    c = stencils.jacobi2d(shape=(32, 8), iterations=2)
    cache.get_or_build(a)
    cache.get_or_build(b)
    cache.get_or_build(a)                 # refresh a: now MRU
    cache.get_or_build(c)                 # evicts b, not a
    assert cache.runner_evictions == 1
    misses = cache.misses
    assert cache.get_or_build(a).hit      # still resident
    assert cache.misses == misses
    assert not cache.get_or_build(b).hit  # was evicted: rebuild


def test_max_designs_composes_with_bucketed_registrations():
    """Bucket-ladder eviction drops the registration's reference; the
    cache cap bounds the shared memoization underneath.  A bucketed rehit
    after cache eviction rebuilds instead of silently growing."""
    cache = DesignCache(max_designs=1)
    spec = stencils.jacobi2d(shape=(20, 13), iterations=2)
    bd = cache.bucketed(spec, tile_rows=8)
    bd.runner_for((20, 13))               # bucket (32, 16)
    bd2 = cache.bucketed(spec, tile_rows=8)
    bd2.runner_for((40, 40))              # bucket (64, 64): evicts the first
    assert cache.runner_evictions >= 1
    # the first registration still holds its compiled reference and serves
    arrays = {"in_1": RNG.standard_normal((1, 20, 13)).astype(np.float32)}
    out = bd.runner_for((20, 13)).runner(arrays)
    np.testing.assert_allclose(
        out[0],
        np.asarray(ref.stencil_iterations_ref(
            stencils.jacobi2d(shape=(20, 13), iterations=2),
            {"in_1": jnp.asarray(arrays["in_1"][0])}, 2,
        )),
        rtol=2e-4, atol=2e-4,
    )


def test_clear_resets_eviction_counter():
    cache = DesignCache(max_designs=1)
    cache.get_or_build(stencils.jacobi2d(shape=(16, 8), iterations=2))
    cache.get_or_build(stencils.jacobi2d(shape=(24, 8), iterations=2))
    assert cache.runner_evictions == 1
    cache.clear()
    assert cache.runner_evictions == 0 and len(cache) == 0
