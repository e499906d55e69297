"""Exact-shape micro-batches are formed on the device.

The server hands the runner each request's own grid; the runner sends
every grid to the device once, fills the padding slots with the first
grid's device buffer and stacks the batch there.  Results must be
bitwise those of the runner over the host ``np.stack`` the server used
to build, no padding byte may cross to the device, and runners that
cannot stack on the device (plain callables, runners without
``stages_grids``) still get one host array per input.
"""
import numpy as np
import pytest

from repro.configs import stencils
from repro.runtime import DesignCache
from repro.serve import StencilRequest, StencilScheduler, StencilServer
from repro.serve.engine import host_batch

MAX_BATCH = 4
RNG = np.random.default_rng(41)


def spec_for(name):
    return stencils.get(name, shape=(16, 8), iterations=2)


def requests(spec, n):
    return [
        StencilRequest("st", {
            name: RNG.standard_normal(shape).astype(dt)
            for name, (dt, shape) in spec.inputs.items()
        })
        for _ in range(n)
    ]


def serve_flush(srv, reqs):
    return srv.serve(reqs)


def serve_scheduled(srv, reqs):
    with StencilScheduler(srv, start=False) as sched:
        tickets = [sched.submit(r) for r in reqs]
        sched.drain()
        return [t.result(timeout=60) for t in tickets]


def expected(runner, spec, reqs):
    """The runner over the host batch the server used to stack: the
    chunk's grids, the first repeated as padding."""
    out = []
    for lo in range(0, len(reqs), MAX_BATCH):
        chunk = reqs[lo:lo + MAX_BATCH]
        grids = {n: [r.arrays[n] for r in chunk] for n in spec.inputs}
        batch = host_batch(grids, MAX_BATCH - len(chunk))
        out += list(np.asarray(runner(batch))[:len(chunk)])
    return out


SERVE = pytest.mark.parametrize(
    "serve", [serve_flush, serve_scheduled], ids=["flush", "scheduler"])


@SERVE
@pytest.mark.parametrize("n", [MAX_BATCH, MAX_BATCH - 1, MAX_BATCH + 1],
                         ids=["full", "short", "full+short"])
@pytest.mark.parametrize("stencil", ["jacobi2d", "hotspot"])
def test_exact_serving_is_bitwise_the_host_stack(serve, n, stencil):
    spec = spec_for(stencil)
    srv = StencilServer(max_batch=MAX_BATCH, cache=DesignCache())
    reg = srv.register("st", spec)
    reqs = requests(spec, n)
    outs = serve(srv, reqs)
    want = expected(reg.cached.runner, spec, reqs)
    for got, ref in zip(outs, want, strict=True):
        assert got.dtype == ref.dtype and np.array_equal(got, ref)
    st = srv.stats()["st"]
    assert st["batches"] == -(-n // MAX_BATCH)
    assert st["device_batches"] == st["batches"]
    # the real grids only: no padding crossed to the device
    grid_bytes = sum(a.nbytes for a in reqs[0].arrays.values())
    assert st["staged_bytes"] == n * grid_bytes
    assert st["padded_grids"] == st["batches"] * MAX_BATCH - n


def test_runner_stage_of_grids_equals_stage_of_host_batch(monkeypatch):
    """A grid list staged with padding gives the operand a host batch
    gives: same shape, dtype and values, so the same program runs; and
    only the real grids are handed to ``jax.device_put``."""
    import jax

    from repro.runtime.store import batch_signature

    spec = spec_for("jacobi2d")
    runner = DesignCache().get_or_build(spec).runner
    assert runner.stages_grids
    grids = {n: [r.arrays[n] for r in requests(spec, 3)]
             for n in spec.inputs}
    put, sent = jax.device_put, []

    def spy(x, *args, **kwargs):
        sent.extend(a.nbytes for a in jax.tree.leaves(x)
                    if isinstance(a, np.ndarray))
        return put(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", spy)
    on_device = runner.stage(grids, pad=MAX_BATCH - 3)
    assert sum(sent) == sum(g.nbytes for v in grids.values() for g in v)
    from_host = runner.stage(host_batch(grids, MAX_BATCH - 3))
    assert batch_signature(on_device) == batch_signature(from_host)
    for n in spec.inputs:
        assert np.array_equal(np.asarray(on_device[n]),
                              np.asarray(from_host[n]))


class _Recorder:
    """What a runner without device staging was handed."""

    def __init__(self, runner, chained):
        self.runner, self.batches = runner, []
        if chained:
            self.stage, self.dispatch = self._stage, runner.dispatch
            self.finalize = runner.finalize

    def _seen(self, arrays):
        self.batches.append(arrays)
        return arrays

    def __call__(self, arrays):
        return self.runner(self._seen(arrays))

    def _stage(self, arrays):
        return self.runner.stage(self._seen(arrays))


@SERVE
@pytest.mark.parametrize("chained", [False, True],
                         ids=["plain-callable", "stage-without-grids"])
def test_runner_without_device_staging_gets_a_host_batch(serve, chained):
    spec = spec_for("jacobi2d")
    srv = StencilServer(max_batch=MAX_BATCH, cache=DesignCache())
    reg = srv.register("st", spec)
    real = reg.cached.runner
    reg.cached.runner = rec = _Recorder(real, chained)
    reqs = requests(spec, MAX_BATCH - 1)
    outs = serve(srv, reqs)
    (batch,) = rec.batches
    (x,) = batch.values()
    first = reqs[0].arrays["in_1"]
    assert isinstance(x, np.ndarray) and x.shape == (MAX_BATCH, 16, 8)
    assert np.array_equal(x[:-1], [r.arrays["in_1"] for r in reqs])
    assert np.array_equal(x[-1], first)                 # padding slot
    for got, ref in zip(outs, expected(real, spec, reqs), strict=True):
        assert np.array_equal(got, ref)
    st = srv.stats()["st"]
    assert st["device_batches"] == 0
    # a host batch crosses whole, padding included, when it is staged
    assert st["staged_bytes"] == (x.nbytes if chained else 0)


@SERVE
def test_store_runner_serves_grids_with_no_new_executable(tmp_path, serve):
    """The store-wrapped runner compiles its one dispatch signature at
    registration; serving full and short device-stacked batches, and a
    fresh cache over the same store, build nothing more."""
    spec = spec_for("jacobi2d")
    cache = DesignCache(store=tmp_path)
    srv = StencilServer(max_batch=MAX_BATCH, cache=cache)
    reg = srv.register("st", spec)
    assert reg.cached.runner.stages_grids
    builds = cache.jit_builds
    assert builds == 1 and len(reg.cached.runner.executables) == 1
    reqs = requests(spec, MAX_BATCH + 1)
    outs = serve(srv, reqs)
    assert cache.jit_builds == builds
    assert len(reg.cached.runner.executables) == 1
    st = srv.stats()["st"]
    assert st["device_batches"] == st["batches"] == 2

    warm = DesignCache(store=tmp_path)
    srv2 = StencilServer(max_batch=MAX_BATCH, cache=warm)
    srv2.register("st", spec)
    assert warm.jit_builds == 0
    for got, ref in zip(serve(srv2, reqs), outs, strict=True):
        assert np.array_equal(got, ref)
