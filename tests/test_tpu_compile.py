"""Compile the served Pallas kernels for a described TPU v5e, no chip needed.

The Pallas interpreter, which runs every other kernel test on the CPU,
accepts block shapes and VMEM footprints that the Mosaic lowering
refuses.  These tests run the TPU compiler itself against a described
``v5e:2x2`` topology: every stock kernel at its paper grid size, batch 8,
at the geometry the TPU ranking picks, through the batched runner the
server dispatches.  Each compiled program must contain the Mosaic kernel
(``tpu_custom_call``) and fit the chip's HBM.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import stencils
from repro.core.autotune import autotune
from repro.core.ir import lower
from repro.core.model import ParallelismConfig
from repro.core.platform import TPU_PLATFORMS
from repro.runtime.batching import build_batched_runner
from repro.runtime.bucketing import bucket_spec

ITERATIONS = 16      # what chip_smoke.py serves; the ranking derives s from it
BATCH = 8            # the server's max_batch


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip, with the persistent compilation cache off
    around the module (a compile for a described chip cannot be read
    back, and would warn on the next lookup)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(spec, cfg, dev):
    run = build_batched_runner(
        spec, cfg, iterations=ITERATIONS, devices=[dev], backend="pallas",
        interpret=False,
    )
    assert (run.path, run.backend, run.interpret) == (
        "tile_pipeline", "pallas", False
    )
    one = SingleDeviceSharding(dev)
    args = {
        n: jax.ShapeDtypeStruct((BATCH,) + tuple(shape), jnp.dtype(dt),
                                sharding=one)
        for n, (dt, shape) in spec.inputs.items()
    }
    compiled = run.jitted.lower(args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= TPU_PLATFORMS[dev.device_kind].hbm_bytes, used
    return compiled


def _ranked(spec, dev):
    """The design the served path picks on one chip of this kind."""
    plat = TPU_PLATFORMS[dev.device_kind].with_chips(1)
    tuned = autotune(spec, platform=plat, iterations=ITERATIONS, build=False)
    return tuned.spec, tuned.config


@pytest.mark.parametrize("name", sorted(stencils.BENCHMARKS))
def test_ranked_design_compiles_for_v5e(v5e, name):
    spec, cfg = _ranked(stencils.BENCHMARKS[name](iterations=ITERATIONS), v5e)
    assert cfg.buffer_depth >= 2 and cfg.variant == "temporal", cfg
    _compile(spec, cfg, v5e)


def test_odd_halo_depth_compiles_for_v5e(v5e):
    """s*r = 3: the input window is 2*3 rows deeper than the tile, no
    multiple of 8 unless plan_blocks rounds it up."""
    spec = lower(stencils.jacobi2d(iterations=ITERATIONS)).spec
    cfg = ParallelismConfig(
        "temporal", s=3, tile_rows=256, batch_tile=BATCH, buffer_depth=2
    )
    _compile(spec, cfg, v5e)


def test_bucketed_replicate_design_compiles_for_v5e(v5e):
    """The streamed halo-index fixup of a bucketed replicate design (the
    per-stage clamp the server runs for mixed-shape image filters)."""
    bspec = bucket_spec(
        stencils.blur_replicate(iterations=ITERATIONS), (16384, 1024), None
    )
    spec, cfg = _ranked(bspec, v5e)
    assert spec.halo_index_inputs
    _compile(spec, cfg, v5e)
