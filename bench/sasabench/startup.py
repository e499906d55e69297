"""Start-up shared by the benchmark's scripts: JAX's compile cache in this
checkout, JAX on the TPU, and the time each step of start-up took.

A script puts ``bench/`` and ``src/`` on ``sys.path`` and calls
:func:`start` before anything compiles.
"""
from __future__ import annotations

import os
import sys
import time

from sasabench.cells import ROOT


def start(chips: int, who: str, t_start: float, log) -> bool:
    """Point JAX's persistent compile cache at ``.jax_cache/`` in this
    checkout (a fixed path; the program takes the directory it is given
    in the environment), then check that JAX sees ``chips`` TPU chips.
    Nothing may compile before this call.  Returns False, having said why
    on standard error, where JAX does not see them."""
    cache_dir = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    t_jax = time.perf_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"{who}: needs {chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform!r} device(s)", file=sys.stderr)
        return False
    from repro.compat import use_compile_cache

    use_compile_cache()
    # cache every program, however quick its compile, so that a second
    # run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"start-up: jax imported at {t_jax - t_start:.3f} s, "
        f"{len(devices)} {devices[0].device_kind} device(s) up at "
        f"{t_devices - t_start:.3f} s, program imported at "
        f"{time.perf_counter() - t_start:.3f} s")
    return True
