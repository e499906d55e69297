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


def start(chips: int, who: str, t_start: float, log,
          workers_hold_chips: bool = False) -> bool:
    """Point JAX's persistent compile cache at ``.jax_cache/`` in this
    checkout (a fixed path; the program takes the directory it is given
    in the environment), then check that there are ``chips`` TPU chips.
    Nothing may compile before this call.  Returns False, having said why
    on standard error, where there are fewer.

    Where the cell's chips belong to worker processes
    (``workers_hold_chips``), the chips are counted on the host's bus and
    this process opens no JAX backend: one would hold every chip it sees.
    The workers inherit the environment, and with it the compile cache."""
    cache_dir = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    # a worker reads this one from the environment as it imports JAX
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    t_jax = time.perf_counter()
    if workers_hold_chips:
        from repro.serve.router import tpu_host_chips

        found = tpu_host_chips()
        ok = found >= chips
        seen = f"{found} TPU chip(s) on this host's bus"
        up = f"{seen}, counted with no JAX backend here,"
    else:
        devices = jax.devices()
        found = len(devices)
        ok = devices[0].platform == "tpu" and found >= chips
        seen = f"JAX found {found} {devices[0].platform!r} device(s)"
        up = f"{found} {devices[0].device_kind} device(s) up"
    t_devices = time.perf_counter()
    if not ok:
        print(f"{who}: needs {chips} TPU chip(s); {seen}", file=sys.stderr)
        return False
    from repro.compat import use_compile_cache

    use_compile_cache()
    # cache every program, however quick its compile, so that a second
    # run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"start-up: jax imported at {t_jax - t_start:.3f} s, "
        f"{up} at {t_devices - t_start:.3f} s, program imported at "
        f"{time.perf_counter() - t_start:.3f} s")
    return True
