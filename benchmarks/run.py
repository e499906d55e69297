"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Modules:
  intensity          — Fig. 1  (computation intensity)
  single_pe          — Fig. 8  (single-PE resources / VMEM tiles)
  model_accuracy     — Fig. 9  (analytical model vs measured)
  parallelism_sweep  — Figs. 10-17 (GCell/s per parallelism x iteration)
  best_config        — Table 3 (best parallelism per benchmark)
  speedup_vs_soda    — Sec. 5.4 (SASA vs SODA headline speedups)
  serving_throughput — runtime subsystem: cached+batched serving vs
                       per-request autotune (grids/s vs batch size)
  lm_roofline        — assigned-arch roofline table from the dry-run
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from repro.compat import use_compile_cache

    use_compile_cache()
    from benchmarks import (best_config, intensity, lm_roofline,
                            model_accuracy, parallelism_sweep,
                            serving_throughput, single_pe, speedup_vs_soda)
    modules = [
        ("intensity", intensity),
        ("single_pe", single_pe),
        ("best_config", best_config),
        ("speedup_vs_soda", speedup_vs_soda),
        ("serving_throughput", serving_throughput),
        ("model_accuracy", model_accuracy),
        ("parallelism_sweep", parallelism_sweep),
        ("lm_roofline", lm_roofline),
    ]
    print("name,us_per_call,derived")
    for name, mod in modules:
        t0 = time.time()
        try:
            for row in mod.run():
                print(row, flush=True)
        except Exception as e:  # keep the harness alive per-module
            traceback.print_exc(file=sys.stderr)
            print(f"{name}/ERROR,0.00,{type(e).__name__}: {e}")
        print(f"{name}/elapsed,{(time.time() - t0) * 1e6:.0f},",
              flush=True)


if __name__ == "__main__":
    main()
