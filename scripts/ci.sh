#!/usr/bin/env bash
# Tier-1 CI gate: full test suite + CPU smoke of the end-to-end flows.
#
# Usage: scripts/ci.sh [fast]
#   fast: skip the `slow`-marked multi-device subprocess tests.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

MARK=()
if [[ "${1:-}" == "fast" ]]; then
  MARK=(-m "not slow")
fi

echo "== lint: pyflakes =="
# CI installs pyflakes (see .github/workflows/ci.yml); hosts without it
# fall back to a byte-compile pass so the gate never silently vanishes.
if python -c "import pyflakes" >/dev/null 2>&1; then
  python -m pyflakes src tests benchmarks examples scripts
else
  echo "pyflakes not installed; falling back to compileall"
  python -m compileall -q src tests benchmarks examples scripts
fi

echo "== lint: compat imports =="
# ast-based version-policy guard: experimental/private jax APIs (shard_map,
# pcast, element-indexed BlockSpecs, executable serialization, jax._src)
# only via repro/compat.py
python scripts/check_compat_imports.py

echo "== lint: stock kernels + example DSL =="
# static analyzer gate: every stock kernel x 4 boundary modes and every
# example DSL source must verify with zero error-severity diagnostics
python scripts/lint_stencils.py

echo "== lint: machine-readable numerics pass over examples =="
# repro.lint's JSON mode over every DSL literal embedded in examples/:
# exits non-zero only on error-severity diagnostics, and the JSON output
# is itself validated (this doubles as a CI check of the --format json
# contract that editor/CI integrations consume)
python -m repro.lint --format json --from-py examples/*.py | python -c '
import json, sys
doc = json.load(sys.stdin)
assert doc["version"] == 1 and "summary" in doc, "bad lint JSON shape"
s = doc["summary"]
print("lint JSON ok: %d literal(s), %d error(s), %d warning(s)"
      % (len(doc["files"]), s["errors"], s["warnings"]))
'

echo "== slow-marker audit =="
# static guard: subprocess suites stay slow-marked, the conformance
# suite's hypothesis profile stays CI-capped, and the pinned random-spec
# floor stays >= 200 — so the growing suite can't silently blow up
# tier-1 wall-clock
python scripts/audit_slow_markers.py

echo "== tier-1: pytest =="
# --durations=15 prints the slowest tests on every run, making
# wall-clock regressions visible in the CI log before they hurt
python -m pytest -x -q --durations=15 "${MARK[@]}"

echo "== smoke: examples/quickstart.py =="
python examples/quickstart.py

echo "== smoke: serving runtime (pipeline + cache + batching + bucketing) =="
# --smoke scales the traces down to CI size while asserting the same
# gates: tile pipeline no slower than vmap with strictly fewer HLO fusion
# boundaries; >=20 shapes from <=4 bucket designs, >=5x over per-shape
# autotune, async dispatch not slower than sync, reference-exact results;
# cold-start: a fresh subprocess against a warm DesignStore reaches its
# first bitwise-identical result >=10x faster than cold autotune+jit,
# with zero autotune invocations and zero jit builds on the warm side.
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
  python benchmarks/serving_throughput.py --smoke

echo "== smoke: analytical-model ranking accuracy =="
# calibrate-on-some / validate-on-held-out at CI size; gate: the model
# must order held-out kernels' (iterations, fusion) points better than
# chance — ranking is what the auto-tuner consumes
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
  python benchmarks/model_accuracy.py --smoke

echo "== smoke: continuous-batching serving latency =="
# Poisson open-loop trace against the flush-barrier loop and the
# continuous scheduler over one shared cache; gates: zero drops,
# continuous throughput >= 0.9x flush, p99 at or below the barrier's,
# every result bitwise-identical to synchronous single-shot serve()
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
  python benchmarks/serving_latency.py --smoke

echo "CI OK"
