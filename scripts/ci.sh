#!/usr/bin/env bash
# Tier-1 verification, as run by .github/workflows/ci.yml: install the
# manifest dependencies, run the test suite on CPU (the Pallas kernels
# are interpreted there), then run the serving load generator
# in smoke mode and gate on the recorded baseline. Falls back to
# preinstalled deps in hermetic/offline containers; tests/conftest.py
# shims `hypothesis` if the dev extras could not be installed.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m pip install -e ".[dev]" \
    || echo "ci.sh: pip install failed (offline?); using preinstalled deps"

JAX_PLATFORMS=cpu PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -q --durations=10

# Cross-workload serving conformance + LM property suites, in full: the
# default addopts exclude tests marked `slow` (the LM decode differential
# pin and the padding sweep), so run these two files with the marker
# filter cleared — a new Workload plugin is servable exactly when this
# passes.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q -o addopts= \
    tests/test_workload_conformance.py tests/test_lm_properties.py

# Serving load generator, smoke mode: real drain race (async vs sync, with
# the batched-vs-sequential equivalence assertion inside) + virtual-time
# Poisson sweep. Writes the artifact next to the checked-in baseline so
# the two can be diffed, then gates:
#   - equivalence: benchmarks/serving.py asserts max_abs_dev < 1e-4 and
#     exits non-zero on violation (caught by set -e above);
#   - throughput: async drain windows/sec must stay within 20% of the
#     checked-in BENCH_serving.json baseline.
mkdir -p artifacts
BENCH_SERVING_OUT=artifacts/BENCH_serving.json \
    PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --only serving

python scripts/check_serving_baseline.py \
    BENCH_serving.json artifacts/BENCH_serving.json

# Telemetry-overhead gate: enabling spans + decision logging on the real
# async drain race must cost <= 5% throughput (and the disabled-mode hot
# path must not have grown per-request work — measured on the pure-Python
# virtual-time DES, where bookkeeping cannot hide behind device compute).
PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} \
    python scripts/check_telemetry_overhead.py

# Kernel suite: Pallas kernels + the batched megakernel. Writes the
# roofline/equivalence artifact, then gates megakernel-vs-reference
# equivalence, zero spill, and the no-regression floor on the analytic
# interpret-mode HBM-traffic ratios (see scripts/check_kernels_baseline.py).
BENCH_KERNELS_OUT=artifacts/BENCH_kernels.json \
    PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --only kernels

python scripts/check_kernels_baseline.py \
    BENCH_kernels.json artifacts/BENCH_kernels.json

# Cost-model gate: shipped characterization tables must validate and the
# calibrated paper profile must stay within +/-3 points of the paper's
# headline ratios on the checked-in measured trace (pure arithmetic).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python scripts/check_profiles.py
