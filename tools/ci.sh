#!/usr/bin/env bash
# CI smoke: engine-conformance fast lane, then the tier-1 test suite + one
# quickstart example end-to-end.
#
#   tools/ci.sh            # matrix lane + full tier-1 (ROADMAP.md) + quickstart
#   tools/ci.sh --fast     # matrix lane + GENIE-core test modules + quickstart
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# First lane: static contracts.  Pure-AST (no jax import), so a spine/kernel/
# lock/hygiene violation fails in milliseconds before any device work.
# Contracts + suppression syntax: docs/CONTRACTS.md.
echo "--- genielint (static invariants; docs/CONTRACTS.md) ---"
PYTHONPATH=".:$PYTHONPATH" python -m tools.genielint --json reports/lint.json

# Fast lane: the engine x {reference,kernel} x {search,segmented} conformance
# matrix runs first so an engine-contract break fails in minutes (the
# distributed leg needs a multi-device subprocess and runs with the suite).
echo "--- engine conformance matrix (fast lane) ---"
python -m pytest -q -k "matrix and not distributed" tests/test_engine_matrix.py

echo "--- segment/merge conformance (segmented == monolithic) ---"
python -m pytest -q -k "not distributed" tests/test_segments.py

echo "--- planner parity (execute(plan) == legacy paths, plan-cache hits) ---"
python -m pytest -q -k "not distributed and not sharded_serving" tests/test_plan.py

echo "--- routing conformance (ROUTED_VERIFIED == full scan bit-for-bit) ---"
python -m pytest -q -k "not distributed" tests/test_routing.py

echo "--- serving-frontend parity (coalesced == serial bit-for-bit, 6 engines x routing on/off) ---"
python -m pytest -q -k "parity_matrix or mixed_tenants" tests/test_frontend.py

echo "--- autotuner contracts (tiled-plan parity, cache fallback, plan keying) ---"
python -m pytest -q -k "not tune_end_to_end and not service_tune" tests/test_autotune.py

if [[ "${1:-}" == "--fast" ]]; then
    # (tests/test_plan.py's fast, non-subprocess lane already ran above)
    python -m pytest -x -q \
        tests/test_engines.py tests/test_engine_matrix.py tests/test_cpq.py \
        tests/test_streaming.py tests/test_kernels.py tests/test_system.py
else
    # tier-1 verify command from ROADMAP.md
    python -m pytest -x -q
fi

echo "--- quickstart example ---"
python examples/quickstart.py

echo "--- add-throughput micro-benchmark (BENCH JSON; fails if not flat) ---"
PYTHONPATH=".:$PYTHONPATH" python benchmarks/bench_add_throughput.py

echo "--- serve-latency micro-benchmark (BENCH JSON; cached vs uncached plan) ---"
PYTHONPATH=".:$PYTHONPATH" python benchmarks/bench_serve_latency.py

echo "--- frontend-throughput benchmark (BENCH JSON; batched >= 2x serial gate) ---"
PYTHONPATH=".:$PYTHONPATH" python benchmarks/bench_frontend.py

echo "--- signature-storage roofline (BENCH JSON; packed <= wide/4 gate) ---"
PYTHONPATH=".:$PYTHONPATH" python benchmarks/roofline.py

echo "--- coarse-routing micro-benchmark (BENCH JSON; parity + <50% scanned at recall >= 0.95) ---"
PYTHONPATH=".:$PYTHONPATH" python benchmarks/bench_routing.py

# tiny-budget smoke of the measured autotuner: its main() gates on tuned ==
# default parity, the cache round-trip + fingerprint gate, tuned >= 1.0x on
# at least one engine, and no engine regressing past the noise floor.  The
# full-size acceptance run (>= 1.15x on two engines) is benchmarks/run.py.
echo "--- autotune smoke (BENCH JSON; parity + cache + tuned never regresses) ---"
PYTHONPATH=".:$PYTHONPATH" python -m benchmarks.bench_autotune \
    --n 2048 --q 16 --budget 6 --repeats 2 --engines minsum,tanimoto
echo "CI smoke OK"
