#!/usr/bin/env bash
# Tier-1 verify: the quick (non-slow) suite, then the 8-device GRASP
# exchange equivalence check in its own process (it must set XLA's host
# device count before jax initialises).
set -euo pipefail
cd "$(dirname "$0")/.."

python -m pytest -q -m "not slow"

XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python tests/helpers/grasp_gnn_equivalence.py

# 8-device check of the pipelined (overlap=True) GRASP step vs the
# sequential exchange: loss and params equal to float32 rounding (1e-6)
# over multiple layers/steps
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python tests/helpers/grasp_pipeline_equivalence.py

# non-tier-1: serving subsystem end-to-end smoke (GRASP cache vs unpinned
# baselines + shed-load p99 bound); emits BENCH_serve.json
PYTHONPATH=src python -m benchmarks.serve_smoke --out BENCH_serve.json

# non-tier-1: gateway RPC front-end over loopback sockets (closed-loop hit
# rate vs the baseline BENCH_serve.json just wrote + 2x-overload tail
# bound + 503-retry recovery); bounded wall-clock, emits BENCH_gateway.json
PYTHONPATH=src timeout 600 python -m benchmarks.gateway_smoke --out BENCH_gateway.json

# non-tier-1: seeded fault injection over the same stack (conservation
# under crashes/resets, supervisor restarts == injected deaths, breaker
# 500-tail bound, same-seed determinism, warm-restart snapshot recovery);
# bounded wall-clock, emits BENCH_chaos.json
PYTHONPATH=src timeout 600 python -m benchmarks.chaos_smoke --out BENCH_chaos.json

# non-tier-1: tracked perf baseline (vectorized lookup >=3x the retained
# reference loop with bit-identical outputs/counters, pipelined dist step
# within 1e-6 of sequential, hot_gather microbench); emits BENCH_perf.json
PYTHONPATH=src timeout 600 python -m benchmarks.perf_smoke --out BENCH_perf.json

echo "verify: OK"
