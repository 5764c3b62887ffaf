.PHONY: test quick slow verify serve-smoke gateway-smoke chaos-smoke perf-smoke gateway

# full tier-1 suite (same command ROADMAP.md documents)
test:
	PYTHONPATH=src python -m pytest -x -q

# quick loop: everything except the multi-minute subprocess tests
quick:
	python -m pytest -q -m "not slow"

slow:
	python -m pytest -q -m slow

# quick suite + the 8-device GRASP exchange equivalence check + serve smoke
verify:
	./scripts/verify.sh

# end-to-end repro.serve check on a zipf stream (non-tier-1): GRASP cache
# must beat the unpinned baselines and shed-load must bound p99; emits
# BENCH_serve.json
serve-smoke:
	PYTHONPATH=src python -m benchmarks.serve_smoke --out BENCH_serve.json

# loopback load test of the repro.gateway RPC front-end (non-tier-1):
# closed-loop hit rate over real sockets + 2x-overload open loop with the
# shed-load tail bound and 503-retry recovery; emits BENCH_gateway.json
gateway-smoke:
	PYTHONPATH=src python -m benchmarks.gateway_smoke --out BENCH_gateway.json

# seeded fault-injection run of the gateway stack (non-tier-1): request
# conservation under crashes/resets/latency spikes, supervisor restarts ==
# injected pump deaths, breaker-bounded 500 tail, same-seed injection-log
# determinism, and warm-restart snapshot hit-rate recovery; emits
# BENCH_chaos.json
chaos-smoke:
	PYTHONPATH=src python -m benchmarks.chaos_smoke --out BENCH_chaos.json

# tracked perf baseline (non-tier-1): vectorized cache lookup rows/s vs the
# retained reference loop (>=3x floor at batch 256 / zipf 1.1, bit-identical
# outputs + counters), pipelined vs sequential GRASP dist step (loss and
# params within 1e-6 on the 8-device mesh), and the hot_gather kernel
# microbench (all timed on the host CPU);
# emits BENCH_perf.json
perf-smoke:
	PYTHONPATH=src python -m benchmarks.perf_smoke --out BENCH_perf.json

# launch the gateway for manual poking (recsys engine on :8077):
#   curl -s -XPOST localhost:8077/v1/score -d '{"hist":[1,2,3],"candidates":[4,5]}'
gateway:
	PYTHONPATH=src python -m repro.launch.serve --engine recsys --smoke --gateway 127.0.0.1:8077
