# Convenience targets; CI runs the same commands (.github/workflows/ci.yml).
# `test` uses whatever python is active (jax, pytest and hypothesis).

PY ?= python
MDFLAGS = XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu

.PHONY: test test-tier1 test-multidevice analyze analyze-lint bench-quick \
	bench-dispatch bench-dispatch-sharded bench-autotune bench-decode-tick \
	bench-qos bench-library bench-fused bench-ci-dispatch bench-serve \
	bench-serve-sharded deps

deps:
	$(PY) -m pip install "jax[cpu]" pytest hypothesis

test-tier1:
	$(PY) -m pytest -x -q

test:
	$(PY) -m pytest -q

# the engine contract gate (docs/analysis.md): stage 1 AST-lints the
# sources (RL001-RL005), stage 2 trace-audits the real entrypoints
# across capacities x QoS margins x residency sets (TA001-TA003); any
# finding not grandfathered in analysis_baseline.txt exits nonzero
analyze:
	PYTHONPATH=src JAX_PLATFORMS=cpu $(PY) -m repro.analysis

# stage 1 only — pure stdlib, runs in ~2s without jax installed
analyze-lint:
	PYTHONPATH=src $(PY) -m repro.analysis --stage lint

# mirrors the CI "multidevice" leg: shard_map tests (incl. the tick-scope
# mesh decode + the QoS tier-mix module) + the sharded dispatch microbench
# on 8 virtual CPU devices
test-multidevice:
	$(MDFLAGS) $(PY) -m pytest -x -q tests/test_sharding.py tests/test_sharded_dispatch.py tests/test_dispatch_plan.py tests/test_qos_tiers.py tests/test_serving.py tests/test_library.py tests/test_paged_cache.py
	PYTHONPATH=src $(MDFLAGS) $(PY) -m benchmarks.bench_dispatch --quick --devices 8 --autotune --decode-tick --qos --library --backend-sweep
	PYTHONPATH=src $(MDFLAGS) $(PY) -m benchmarks.bench_serve --quick --devices 8 --n-reqs 6

bench-quick:
	PYTHONPATH=src $(PY) -m benchmarks.run --quick --only kernels,dispatch

# mirrors the CI dispatch.csv artifact leg (pallas-vs-xla oracle gate)
bench-dispatch:
	PYTHONPATH=src $(PY) -m benchmarks.bench_dispatch --quick

bench-dispatch-sharded:
	PYTHONPATH=src $(PY) -m benchmarks.bench_dispatch --quick --devices 8

# capacity-autotuning trajectory leg: pallas-vs-xla divergence gated at
# EVERY visited operating point
bench-autotune:
	PYTHONPATH=src $(PY) -m benchmarks.bench_dispatch --quick --autotune

# tick-level dispatch planning: a full L-layer decode tick at
# route_scope=layer vs tick (asserts 1 class-sort per tick under tick
# scope; oracle-gated at both scopes)
bench-decode-tick:
	PYTHONPATH=src $(PY) -m benchmarks.bench_dispatch --quick --decode-tick

# per-request QoS tier-mix sweep: mixed error-bound batches at several
# operating points, oracle-gated per mix; asserts loose-bound rows serve
# strictly more invocation than tight-bound rows at every visited point
bench-qos:
	PYTHONPATH=src $(PY) -m benchmarks.bench_dispatch --quick --qos

# approximator-library residency: 16-member library, 4 resident slots,
# phase-shifting demand; the ResidencyController-tuned hot set must serve
# strictly more approximator rows than the static first-4 baseline at the
# same capacities, pallas==xla at every visited residency set, zero
# retraces across swaps
bench-library:
	PYTHONPATH=src $(PY) -m benchmarks.bench_dispatch --quick --library

# the CI dispatch.csv artifact leg: base shapes + autotune trajectory +
# decode-tick + QoS tier-mix + library-residency rows in ONE csv
# (separate invocations would overwrite it)
# fused-kernel sweep: fused vs unfused pallas vs xla over an L-layer
# tick; asserts <=1 standalone activation gather/scatter per layer under
# fused, bitwise fused==pallas + <1e-4 vs xla at every visited operating
# point, zero retraces, and fused no slower than unfused in interpret
bench-fused:
	PYTHONPATH=src $(PY) -m benchmarks.bench_dispatch --quick --backend-sweep

bench-ci-dispatch:
	PYTHONPATH=src $(PY) -m benchmarks.bench_dispatch --quick --autotune --decode-tick --qos --library --backend-sweep

# serving-scheduler arrival replay: Poisson/bursty streams, chunked
# prefill vs token-by-token vs the paged KV cache, p50/p99 TTFT +
# tokens/sec + resident-KV-bytes per offered load; gates chunked==token
# greedy tokens, chunked TTFT wins on long prompts, paged==dense tokens
# at strictly lower kv_bytes_resident, and pallas==xla at the server
# level.  Writes benchmarks/out/serve.csv.
bench-serve:
	PYTHONPATH=src $(PY) -m benchmarks.bench_serve --quick

bench-serve-sharded:
	PYTHONPATH=src $(MDFLAGS) $(PY) -m benchmarks.bench_serve --quick --devices 8 --n-reqs 6
