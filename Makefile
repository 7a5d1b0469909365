# Repo CI entry points (documented in README.md "Verify").
# The tier-1 command is `make test`; `make ci` adds the compileall lint pass
# and runs the schema-conformance + executor-differential suites first
# (fail fast on the paper's invariants before the long e2e sweeps).

PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)
# tests run on the CPU (Pallas kernels in interpret mode); the bench-*
# targets run on whatever accelerator JAX finds
PYTEST := JAX_PLATFORMS=cpu $(PYTHON) -m pytest

.PHONY: test test-fast test-schemas test-stream test-x2y test-hierarchy \
	test-obs lint ci bench bench-quick bench-skewed bench-fused \
	bench-sharded bench-coded bench-stream bench-x2y bench-hierarchy \
	bench-obs

test:
	$(PYTEST) -q

# tier-1 minus the `slow` marker (full arch/kernel/model-decode e2e sweeps)
test-fast:
	$(PYTEST) -q -m "not slow"

# the paper's correctness core: schema conformance + bucketed-, fused-,
# sharded- and coded-executor differential tests
test-schemas:
	$(PYTEST) -q tests/test_schema_conformance.py \
		tests/test_bucketed_executor.py tests/test_fused_executor.py \
		tests/test_sharded_executor.py tests/test_coded_executor.py

# streaming maintenance: edit-sequence conformance + streamed-vs-cold
# differential + serving edit API
test-stream:
	$(PYTEST) -q tests/test_stream.py tests/test_stream_tail.py

# rectangular X2Y execution: the executor-generic conformance matrix
# (every registry executor x {allpairs, x2y, some-pairs, block} x skew
# profiles) plus the X2Y differential suite (rect kernel vs oracle, rect
# partition invariants, streaming X- and Y-side edits, skew-join routing)
test-x2y:
	$(PYTEST) -q tests/test_schema_conformance.py \
		tests/test_x2y_executors.py

# hierarchical planning: prefix pack vs FFD/BFD oracles, composed gap
# ledger (gap_total == gap_outer * gap_inner), PlanCache keying by
# grouping factor, sampled pair coverage at large m, run_block vs dense
test-hierarchy:
	$(PYTEST) -q tests/test_hierarchy.py

# observability layer: histogram quantiles vs numpy, span nesting +
# Chrome-trace schema, comm-ledger reconciliation exact on every
# executor (coded r=2 vs the analytic model on an 8-device mesh),
# FUSED_STATS isolation regression, cache-eviction events
test-obs:
	$(PYTEST) -q tests/test_obs.py

lint:
	$(PYTHON) -m compileall -q src

ci: lint test-schemas test-stream test-x2y test-hierarchy test-obs test \
	bench-coded bench-obs

bench:
	$(PYTHON) benchmarks/bench_planner.py

bench-quick:
	$(PYTHON) benchmarks/bench_planner.py --quick

bench-skewed:
	$(PYTHON) benchmarks/bench_engine.py --skewed

# dense vs bucketed vs fused executor; writes benchmarks/BENCH_engine.json
bench-fused:
	$(PYTHON) benchmarks/bench_engine.py --fused

# sharded vs bucketed vs fused over the local devices (8 virtual ones when
# JAX_PLATFORMS=cpu); merges the engine_sharded section into
# benchmarks/BENCH_engine.json
bench-sharded:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PYTHON) benchmarks/bench_engine.py --sharded

# coded vs sharded assembly traffic over the local devices (8 virtual ones
# when JAX_PLATFORMS=cpu) +
# replication-vs-communication Pareto frontier; writes
# benchmarks/BENCH_coded.json and enforces the acceptance bars:
# allclose to dense, coded r=2 assembly bytes <= 0.6x uncoded sharded,
# frontier monotone in r, every point >= the Thm-8 lower bound
bench-coded:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PYTHON) benchmarks/bench_coded.py

# streaming edits vs full re-planning on Zipf m=512 (first-edit p99,
# update latency, recompute fraction, delta-vs-replan comm bytes); writes
# benchmarks/BENCH_stream.json and enforces the acceptance bars:
# first-edit p99 < 200ms, sustained achievable gap <= 1.3x, nonzero
# drift_replans + repacks, <25% single-edit recompute, allclose/conformance
bench-stream:
	$(PYTHON) benchmarks/bench_stream.py

# X2Y planner bounds + every registry executor on the skew_join(200x8)
# and balanced(30x30) rectangular profiles; merges into
# benchmarks/BENCH_x2y.json
bench-x2y:
	$(PYTHON) -m benchmarks.bench_x2y

# hierarchical planner at m=10^6 (Zipf profile) + block serving; writes
# benchmarks/BENCH_hierarchy.json and enforces the acceptance bars:
# plan+bound < 10s, o(m^2) host index, sampled coverage == 1.0,
# gap_total <= 2x flat gap at m=1024, block-served allclose to dense
bench-hierarchy:
	$(PYTHON) benchmarks/bench_hierarchy.py

# observability overhead on the serving hot path: fused Zipf m=512
# obs-on vs obs-off (repro.obs.configure kill switch); writes
# benchmarks/BENCH_obs.json and enforces the acceptance bar: < 5%
bench-obs:
	$(PYTHON) benchmarks/bench_obs.py
