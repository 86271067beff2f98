# Developer entry points. Tier-1 CI runs `make lint` semantics via
# tests/test_analysis.py::test_repo_is_clean_under_strict (+ the
# v2/v3/v4/v5 per-family gates and the stub-drift gate in
# tests/test_analysis_v3.py).

.PHONY: lint lint-diff lint-stats lint-stubs-check gen-stubs test \
	trace-demo bench-rl-dist bench-chaos bench-gang bench-pipeline

# The full gate: regenerate-and-diff the typed RPC stubs, then the
# strict 14-family run WITH the stats.json refresh folded in (one
# analysis pass serves both; a drifted stats artifact shows up as a
# dirty tree, same as drifted stubs).
lint: lint-stubs-check
	python -m ray_tpu.analysis --strict \
		--stats-json ray_tpu/analysis/stats.json

# Pre-push fast path: findings only in files changed vs origin/main
# (override with DIFF_REF=<ref>); whole-program indexes still span the
# package, so cross-file findings in your files are not missed.
DIFF_REF ?= origin/main
lint-diff:
	python -m ray_tpu.analysis --strict --diff $(DIFF_REF)

# Back-compat alias: the artifact now refreshes on every `make lint`.
lint-stats:
	python -m ray_tpu.analysis --strict --stats \
		--stats-json ray_tpu/analysis/stats.json

# Drift gate for the generated typed RPC stubs (core/rpc_stubs.py):
# regenerate in place and fail when the checked-in module changed —
# i.e. a handler signature moved without rerunning --gen-stubs. The
# rpc-stub-drift rule enforces the same in-process for `--strict`.
lint-stubs-check:
	python -m ray_tpu.analysis --gen-stubs
	git diff --exit-code -- ray_tpu/core/rpc_stubs.py

gen-stubs:
	python -m ray_tpu.analysis --gen-stubs

test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow'

# The bench-* targets below run on the CPU backend: their rows are
# counts and mechanisms, never chip times (PERF.md). The chip check is
# `python chip_smoke.py`; the chip benchmark is `benchmarks/run.py`.

# Control-plane MTTR (ISSUE 12): SIGKILL the serve controller under
# live streams via util/faultinject (never ad-hoc kills), measure
# detection -> snapshots-flowing recovery, in-flight failures (bound
# 0) and adopted-in-place replicas -> BENCH_SERVE.json, rows merged
# without clobbering the existing sections.
bench-chaos:
	JAX_PLATFORMS=cpu python bench_chaos.py

# Multi-host gang bench (ISSUE 13): formation latency, member-death ->
# reconciled MTTR and coordinator-failover MTTR for 2/4/8-host virtual
# groups (8x8/8 virtual slice), faults via util/faultinject at the
# member beat site -> BENCH_SERVE.json rows, merge-preserving.
bench-gang:
	JAX_PLATFORMS=cpu python bench_gang.py

# Pipeline-parallel training plane (ISSUE 14): inter-stage activation
# bytes/s through the object plane at 2/4 stages, 1F1B bubble fraction
# vs microbatch count, ZeRO-1 per-replica optimizer-state bytes at
# data=2/4/8 -> BENCH_TUNE.json "rows", merge-preserving.
bench-pipeline:
	JAX_PLATFORMS=cpu python bench_pipeline.py

# Podracer substrate scaling rows (env-steps/s + learner updates/s at
# 1/2/4 rollout actors, parameter-staleness p50/p99) -> BENCH_RL.json
# distributed section; other sections' rows are preserved.
bench-rl-dist:
	python bench_rl.py --sections distributed

# Tiny serve session through the real HTTP proxy -> Chrome trace JSON,
# validated (loads as JSON, >=1 cross-process parent/child span,
# engine step slices merged). Tier-1 runs the same demo in-process
# (tests/test_trace_demo.py).
trace-demo:
	JAX_PLATFORMS=cpu python -m ray_tpu.serve.trace_demo \
		--output /tmp/serve_trace.json
