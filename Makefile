.PHONY: verify test-fast test-workers test-conformance test-measure \
	test-serve test-kernels test-population bench bench-full

# Tier-1 tests (ROADMAP.md)
verify:
	./scripts/verify.sh

# Tier-1 minus the hypothesis property suite (quick local iteration)
test-fast:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} python -m pytest -x -q \
		--ignore=tests/test_core_properties.py

# Worker-fabric suite: subprocess-executor smoke tests, fault paths,
# cross-process cache dedup, the spec wire and worker protocol (the CI
# test-workers job)
test-workers:
	REPRO_CAMPAIGN_WORKERS=2 PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} \
		python -m pytest -q tests/test_workers.py tests/test_worker_fabric.py

# Executor behavioral contract (winner equivalence, cache replay, fault
# paths, cross-process pattern inheritance) + PatternStore journal suite
# (the CI test-conformance job)
test-conformance:
	REPRO_CAMPAIGN_WORKERS=2 PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} \
		python -m pytest -q tests/test_executor_conformance.py \
			tests/test_patterns_store.py

# Adaptive measurement engine: CI-based stopping, incumbent racing,
# cross-process timing lease (the CI test-measure job)
test-measure:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} \
		python -m pytest -q tests/test_measure.py \
			tests/test_executor_conformance.py::test_timing_lease_two_process_contention \
			tests/test_executor_conformance.py::test_measured_fanout_then_serial_replay_agree

# Serving engine: continuous-batching equivalence properties, server
# mechanics, and the online autotune loop (the CI test-serve job)
test-serve:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} \
		python -m pytest -q tests/test_serve_decode.py \
			tests/test_serve_continuous.py tests/test_serve_autotune.py

# Pallas kernels: interpreted sweeps against kernels/ref.py, compiles for
# a described TPU v5e, and the measured perf variants (CI test-kernels)
test-kernels:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} \
		python -m pytest -q tests/test_kernels.py \
			tests/test_chip_compile.py tests/test_perf_variants.py

# Population search: expert personae, tournament racing, island
# migration — includes the slow cross-executor migration/conformance
# legs (the CI test-population job)
test-population:
	REPRO_CAMPAIGN_WORKERS=2 PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} \
		python -m pytest -q tests/test_population.py

# Campaign-engine benchmark tables (CI-scale parameters)
bench:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} python -m benchmarks.run --tables 1,2

# Paper-scale parameters (D=6/10, N=3/5, R=30, k=3) — slow
bench-full:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} python -m benchmarks.run --full
