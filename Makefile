# Convenience entry points (the canonical commands the docs reference).
PY ?= python
REPO := $(dir $(abspath $(lastword $(MAKEFILE_LIST))))

.PHONY: test test-book chip-smoke test-onchip bench int8-bench \
	serve-bench decode-bench ragged-bench health-bench phase-bench \
	pass-bench pipeline-bench autotune recovery-drill recovery-bench \
	serve-drill \
	perf-compare lint-api lint-resilience lint-observability \
	lint-collectives lint-passes lint-kernels analyze

test:            ## full suite on the 8-device virtual CPU mesh (~8 min)
	$(PY) -m pytest tests/ -q --ignore=tests/book

test-book:       ## the 10 book workloads (end-to-end models)
	$(PY) -m pytest tests/book -q

chip-smoke:      ## trainer + decode server (+ 4-chip DP) once ON the chip; fails without a TPU
	$(PY) chip_smoke.py

test-onchip:     ## curated pytest subset on the chip (needs a TPU)
	PADDLE_TPU_TEST_REAL=1 PYTHONPATH=$(REPO) \
	  $(PY) -m pytest tests/test_onchip_smoke.py -m onchip -q

bench:           ## one-line JSON headline; non-zero exit without a TPU
	PYTHONPATH=$(REPO) $(PY) bench.py

int8-bench:      ## int8 vs bf16 vs fp32 dense-serving A/B
	PYTHONPATH=$(REPO) $(PY) tools/bench_int8_serve.py

serve-bench:     ## serving-engine load generator (throughput + p50/p99)
	PYTHONPATH=$(REPO) PT_BENCH_SERVE=1 $(PY) bench.py

decode-bench:    ## decode-lane load-gen: tokens/s vs naive, steady-state compiles==0, p99
	PYTHONPATH=$(REPO) PT_BENCH_DECODE=1 $(PY) bench.py

ragged-bench:    ## bucketed-padded vs ragged serving A/B + modeled fp32/int8 KV bytes
	PYTHONPATH=$(REPO) PT_BENCH_RAGGED=1 $(PY) bench.py

health-bench:    ## health-sentinel on/off A/B (overhead gate <=2% p50)
	PYTHONPATH=$(REPO) PT_BENCH_HEALTH=1 $(PY) bench.py

phase-bench:     ## phase-instrumentation on/off A/B (overhead within noise)
	PYTHONPATH=$(REPO) PT_BENCH_PHASES=1 $(PY) bench.py

pass-bench:      ## graph-passes on/off A/B + per-pass cost attribution
	PYTHONPATH=$(REPO) PT_BENCH_PASSES=1 $(PY) bench.py

pipeline-bench:  ## pipeline-as-policy A/B: PipelineRunner vs PipelinePolicy, gpipe vs 1f1b, microbatch sweep
	PYTHONPATH=$(REPO) PT_BENCH_PIPELINE=1 $(PY) bench.py

autotune:        ## mesh autotuner sweep: enumerate→rank→measure, report + pinned-winner re-run
	PYTHONPATH=$(REPO) PT_BENCH_AUTOTUNE=1 $(PY) bench.py

recovery-drill:  ## fast in-process preempt→restore drill (window restore + parity)
	JAX_PLATFORMS=cpu $(PY) -m paddle_tpu.distributed.recovery

recovery-bench:  ## measured recovery rung: per-phase seconds + MTTR into the bench record
	PYTHONPATH=$(REPO) PT_BENCH_RECOVERY=1 $(PY) bench.py

serve-drill:     ## serving fault drills: replica_kill failover (token-exact), canary promotion, hedging
	PYTHONPATH=$(REPO) PT_BENCH_SERVE_DRILL=1 $(PY) bench.py

# diff two bench records (one JSON line each, as `make bench` prints),
# exit nonzero on regression:
#   make perf-compare OLD=a.json NEW=b.json [PC_ARGS=--threshold-pct=10]
perf-compare:    ## regression gate between two bench records
	$(PY) tools/perf_compare.py $(OLD) $(NEW) $(PC_ARGS)

lint-api:        ## fail if the public API surface drifted from API.spec
	$(PY) tools/gen_api_spec.py --check

lint-resilience: ## no swallowed errors / unbounded waits in the distributed layer
	$(PY) tools/lint_resilience.py

lint-observability: ## no bare print() diagnostics in library code
	$(PY) tools/lint_observability.py

lint-collectives: ## raw psum/ppermute sites must route through the kernels layer
	$(PY) tools/lint_collectives.py

lint-passes:     ## program mutation outside the pass framework / sanctioned transpilers
	$(PY) tools/lint_passes.py

lint-kernels:    ## raw pallas_call/pallas imports must route through kernels/primitives/
	$(PY) tools/lint_kernels.py

analyze:         ## the whole static-analysis gate: six source lints + IR verify over the model zoo
	$(PY) tools/lint_collectives.py
	$(PY) tools/lint_passes.py
	$(PY) tools/lint_resilience.py
	$(PY) tools/lint_observability.py
	$(PY) tools/lint_kernels.py
	$(PY) tools/gen_api_spec.py --check
	JAX_PLATFORMS=cpu $(PY) tools/analyze_program.py --zoo all --mesh dp=4 --strict
