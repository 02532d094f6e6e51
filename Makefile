# Convenience entry points (the canonical commands the docs reference).
PY ?= python
REPO := $(dir $(abspath $(lastword $(MAKEFILE_LIST))))

.PHONY: test test-book chip-smoke test-onchip benchmark recovery-drill \
	serve-drill lint-api lint-resilience lint-observability \
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

# one cell of BENCHMARK.json, one run, on the chip (PERF.md has the account):
#   make benchmark CELL=gpt2-large.closed16-mixed SEED=1
benchmark:       ## the harness of record: one cell of BENCHMARK.json; non-zero exit without a TPU
	$(PY) benchmark/run.py --workload $(CELL) --seed $(SEED) --seconds 40 --trace 0

recovery-drill:  ## fast in-process preempt→restore drill (window restore + parity)
	JAX_PLATFORMS=cpu $(PY) -m paddle_tpu.distributed.recovery

serve-drill:     ## serving fault drills: replica_kill failover (token-exact), canary promotion, hedging
	$(PY) -m paddle_tpu.serving.drill

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
