# fusioninfer-tpu — build/test/deploy targets (capability parity with the
# reference's Makefile: manifests/test/lint/build/deploy + drift checks).

PYTHON ?= python
IMG ?= fusioninfer-tpu:latest

.PHONY: all
all: test

##@ Development

.PHONY: manifests
manifests: ## Regenerate config/ from the Python sources.
	$(PYTHON) -m fusioninfer_tpu.cli render config --out config

.PHONY: manifests-check
manifests-check: manifests ## Fail if config/ drifts from the generators.
	@git diff --exit-code -- config/ || \
		(echo "config/ drifted — run 'make manifests' and commit" && exit 1)

.PHONY: test
test: ## Unit + integration tests (virtual 8-device CPU mesh).
	$(PYTHON) -m pytest tests/ -q

.PHONY: test-fast
test-fast: ## Tests, stop at first failure.
	$(PYTHON) -m pytest tests/ -x -q

.PHONY: fast
fast: ## Sub-2-minute smoke tier (curated module list: tests/conftest.py FAST_MODULES).
	$(PYTHON) -m pytest tests/ -q -m fast

.PHONY: test-tpu
test-tpu: ## Hardware kernel tests (interpret=False, serving shapes): a chip-tool command — fails, not skips, without a TPU.
	FUSIONINFER_TEST_TPU=1 $(PYTHON) -m pytest tests/test_kernels_tpu.py -q

KIND_CLUSTER ?= fusioninfer-tpu-e2e

.PHONY: test-e2e
test-e2e: ## kind e2e: deploy the operator into a real cluster, reconcile a sample (needs kind/kubectl/docker).
	FUSIONINFER_E2E=1 KIND_CLUSTER=$(KIND_CLUSTER) $(PYTHON) -m pytest test/e2e/ -v -q

.PHONY: test-e2e-repro
test-e2e-repro: ## Reproducible kind e2e from the committed bundle + script; evidence lands in test/e2e/kind/last-run/.
	KIND_CLUSTER=$(KIND_CLUSTER) test/e2e/kind/run-kind-e2e.sh

.PHONY: cleanup-test-e2e
cleanup-test-e2e: ## Tear down the e2e kind cluster.
	kind delete cluster --name $(KIND_CLUSTER)

.PHONY: chaos
chaos: ## Fault-injection chaos suite (seeded, deterministic; docs/design/resilience.md).
	$(PYTHON) -m pytest tests/test_resilience.py -q -m chaos

.PHONY: autoscale
autoscale: ## Autoscaling suite (fake-clock control-loop + drain + chaos; docs/design/autoscaling.md).
	$(PYTHON) -m pytest tests/test_autoscale.py tests/test_metrics.py -q

.PHONY: lint
lint: ## Gating lint: fusionlint (all thirteen passes incl. trace-boundary + thread-safety, JSON archived to dist/lint.json) + fault-site coverage + byte-compile (CI adds ruff).
	$(PYTHON) -m tools.fusionlint --json-out dist/lint.json
	$(PYTHON) tools/check_fault_sites.py
	$(PYTHON) -m compileall -q fusioninfer_tpu tests tools bench.py chip_smoke.py __graft_entry__.py

.PHONY: lint-changed
lint-changed: ## Fast pre-commit lint: fusionlint over files differing from HEAD only.
	$(PYTHON) -m tools.fusionlint --changed

.PHONY: compile-gate
compile-gate: ## Compile-budget gate: self-test, then `make fast` under the compile ledger, then per-family signature budgets (docs/design/static-analysis.md).
	$(PYTHON) tools/check_compile_budget.py --self-test
	FUSIONINFER_COMPILE_LEDGER=dist/compile_ledger.json $(PYTHON) -m pytest tests/ -q -m fast
	$(PYTHON) tools/check_compile_budget.py dist/compile_ledger.json

.PHONY: lock-gate
lock-gate: ## Lock-order gate: self-test, then `make fast` under the lock trace, then cycle-check the merged static+runtime graph (docs/design/static-analysis.md).
	$(PYTHON) tools/check_lock_order.py --self-test
	FUSIONINFER_LOCKTRACE=dist/lock_trace.json $(PYTHON) -m pytest tests/ -q -m fast
	$(PYTHON) tools/check_lock_order.py dist/lock_trace.json

.PHONY: verify-manifests
verify-manifests: ## Regenerate CRDs/config from the Python sources in memory, fail on drift; validate samples against the CRD schemas.
	$(PYTHON) tools/verify_manifests.py

.PHONY: bench
bench: ## One-line JSON decode-throughput benchmark on the accelerator (fails without one; BENCH_PLATFORM=cpu = bench-smoke).
	$(PYTHON) bench.py
	$(PYTHON) tools/check_bench_record.py BENCH_OUT.json

.PHONY: bench-smoke
bench-smoke: ## CPU bench smoke + record gates: ceiling_fraction/scheduler fields, tp=2 sharedprefix leg.
	BENCH_PLATFORM=cpu $(PYTHON) bench.py
	$(PYTHON) tools/check_bench_record.py BENCH_OUT.json

.PHONY: fleet-smoke
fleet-smoke: ## Closed-loop fleet smoke (CPU, 3 engines + PD pair): real manager+engines+EPP+autoscaler through steady/PD-fabric/scale-up/OVERLOAD/REVOCATION/faults/recover/drain; record gated (SLO-tier shed + preempt/park/resume, spot revocation waves w/ evacuation + survivor resume, layer-streamed PD overlap >= 0.5 + cross-engine prefix pull).
	$(PYTHON) bench.py --fleet-smoke --out FLEET_OUT.json
	$(PYTHON) tools/check_fleet_record.py FLEET_OUT.json

.PHONY: dryrun
dryrun: ## Multichip sharding dry-run on 8 virtual CPU devices.
	$(PYTHON) __graft_entry__.py 8

.PHONY: chip-smoke
chip-smoke: ## engine serve qwen3-1.7b on the chip, end to end: a chip-tool command (python chip_smoke.py --cpu-dry-run = CPU control-flow check).
	$(PYTHON) chip_smoke.py

##@ Render

.PHONY: render-samples
render-samples: ## Dry-run render every sample InferenceService.
	@for f in config/samples/*.yaml; do \
		echo "--- $$f"; \
		$(PYTHON) -m fusioninfer_tpu.cli render resources -f $$f > /dev/null || exit 1; \
	done; echo "all samples render"

##@ Build

.PHONY: docker-build
docker-build: ## Build the controller image.
	docker build --target controller -t $(IMG) .

.PHONY: docker-build-engine
docker-build-engine: ## Build the engine image (JAX TPU + loader deps).
	docker build --target engine -t fusioninfer-tpu-engine:latest .

.PHONY: build-installer
build-installer: manifests ## Single-file install manifest (kustomize transforms applied).
	$(PYTHON) -m fusioninfer_tpu.cli render installer --out dist/install.yaml

##@ Deployment

.PHONY: install
install: manifests ## Install CRDs into the current cluster.
	kubectl apply -f config/crd/bases/

.PHONY: deploy
deploy: ## Deploy controller via kustomize.
	kubectl apply -k config/default

.PHONY: undeploy
undeploy:
	kubectl delete -k config/default --ignore-not-found=true

.PHONY: help
help:
	@awk 'BEGIN {FS = ":.*##"} /^[a-zA-Z_-]+:.*?##/ { printf "  %-18s %s\n", $$1, $$2 }' $(MAKEFILE_LIST)
