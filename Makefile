# mlrun-tpu make targets (reference analog: Makefile test/test-go-unit/...)

PYTHON ?= python

.PHONY: help test test-fast chaos lint-invariants native bench bench-serve bench-fleet bench-train bench-attn bench-autoscale bench-lora bench-canary bench-goodput bench-reqtrace bench-elastic bench-prefill bench-fleet-elastic bench-reconcile bench-kv-tier bench-failslow bench-spec bench-index obs-smoke dryrun clean

help:            ## list targets with their one-line descriptions
	@grep -E '^[a-z][a-zA-Z_-]*:.*##' $(MAKEFILE_LIST) | \
	  awk -F':.*## ' '{printf "  %-16s %s\n", $$1, $$2}'

test:            ## full suite on the virtual 8-device CPU mesh
	$(PYTHON) -m pytest tests/ -q

test-fast:       ## skip the slow jax-compile-heavy suites
	$(PYTHON) -m pytest tests/ -q \
	  --ignore=tests/test_models_training.py \
	  --ignore=tests/test_context_parallel.py \
	  --ignore=tests/test_pipeline_parallel.py \
	  --ignore=tests/test_bert.py --ignore=tests/test_moe.py \
	  --ignore=tests/test_checkpoint.py --ignore=tests/test_ops.py \
	  --ignore=tests/test_llm_engine.py

chaos:           ## fault-injection subset: runs + serving resilience (docs/fault_tolerance.md, docs/serving_resilience.md)
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m chaos

lint-invariants: ## mlt-lint: AST invariant checker over the package (docs/static_analysis.md); JSON report at /tmp/mlt_lint.json
	JAX_PLATFORMS=cpu $(PYTHON) -m mlrun_tpu.analysis mlrun_tpu/ --json /tmp/mlt_lint.json

native:          ## build the C++ log collector (mlt-logd)
	$(MAKE) -C native

bench:           ## training benchmark on a TPU (one JSON line; exits non-zero without a chip)
	$(PYTHON) bench.py

bench-serve:     ## prefix-cache / chunked-prefill microbench, CPU-runnable (one JSON line)
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py

bench-fleet:     ## engine-fleet routing A/B at replicas=4: affinity vs random, CPU-runnable (one JSON line)
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --fleet

bench-autoscale: ## closed-loop autoscaling A/B under a synthetic load ramp (docs/observability.md "Autoscaler"); rewrites BENCH_r08.json
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --autoscale > BENCH_r08.tmp \
		&& tail -n 1 BENCH_r08.tmp > BENCH_r08.json \
		&& rm BENCH_r08.tmp && cat BENCH_r08.json

bench-lora:      ## multi-tenant LoRA A/B: batched multi-adapter engine vs sequential merged-weights swaps (docs/serving.md "Multi-tenant LoRA"); rewrites BENCH_r09.json
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --lora > BENCH_r09.tmp \
		&& tail -n 1 BENCH_r09.tmp > BENCH_r09.json \
		&& rm BENCH_r09.tmp && cat BENCH_r09.json

bench-canary:    ## continuous fine-tune→canary→promote closed loop: injected drift → detection→promotion wall time + stable-path canary-split overhead (docs/continuous_tuning.md); rewrites BENCH_r11.json
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --canary > BENCH_r11.tmp \
		&& tail -n 1 BENCH_r11.tmp > BENCH_r11.json \
		&& rm BENCH_r11.tmp && cat BENCH_r11.json

bench-reqtrace:  ## request-forensics A/B: phase ledger + exemplars on vs off on the repeated-prefix workload (docs/observability.md "Request attribution"); rewrites BENCH_r12.json
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --reqtrace > BENCH_r12.tmp \
		&& tail -n 1 BENCH_r12.tmp > BENCH_r12.json \
		&& rm BENCH_r12.tmp && cat BENCH_r12.json

bench-prefill:   ## paged prefill kernel + int8 KV pages A/B: prefix-hit TTFT kernel vs gather + hit-rate at fixed pool bytes int8 on/off (docs/serving.md "Attention kernels"); rewrites BENCH_r15.json
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --prefill-kernel > BENCH_r15.tmp \
		&& tail -n 1 BENCH_r15.tmp > BENCH_r15.json \
		&& rm BENCH_r15.tmp && cat BENCH_r15.json

bench-fleet-elastic: ## pod-elasticity A/B: cold vs pre-warmed ring join p95 TTFT + SLO met/violated through a fake_k8s pod preemption (docs/serving.md "Engine fleet"); rewrites BENCH_r16.json
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --fleet-elastic > BENCH_r16.tmp \
		&& tail -n 1 BENCH_r16.tmp > BENCH_r16.json \
		&& rm BENCH_r16.tmp && cat BENCH_r16.json

bench-reconcile: ## control-plane crash-recovery A/B: journaled reconcile vs cold below-min rebuild — recovery wall, ticks, orphaned JobSets, dropped requests (docs/fault_tolerance.md "Control-plane crash recovery"); rewrites BENCH_r17.json
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --reconcile > BENCH_r17.tmp \
		&& tail -n 1 BENCH_r17.tmp > BENCH_r17.json \
		&& rm BENCH_r17.tmp && cat BENCH_r17.json

bench-kv-tier:   ## hierarchical KV cache A/B: host-tier hit rate at fixed device bytes + ring-reassignment fetch vs re-prefill first-request TTFT (docs/serving.md "Hierarchical KV"); rewrites BENCH_r18.json
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --kv-tier --prefixes 6 \
		--requests-per-prefix 2 > BENCH_r18.tmp \
		&& tail -n 1 BENCH_r18.tmp > BENCH_r18.json \
		&& rm BENCH_r18.tmp && cat BENCH_r18.json

bench-failslow:  ## fail-slow detection A/B: one chaos-degraded replica, detection off vs on — p95 TTFT, zero drops, zero error-path redispatches (docs/observability.md "Replica health & fail-slow detection"); rewrites BENCH_r19.json
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --failslow > BENCH_r19.tmp \
		&& tail -n 1 BENCH_r19.tmp > BENCH_r19.json \
		&& rm BENCH_r19.tmp && cat BENCH_r19.json

bench-spec:      ## in-engine speculative decoding A/B: spec-off vs spec-on vs adversarial draft on the paged engine — decode tokens/s, acceptance, exact-parity booleans (docs/serving.md "Speculative decoding"); rewrites BENCH_r20.json
	JAX_PLATFORMS=cpu $(PYTHON) bench_serve.py --spec > BENCH_r20.tmp \
		&& tail -n 1 BENCH_r20.tmp > BENCH_r20.json \
		&& rm BENCH_r20.tmp && cat BENCH_r20.json

bench-index:     ## aggregate all BENCH_r*.json into the BENCH_INDEX.md trajectory table
	$(PYTHON) scripts/bench_index.py

bench-train:     ## hot-loop pipelining A-B: prefetch on/off + compile cache, CPU-runnable (one JSON line)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --train

bench-goodput:   ## goodput/badput attribution of the train A-B (docs/observability.md "Goodput & badput"); rewrites BENCH_r10.json
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --train --goodput > BENCH_r10.tmp \
		&& tail -n 1 BENCH_r10.tmp > BENCH_r10.json \
		&& rm BENCH_r10.tmp && cat BENCH_r10.json

bench-elastic:   ## elastic vs full-resubmit A-B under the same injected slice kill (docs/fault_tolerance.md "Elastic training"); rewrites BENCH_r13.json
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PYTHON) bench.py --elastic > BENCH_r13.tmp \
		&& tail -n 1 BENCH_r13.tmp > BENCH_r13.json \
		&& rm BENCH_r13.tmp && cat BENCH_r13.json

bench-attn:      ## attention kernels vs reference (flash v2 + paged decode), CPU interpret mode; rewrites BENCH_ATTN_CPU.json
	JAX_PLATFORMS=cpu $(PYTHON) scripts/bench_attention_cpu.py

obs-smoke:       ## graph + fleet + adapter + training smoke: scrape /metrics, federate, SLO status, adapter cardinality, span artifact, goodput families + flight artifact on a forced preemption (docs/observability.md)
	JAX_PLATFORMS=cpu $(PYTHON) scripts/obs_smoke.py

dryrun:          ## multi-chip sharding dryrun on 8 virtual CPU devices
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PYTHON) __graft_entry__.py 8

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
