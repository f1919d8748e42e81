# Developer entry points

.PHONY: lint test-fast test-mid test-std test-all test-fault test-serve-drill test-data-drill test-obs test-paged test-prefix test-spec test-trace test-router test-elastic test-disagg test-parallel test-fleet-obs test-decode-overlap test-kv-tier test-tenant test-ha test-goodput

# stdlib AST lint gate (no ruff/flake8 in the image): unused imports,
# bare except, eval/exec, tabs, trailing whitespace, mutable defaults
lint:
	python tools/lint.py

# <5-min gate on a 1-core CPU-mesh box: units + core model/sharding + one
# pipeline parity case
FAST_FILES = tests/test_config.py tests/test_tokenizer.py tests/test_data.py \
             tests/test_optims.py tests/test_rigid.py tests/test_glue.py \
             tests/test_lm_eval.py tests/test_configs_launch.py \
             tests/test_gpt_model.py tests/test_mesh_sharding.py \
             tests/test_serving.py tests/test_request_queue.py \
             tests/test_chunked_ce.py tests/test_lint.py \
             tests/test_telemetry.py tests/test_tracing.py \
             tests/test_router.py tests/test_controller.py \
             tests/test_prefix_cache.py tests/test_shard_map_compat.py \
             tests/test_fleet_obs.py tests/test_tenancy.py \
             tests/test_fleet_journal.py

# lint runs inside the gate via tests/test_lint.py::test_repo_is_clean
test-fast:
	python -m pytest $(FAST_FILES) -q -m "not slow" -x
	python -m pytest "tests/test_pipeline.py::test_pipeline_1f1b_train_loss_and_grads[2-extra1-4-1]" -q

# mid tier: fast gate + the per-family model/engine suites, still skipping
# the heaviest compile files — the iteration loop for model-family work
# (~4 min warm on 1 core; cold compiles land in tests/.jax_cache, so the
# first run of any tier pays ~3x once)
MID_EXTRA = tests/test_engine.py tests/test_generation.py tests/test_moe.py \
            tests/test_ernie.py tests/test_t5.py tests/test_vit.py \
            tests/test_vision.py tests/test_auto_tune.py tests/test_check.py \
            tests/test_compression_profiler.py tests/test_hf_convert.py \
            tests/test_long_context.py tests/test_paged_cache.py \
            tests/test_continuous_batching.py tests/test_speculative.py \
            tests/test_kv_handoff.py tests/test_tenant_sched.py
test-mid:
	python -m pytest $(FAST_FILES) $(MID_EXTRA) -q -m "not slow" -x
	python -m pytest "tests/test_pipeline.py::test_pipeline_1f1b_train_loss_and_grads[2-extra1-4-1]" -q
	# flash kernel parity (split/fused schedules, bf16 accuracy, config
	# plumb) is a default-gate safety net despite the file's slow mark
	# (~25s warm in interpret mode)
	python -m pytest tests/test_flash_attention.py -q

# standard suite: everything except Pallas interpret-mode / big-compile
# files (marked slow)
test-std:
	python -m pytest tests/ -q -m "not slow"

test-all:
	python -m pytest tests/ -q

# fault-tolerance drills: PFX_FAULT crash-resume parity through the real
# CLI + the resilience/checkpoint-integrity units (docs/fault_tolerance.md)
test-fault:
	python -m pytest tests/test_fault_tolerance.py tests/test_fault_injection.py -q

# serving robustness drills: request-queue units + subprocess traffic
# drills (flood / SIGTERM drain / gen_crash / gen_hang watchdog) through
# the real tools/serve.py CLI (docs/serving.md runbook)
test-serve-drill:
	python -m pytest tests/test_request_queue.py tests/test_serve_drills.py -q

# data-pipeline drills: loader/sampler/index-cache units + subprocess
# fault drills (corrupt_sample skip budget / io_stall watchdog / index-map
# build race / rollback-rewind replay) through the real tools/train.py CLI
# (docs/data_pipeline.md runbook)
test-data-drill:
	python -m pytest tests/test_data.py tests/test_data_drills.py "tests/test_fault_injection.py::test_nan_rollback_rewind_replay_parity" -q

# observability gate: telemetry registry/span/MFU/flight-recorder units,
# the training observatory (per-layer-group stats, non-finite provenance,
# memory watermarks, compile watcher, tools/report.py), the serving
# metrics surfaces, and the Prometheus-exposition + flight recorder
# drills through the real tools/serve.py CLI (docs/observability.md)
test-obs:
	python -m pytest tests/test_telemetry.py tests/test_model_stats.py tests/test_serving.py tests/test_request_queue.py -q -m "not slow"
	python -m pytest tests/test_serve_drills.py -q -k "metrics or gen_hang"

# deep-dive tracing gate: trace-context/buffer/export + SLO units, the
# decision-log replay agreement suite, and the /debug + SLO-breach
# drills through the real tools/serve.py CLI (docs/observability.md
# "Deep-dive tracing" + the runbook)
test-trace:
	python -m pytest tests/test_tracing.py tests/test_telemetry.py -q -m "not slow"
	python -m pytest tests/test_serve_drills.py -q -k "metrics or slo"
	python -m pytest "tests/test_paged_drills.py::test_continuous_mid_decode_eviction_frees_blocks_token_identical" -q

# fleet-observability gate: wall-clock-anchor/span-summary/federation/
# fleet-report units, the cross-process stitch + federation-agreement
# drill through the real router+prefill+decode CLIs, and the lint
# E10/E11/E12 tables (docs/observability.md "Fleet tracing" +
# "Fleet metrics federation")
test-fleet-obs:
	python -m pytest tests/test_fleet_obs.py tests/test_tracing.py tests/test_lint.py -q -m "not slow"
	python -m pytest tests/test_fleet_obs_drills.py -q
	python tools/lint.py

# paged-serving gate: block allocator + paged-attention kernel units,
# the continuous-batching engine/scheduler parity + eviction suite, and
# the subprocess drills through tools/serve.py --scheduler continuous
# (docs/serving.md scheduler section; drills reuse the warm
# tests/.jax_cache like every other drill family)
test-paged:
	python -m pytest tests/test_paged_cache.py tests/test_continuous_batching.py tests/test_paged_drills.py -q

# dispatch-ahead decode overlap gate (docs/decode_path.md
# "Dispatch-ahead decode"): the decision-log replay-equality +
# mid-overlap ArenaReset units, then the two-process serve+router drill
# asserting a streamed /generate arrives in >= 2 flushes with monotone
# token indices and an intact stitched trace
test-decode-overlap:
	python -m pytest tests/test_decode_overlap.py -q

# shared-prefix KV reuse gate: refcount/radix-index/COW host units, the
# engine-level reuse + chunked-prefill parity suite (prefix hits, COW
# divergence, eviction-under-pressure, ArenaReset index rebuild, the
# decision-log replay contract) and the prefix CLI drill
# (docs/serving.md "Prefix cache")
test-prefix:
	python -m pytest tests/test_prefix_cache.py -q
	python -m pytest tests/test_continuous_batching.py -q -k "prefix or chunked or cow or accounting or arena_reset or pressure"
	python -m pytest "tests/test_paged_drills.py::test_prefix_cache_and_chunked_prefill_through_real_cli" -q

# fleet KV-durability gate: the host-RAM spill tier (store units,
# spill -> readmit parity, spill_corrupt degrade-to-recompute,
# ArenaReset invalidation, exact decision-log replay), peer-to-peer
# prefix migration (export/adopt cross-engine, torn-payload whole
# rejection, the PFXH1 truncation fuzz), prefix-affinity routing units,
# and the slow+fault rolling-drain CLI drills — migrate-under-stall
# adoption and the wedged-receiver drain-deadline floor
# (docs/serving.md "KV lifecycle")
test-kv-tier:
	python -m pytest tests/test_kv_tier.py tests/test_kv_handoff.py -q

# serving goodput-ledger gate: time/token ledger closure units (exact
# token closure + <=1% time closure under a seeded adversarial mix),
# the fault-marked closure + fleet-profiling drills through the real
# serve/router CLIs and the train-ledger record surface
# (docs/observability.md "Goodput ledger" + "On-demand profiling")
test-goodput:
	python -m pytest tests/test_goodput.py tests/test_tracing.py -q -m "not slow"
	python -m pytest "tests/test_engine.py::test_metrics_file_stream" -q
	python tools/lint.py

# multi-tenant isolation gate: tenancy units (quotas/DRR/label cap/header
# propagation), scheduler fairness + preemption parity, then the real-CLI
# drills (two-tenant flood, preempt-storm token identity, SSE honest
# close) — docs/serving.md "Multi-tenant isolation"
test-tenant:
	python -m pytest tests/test_tenancy.py tests/test_tenant_sched.py -q
	python -m pytest tests/test_tenant_drills.py -q

# speculative-decoding + KV-quant gate: drafter/accept units, greedy
# parity (contiguous + paged, incl. full-rejection iterations), int8
# kernel tolerance + arena-bytes halving, the sampled
# distribution-preservation statistical test and serving-config routing
# (docs/decode_path.md)
test-spec:
	python -m pytest tests/test_speculative.py -q

# multi-host router gate: router-core units against stub replicas (no
# model), the KV-handoff codec + export/adopt parity suite, and the
# multi-process drills — rolling drain under flood, SIGKILL failover,
# disaggregated prefill/decode parity — through the real tools/serve.py
# + tools/router.py CLIs (docs/serving.md "Multi-host serving")
test-router:
	python -m pytest tests/test_router.py tests/test_kv_handoff.py tests/test_router_drills.py -q

# elastic-control-plane gate: controller/supervisor units against stub
# cores + injected clocks, the router-core remote-drain/auth/rejoin
# units, and the chaos drills through the real CLIs — authenticated
# remote drain + /debug gating, crash-loop quarantine within the flap
# budget, SIGKILL-under-flood supervisor restart + router re-admission,
# SLO-breach scale-up + burn recovery (docs/serving.md "Elastic control
# plane")
test-elastic:
	python -m pytest tests/test_controller.py tests/test_router.py tests/test_elastic_drills.py -q

# control-plane survivability: fleet-journal units (torn-tail fuzz,
# replay exact-fold, adoption identity, tenant bucket restore) + the
# SIGKILL-the-router / journal-loss chaos drills
# (docs/serving.md "Control-plane recovery")
test-ha:
	python -m pytest tests/test_fleet_journal.py -q
	python -m pytest tests/test_ha_drills.py -q

# disaggregated-fabric gate: role-aware pool-supervision units +
# handoff-failover/direct-transfer units (stub replicas, no model), the
# prefix-on-prefill-export parity suite, the PR 10 proxy parity drill,
# and the chaos drills through the real CLIs — direct byte-bypass +
# transport parity, handoff_drop/adopt_crash failover, SIGKILL of both
# pool corpses under supervised flood (docs/serving.md "Disaggregated
# operations")
test-disagg:
	python -m pytest tests/test_controller.py tests/test_router.py tests/test_kv_handoff.py -q
	python -m pytest tests/test_disagg_drills.py -q
	python -m pytest "tests/test_router_drills.py::test_disaggregated_prefill_decode_parity_via_router" -q

# multi-chip parallelism gate: the shard_map-port surface in one run —
# compat-adapter units, 1F1B pipeline parity (loss+grads, virtual
# stages, bf16), ring/zigzag long-context parity (incl. the nested
# pp2 x sep2 subprocess case), sharding-rule/ZeRO families, the
# six-layout engine parity sweep, the 2-process jax.distributed e2e,
# and every golden-doc walkthrough incl. the slow-marked ones
# (docs/parallelism.md)
test-parallel:
	python -m pytest tests/test_shard_map_compat.py tests/test_pipeline.py tests/test_long_context.py tests/test_mesh_sharding.py tests/test_distributed.py -q
	python -m pytest "tests/test_engine.py::test_layout_loss_parity_first_step" -q
	python -m pytest tests/test_golden_docs.py -q
