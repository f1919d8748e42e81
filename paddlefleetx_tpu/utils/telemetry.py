"""Unified telemetry: the process-wide metrics registry, span timing, MFU
accounting, and the crash flight recorder.

PRs 1-4 each grew an ad-hoc stats surface (``GenerationServer.stats``,
``RequestQueue.stats``, loader ``stats()``, the ``/healthz`` counter dict,
the engine's JSONL metrics stream) with no single place to scrape and no
hardware-utilization signal.  This module is the one layer under all of
them:

  - **Registry** — thread-safe counters, gauges, and histograms with label
    support.  Every metric NAME must be declared in the ``METRICS`` table
    below and match ``^pfx_[a-z0-9_]+$`` (``tools/lint.py`` E10 enforces
    both statically; the registry raises on undeclared names at runtime),
    so the ``/metrics`` namespace cannot fragment the way the per-module
    dicts did.  ``snapshot()`` returns ONE locked, consistent view;
    ``render_prometheus()`` renders that same view as Prometheus text
    exposition — ``/metrics`` and ``/healthz`` in ``tools/serve.py`` are
    two renderings of one snapshot, never two racing read paths.
  - **StatsView** — a dict-like per-instance stats object (drop-in for the
    old hand-rolled dicts, so ``server.stats["traces"] += 1`` keeps
    working) whose numeric keys are exported onto the registry through a
    weakly-referenced collector.  Instance-local semantics stay exactly as
    before (tests assert absolute per-instance counts); the registry sums
    across live instances at snapshot time.
  - **Span** — lightweight monotonic-clock phase timing.  ``mark()``
    stamps a labeled instant (callers may inject externally-captured
    timestamps, e.g. the request queue's pickup time); ``phases()`` turns
    consecutive marks into durations; ``event()`` shapes the span for the
    flight recorder.
  - **MFU accounting** — the analytic GPT-family FLOPs estimator
    (6·N per token for fwd+bwd, 2·N forward-only; PaLM's convention,
    Chowdhery et al. 2022) plus the per-device-kind peak-FLOPs table
    behind the ``PFX_PEAK_FLOPS`` override: what the ``mfu`` key of the
    engine's step records is computed from (the benchmark keeps its own arithmetic,
    ``pfx_bench/model_math.py``).
  - **FlightRecorder** — a bounded ring of recent structured events (step
    records, data_skip, rollback, preempt_save, gen_errors, watchdog
    flips, request spans, ``pfx.stall`` events) dumped to
    ``flight_recorder.jsonl`` on crash, force-quit, watchdog-degraded, and
    anomaly rollback — postmortems no longer depend on having had
    ``Engine.metrics_file`` set.
  - **StallWatch** — the slow-iteration watcher beside ``ledger_span``:
    the trainer's fit loop and the serving scheduler hand it every
    iteration's wall seconds and bucket seconds; one that ran far past
    the median of its own kind leaves a ``pfx.stall`` event with what the
    thread, the device wait and the machine did meanwhile
    (docs/observability.md "Slow iterations").  Always on: two CPU-clock
    reads and a comparison an iteration, everything else only when an
    iteration is slow.

Knobs (loud-parse, repo convention): ``PFX_PEAK_FLOPS`` (per-chip peak
FLOP/s used as the MFU denominator; default per detected device kind),
``PFX_FLIGHT_DIR`` (artifact directory for dumps + trace exports,
default ./artifacts/), ``PFX_FLIGHT_RECORDER`` (explicit dump path —
overrides everything), ``PFX_FLIGHT_RECORDER_CAP`` (ring capacity,
default 256).  The :class:`SLOTracker` evaluates configured serving
objectives (p99 TTFT, error rate) over rolling multi-window burn rates
and exports them as ``pfx_slo_*`` gauges (docs/observability.md).

Contract notes: metric *mutations* never take the registry lock (each
metric/collector owns a private lock), so hot paths (the serving scheduler,
the train loop) never contend with a scrape; ``snapshot()`` takes the
registry lock and then each collector's lock, and nothing acquires them in
the other order.  No jax import at module scope — launchers that must stay off
the chip (``chip_smoke.py``'s parent) and ``tools/lint.py`` stay jax-free.
"""

from __future__ import annotations

import bisect
import gc
import json
import os
import re
import statistics
import sys
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

from paddlefleetx_tpu.utils.log import logger

METRIC_NAME_RE = re.compile(r"^pfx_[a-z0-9_]+$")

# ---------------------------------------------------------------------------
# THE metric declaration table: name -> (kind, help).  Every name emitted
# through the registry must live here (runtime check + tools/lint.py E10).
# Naming schema: pfx_<subsystem>_<what>[_<unit>][_total]; seconds for time,
# *_total for monotonic cumulatives.
# ---------------------------------------------------------------------------
METRICS: Dict[str, Tuple[str, str]] = {
    # serving core (core/serving.py GenerationServer)
    "pfx_serving_requests_total": ("counter", "Completed generate_ids calls"),
    "pfx_serving_tokens_out_total": ("counter", "Generated tokens delivered"),
    "pfx_serving_gen_seconds_total": ("counter", "Wall seconds inside generate_ids"),
    "pfx_serving_traces_total": ("counter", "Decode jit trace-time entries (retrace probe)"),
    "pfx_serving_gen_errors_total": ("counter", "Generation failures"),
    "pfx_serving_last_latency_seconds": ("gauge", "Latency of the most recent generate_ids call"),
    "pfx_serving_warmup_seconds_total": ("counter", "Seconds spent in warmup compiles"),
    "pfx_serving_params_bytes": ("gauge", "Bytes of the parameter tree the server holds, per leaf dtype, set when it is built (labels: dtype): a bf16 configuration holds its matmul and embedding leaves as bfloat16 and only the LayerNorm leaves as float32; weights under float32 there mean the tree is converted inside every decode step"),
    # request queue (core/request_queue.py)
    "pfx_queue_submitted_total": ("counter", "Requests admitted"),
    "pfx_queue_completed_total": ("counter", "Requests answered"),
    "pfx_queue_batches_total": ("counter", "Runner batches executed"),
    "pfx_queue_coalesced_batches_total": ("counter", "Batches that merged >1 request"),
    "pfx_queue_coalesced_requests_total": ("counter", "Requests served via a coalesced batch"),
    "pfx_queue_shed_deadline_total": ("counter", "Requests shed at their deadline"),
    "pfx_queue_rejected_full_total": ("counter", "Admissions rejected: queue full"),
    "pfx_queue_rejected_closed_total": ("counter", "Admissions rejected: draining"),
    "pfx_queue_gen_errors_total": ("counter", "Runner batches that raised"),
    "pfx_queue_depth": ("gauge", "Requests waiting in the admission queue"),
    "pfx_queue_busy_seconds": ("gauge", "Seconds the current runner call has been executing"),
    # HTTP surface (tools/serve.py)
    "pfx_batch_occupancy": ("gauge", "Active rows / capacity of the continuous decode batch"),
    "pfx_kv_blocks_used": ("gauge", "Paged KV arena blocks allocated to live sequences"),
    "pfx_kv_blocks_free": ("gauge", "Paged KV arena blocks available"),
    "pfx_kv_blocks_available": ("gauge", "Arena blocks admissible right now: free plus reclaimable cached-prefix blocks (the decode-pool scale signal)"),
    "pfx_request_evictions_total": ("counter", "Rows evicted mid-decode (deadline shed frees their blocks)"),
    "pfx_prefill_admits_total": ("counter", "Rows admitted into the running batch (prefill-on-admit)"),
    # speculative decoding + KV quantization (ops/speculative.py,
    # models/gpt/generation.py spec loops, core/continuous_batching.py)
    "pfx_spec_proposed_total": ("counter", "Draft tokens proposed to the speculative verify step"),
    "pfx_spec_accepted_total": ("counter", "Draft tokens accepted and committed by the verify step"),
    "pfx_spec_accept_rate": ("gauge", "Lifetime accepted/proposed draft ratio"),
    "pfx_kv_bytes": ("gauge", "Live KV-cache payload bytes (used blocks x K+V bytes per block)"),
    "pfx_kv_bytes_per_token": ("gauge", "Bytes one cached token takes over all layers that cache tokens, from the model: per-head K and V, or one latent"),
    "pfx_state_bytes_per_row": ("gauge", "Bytes a row keeps beside its pages whatever its length, over all state-space layers: the recurrent state and the conv's last columns (0 for a block whose every layer caches tokens)"),
    "pfx_kv_ring_bytes_per_row": ("gauge", "Bytes a row's rings of window-layer pages take whatever its length, over all window layers (0 for a model without window layers)"),
    "pfx_kv_pages_held": ("gauge", "Pages held of each class of a two-class arena (a model with window layers): class=full the pages that grow with a row, class=window the rows' rings"),
    "pfx_kv_pages_free": ("gauge", "Free pages of each class of a two-class arena (class=full|window); an admission needs both"),
    # shared-prefix KV reuse + chunked prefill (core/paged_cache.py
    # PrefixIndex, core/continuous_batching.py)
    "pfx_prefix_hits_total": ("counter", "Admissions that reused cached prefix blocks"),
    "pfx_prefix_misses_total": ("counter", "Admissions that found no cached prefix (cache enabled)"),
    "pfx_prefix_hit_tokens_total": ("counter", "Prompt tokens whose KV was reused instead of recomputed"),
    "pfx_prefix_evictions_total": ("counter", "Cached prefix blocks evicted (LRU budget or allocation pressure)"),
    "pfx_prefix_cached_blocks": ("gauge", "Arena blocks currently pinned by the prefix index"),
    "pfx_prefill_chunks_total": ("counter", "Chunked-prefill dispatches (one prompt chunk per scheduler iteration)"),
    # host-RAM spill tier (core/paged_cache.py PrefixSpillStore,
    # core/continuous_batching.py spill/readmit sites)
    "pfx_prefix_spill_bytes": ("gauge", "Host-RAM bytes held by spilled prefix blocks (--prefix-spill-bytes tier)"),
    "pfx_prefix_spill_entries": ("gauge", "Prefix blocks currently resident in the host-RAM spill store"),
    "pfx_prefix_spills_total": ("counter", "Evicted prefix blocks demoted to the host-RAM spill store"),
    "pfx_prefix_readmits_total": ("counter", "Spilled prefix blocks promoted back into the arena on a prefix match"),
    "pfx_prefix_spill_discards_total": ("counter", "Spilled entries lost instead of readmitted (checksum/corruption, budget pressure, failed spill or readmit) — the graceful-degradation counter"),

    "pfx_http_requests_in_flight": ("gauge", "In-flight /generate requests"),
    "pfx_http_responses_total": ("counter", "HTTP responses by status code"),
    "pfx_http_client_gone_total": ("counter", "Responses lost to client disconnects"),
    "pfx_request_latency_seconds": ("histogram", "End-to-end /generate latency"),
    "pfx_request_ttft_seconds": ("histogram", "Time to first token (request receipt to first flush; non-streamed: decode done)"),
    "pfx_request_itl_seconds": ("histogram", "Inter-token latency: gap between consecutive streamed token flushes"),
    "pfx_request_queue_wait_seconds": ("histogram", "Admission to scheduler pickup"),
    "pfx_request_decode_seconds": ("histogram", "Scheduler pickup to decode completion"),
    "pfx_request_per_token_seconds": ("histogram", "Decode seconds per delivered token"),
    "pfx_serve_draining": ("gauge", "1 while the server drains for shutdown"),
    "pfx_serve_degraded": ("gauge", "1 while the wedged-generation watchdog is tripped"),
    # training (core/engine.py)
    "pfx_train_steps_total": ("counter", "Optimizer steps completed"),
    "pfx_train_tokens_total": ("counter", "Training tokens consumed"),
    "pfx_train_loss": ("gauge", "Loss at the last logged step"),
    "pfx_train_tokens_per_second": ("gauge", "Throughput over the last logging window"),
    "pfx_train_model_flops_per_second": ("gauge", "Achieved model FLOP/s (analytic estimator)"),
    "pfx_train_mfu": ("gauge", "Model FLOPs utilization vs per-chip peak"),
    "pfx_train_compile_seconds": ("gauge", "First-dispatch trace+compile seconds"),
    "pfx_train_data_wait_seconds_total": ("counter", "Cumulative seconds the step loop waited on data"),
    "pfx_train_host_seconds_total": ("counter", "Cumulative host-side seconds (placement + dispatch)"),
    "pfx_train_rollbacks_total": ("counter", "Anomaly rollbacks executed"),
    "pfx_train_preempt_saves_total": ("counter", "Preemption-path final checkpoints"),
    # training observatory (utils/model_stats.py; labels: group)
    "pfx_train_group_grad_norm": ("gauge", "Per-layer-group gradient L2 norm at the last stats step"),
    "pfx_train_group_param_norm": ("gauge", "Per-layer-group parameter L2 norm at the last stats step"),
    "pfx_train_group_update_ratio": ("gauge", "Per-layer-group update-norm / param-norm ratio at the last stats step"),
    "pfx_train_group_nonfinite_frac": ("gauge", "Per-layer-group fraction of non-finite gradient elements at the last stats step"),
    # memory watermarks (utils/model_stats.py; labels: device)
    "pfx_mem_host_rss_bytes": ("gauge", "Host resident-set size of this process"),
    "pfx_mem_device_bytes_in_use": ("gauge", "Accelerator bytes currently allocated, per device"),
    "pfx_mem_device_peak_bytes": ("gauge", "Peak accelerator bytes allocated, per device"),
    "pfx_mem_device_limit_bytes": ("gauge", "Accelerator memory capacity, per device"),
    "pfx_mem_headroom_frac": ("gauge", "Worst-device free-memory fraction (None-limit devices excluded)"),
    # retrace attribution (utils/model_stats.py CompileWatcher)
    "pfx_compile_events_total": ("counter", "Backend compiles observed by the compile watcher"),
    "pfx_compile_seconds_total": ("counter", "Cumulative backend-compile seconds observed"),
    # data pipeline (data/batch_sampler.py loader stats)
    "pfx_data_skips_total": ("counter", "Corrupt samples skipped under the budget"),
    "pfx_data_stall_warnings_total": ("counter", "Prefetch starvation warnings"),
    "pfx_data_wait_seconds_total": ("counter", "Loader-reported cumulative data wait"),
    "pfx_data_prefetch_depth": ("gauge", "Batches currently buffered by the prefetcher"),
    # profiler (utils/profiler.py)
    "pfx_profiler_traces_total": ("counter", "Profiler trace windows captured"),
    "pfx_profiler_trace_seconds": ("gauge", "Wall seconds of the last trace window"),
    # deep-dive tracing (utils/tracing.py)
    "pfx_trace_sampled_total": ("counter", "Requests/runs sampled into the trace buffer"),
    # fleet metrics federation (core/router.py FleetFederation): the
    # router re-exports every replica's own pfx_* samples from its scrape
    # under ONE generic family — the original sample name rides the
    # `name` label (histogram _bucket/_sum/_count samples federate as
    # their flat spellings), original labels ride along, and counters
    # re-export as their current value (Prometheus-federation style)
    "pfx_fleet_metric": ("gauge", "Federated replica sample re-exported by the router (labels: replica, pool, name=original sample name + the original labels)"),
    "pfx_fleet_scrape_age_seconds": ("gauge", "Seconds since the replica's last successful federation scrape (labels: replica) — the staleness gauge"),
    "pfx_fleet_scrapes_total": ("counter", "Federation scrape attempts (labels: replica, outcome=ok|missing|error)"),
    "pfx_fleet_series": ("gauge", "Federated series currently re-exported (after the cardinality cap)"),
    "pfx_fleet_series_dropped": ("gauge", "Federated series dropped by the PFX_FLEET_SERIES_CAP label-cardinality cap (warned loudly; 0 when everything fits)"),
    # disaggregated KV handoff (core/continuous_batching.py replica side)
    "pfx_handoff_exports_total": ("counter", "Prefilled rows exported as KV-handoff payloads (prefill replica)"),
    "pfx_handoff_adopts_total": ("counter", "KV-handoff payloads adopted into the arena (decode replica)"),
    "pfx_handoff_bytes_total": ("counter", "KV-handoff payload bytes through THIS replica (labels: transport=direct|proxy; prefill counts direct sends, decode counts receives)"),
    "pfx_handoff_direct_total": ("counter", "Direct prefill->decode transfer attempts on the prefill replica (labels: outcome=ok|fallback|rejected|decode_dead)"),
    # drain-time prefix migration (tools/serve.py donor send,
    # core/continuous_batching.py adopt_prefixes receiver)
    "pfx_migrate_sent_total": ("counter", "Prefix-migration payloads accepted by a surviving peer during this replica's drain"),
    "pfx_migrate_adopted_total": ("counter", "Prefix blocks adopted into this arena from a draining peer's migration payload"),
    "pfx_migrate_failed_total": ("counter", "Prefix-migration sends abandoned (retries exhausted or the PFX_MIGRATE_DEADLINE_S ladder expired) — the drain exits 0 regardless"),
    # multi-host router (core/router.py + tools/router.py; labels noted)
    "pfx_router_requests_total": ("counter", "Requests dispatched by the router (labels: replica, outcome)"),
    "pfx_router_rejected_total": ("counter", "Router admissions rejected before dispatch (labels: reason)"),
    "pfx_router_retries_total": ("counter", "Dispatches retried on another replica after connection-refused"),
    "pfx_router_in_flight": ("gauge", "Requests currently inside the router"),
    "pfx_router_replica_depth": ("gauge", "Queue depth last reported by the replica /healthz (labels: replica)"),
    "pfx_router_replica_state": ("gauge", "Replica lifecycle state code: 0 booting, 1 warm, 2 serving, 3 draining, 4 gone (labels: replica)"),
    "pfx_router_replica_latency_seconds": ("histogram", "Downstream dispatch latency (labels: replica)"),
    "pfx_router_poll_failures_total": ("counter", "Failed replica health polls (labels: replica)"),
    "pfx_router_drains_total": ("counter", "Replica drains initiated through the router"),
    "pfx_router_handoff_bytes_total": ("counter", "KV-handoff payload bytes PROXIED through the router (flat under direct transfer)"),
    "pfx_router_handoff_seconds": ("histogram", "Prefill dispatch + handoff transfer seconds per prompt (direct transport: the whole prefill->decode relay — the router cannot see the legs separately)"),
    "pfx_handoff_failovers_total": ("counter", "Handoff legs failed over by the router (labels: leg=prefill|decode)"),
    # elastic control plane (core/controller.py + tools/router.py
    # --supervise; docs/serving.md "Elastic control plane")
    "pfx_controller_ticks_total": ("counter", "Control-loop evaluations, one decision row each (labels: pool on disaggregated pool controllers; unlabeled for the monolith fleet)"),
    "pfx_controller_scale_ups_total": ("counter", "Replica scale-up decisions executed (labels: pool on disaggregated pool controllers)"),
    "pfx_controller_scale_downs_total": ("counter", "Replica scale-down (rolling-drain) decisions executed (labels: pool on disaggregated pool controllers)"),
    "pfx_controller_target_replicas": ("gauge", "Replica count the controller is steering toward (labels: pool on disaggregated pool controllers)"),
    "pfx_controller_breach": ("gauge", "1 while the controller sees a scale signal breached (SLO burn / depth / occupancy / low blocks; labels: pool on disaggregated pool controllers)"),
    "pfx_replica_restarts_total": ("counter", "Supervisor restarts of managed replicas after unexpected exits (labels: replica; only crashes spend the flap budget)"),
    "pfx_replica_quarantines_total": ("counter", "Managed replicas quarantined after crash-looping past the flap budget (labels: replica)"),
    # control-plane survivability (core/router.py FleetJournal +
    # tools/router.py recovery; docs/serving.md "Control-plane recovery")
    "pfx_router_recoveries_total": ("counter", "Router boots that recovered control-plane state from the fleet journal (fleet_state.jsonl)"),
    "pfx_router_adopted_replicas_total": ("counter", "Live replicas re-adopted into their supervised slots at boot without a respawn (labels: replica)"),
    "pfx_router_journal_records": ("gauge", "Records appended to the fleet journal since its last compaction snapshot"),
    "pfx_router_journal_bytes": ("gauge", "Bytes in the fleet journal file (compaction rewrites it atomically)"),
    "pfx_replica_registrations_total": ("counter", "Replica self-registration heartbeats accepted at POST /admin/register (labels: outcome=register|deregister)"),
    # SLO burn rates (telemetry.SLOTracker; labels: objective, window)
    "pfx_slo_objective": ("gauge", "Configured SLO objective value by objective label"),
    "pfx_slo_burn_rate": ("gauge", "Error-budget burn rate over a rolling window (labels: objective, window)"),
    "pfx_slo_breach": ("gauge", "1 while the labeled objective burns >threshold on every window"),
    "pfx_slo_ttft_p99_seconds": ("gauge", "Rolling short-window p99 TTFT seen by the SLO tracker"),
    # multi-tenant isolation (core/tenancy.py vocabulary; emitted by
    # core/router.py, core/continuous_batching.py, tools/serve.py.
    # Every `tenant` label is pre-folded through TenantLabelCap: the
    # first PFX_TENANT_LABEL_TOPK distinct tenants keep their name,
    # later ones share the `__other__` overflow bucket — cardinality
    # is bounded even though tenants are not)
    "pfx_tenant_admitted_total": ("counter", "Rows admitted by the weighted-fair scheduler pull (labels: tenant)"),
    "pfx_tenant_preemptions_total": ("counter", "Active rows preempted mid-decode by a higher-priority arrival and requeued as re-prefill continuations (labels: tenant = the victim's)"),
    "pfx_tenant_rejected_total": ("counter", "Router front-door admissions rejected by a tenant quota (labels: tenant, reason=rate|inflight)"),
    "pfx_tenant_in_flight": ("gauge", "Requests currently inside the router per tenant (labels: tenant)"),
    "pfx_tenant_queue_depth": ("gauge", "Entries waiting in the scheduler's admission queue per tenant (labels: tenant)"),
    "pfx_tenant_ttft_seconds": ("histogram", "Time to first token per tenant (labels: tenant)"),
    "pfx_tenant_slo_burn_rate": ("gauge", "Short-window SLO burn rate per tenant (labels: tenant, objective)"),
    # goodput ledgers (core/continuous_batching.py ContinuousScheduler,
    # core/engine.py fit loop; docs/observability.md "Goodput ledger").
    # The time buckets are exhaustive and mutually exclusive — their sum
    # closes against pfx_sched_wall_seconds_total within 1%; the token
    # dispositions close EXACTLY: admitted == delivered + evicted_lost +
    # preempt_refunded + shed_after_admit + in_flight
    "pfx_sched_time_seconds_total": ("counter", "Scheduler-thread wall seconds by attribution bucket (labels: bucket=device_decode|device_prefill|host_sched|readback|stream_flush|idle)"),
    "pfx_sched_wall_seconds_total": ("counter", "Total scheduler-thread wall seconds the time buckets must close against"),
    "pfx_sched_host_gap_seconds_total": ("counter", "Host seconds the device sat idle waiting for its next dispatch (goodput_frac subtrahend; overlaps the bucket family)"),
    # the token gap's books (core/continuous_batching.TokenGapBooks): every
    # gap between two frames of a row, booked once at the later commit
    # under what the interval between the two commits held
    "pfx_sched_token_gaps_total": ("counter", "Token gaps by what their interval held (labels: held=decode|admission|flush): one per row and commit that delivered the row a FRAME when the previous commit did too (a frame is one commit's tokens for a row: with speculation several tokens, still one gap); a row's first frame is no gap"),
    "pfx_sched_token_gap_seconds_total": ("counter", "Seconds of the token gaps by what their interval held (labels: held=decode|admission|flush): rows x (this commit's stamp - the previous one's); over pfx_sched_token_gaps_total the server's own mean token gap"),
    "pfx_sched_admit_host_seconds_total": ("counter", "Host seconds of the admission path during which the device had nothing queued: from the flush of the step in flight returning (or the iteration's start) to the dispatch of the last admission that was not queued behind a step in flight having returned; an admission behind a step in flight books 0"),
    "pfx_sched_admissions_total": ("counter", "Admissions by where their dispatch found the device (labels: path=behind_step|after_flush|idle): queued behind the step in flight without committing it, after a flush of the step in flight in the same iteration, or with nothing in flight; the sum is pfx_prefill_admits_total"),
    "pfx_sched_gap_books_errors_total": ("counter", "Faults inside the token-gap books (counted and logged once, never raised into the decode loop; 0 in a sound run)"),
    "pfx_train_time_seconds_total": ("counter", "Fit-loop wall seconds by attribution bucket (labels: bucket=compile|device_step|data_wait|host|eval)"),
    # work counted where it happens (one update per decode step / train
    # step from numbers the loop already holds): occupancy is row_steps /
    # slot_steps, the paged kernel's useful share kv_tokens / grid_tokens
    "pfx_sched_decode_steps_total": ("counter", "Decode steps committed by the continuous engine (its own step count)"),
    "pfx_sched_decode_row_steps_total": ("counter", "Live rows summed over decode steps (numerator of batch occupancy)"),
    "pfx_sched_decode_slot_steps_total": ("counter", "Batch capacity summed over decode steps (denominator of batch occupancy)"),
    "pfx_sched_decode_kv_tokens_total": ("counter", "Context tokens of the live rows summed over decode steps (what a step needed to read)"),
    "pfx_sched_decode_grid_tokens_total": ("counter", "KV tokens per head the paged kernel computed on, summed over decode steps: the context of each slot its grid visits rounded up to the kernel's grid step (the live slots alone: the grids of pfx_decode_paged, pfx_decode_window and pfx_decode_mla_paged follow the step's live list; grid steps past a row's context run nothing, and the latent kernel's work list has none of them)"),
    "pfx_sched_decode_kv_window_tokens_total": ("counter", "Tokens the window layers' calls attended, summed over decode steps: each live row's context capped at the window (a model with window layers; over pfx_sched_decode_kv_tokens_total: what the window leaves of the reading)"),
    "pfx_hc_tokens_total": ("counter", "Serving a residual stream of several copies (hc_mult): tokens that went through the maps' kernels (pfx_hc_pre / pfx_hc_post), counted ONCE a forward whatever its sub-blocks: a prefill's real prompt tokens, a decode step's live rows (warm-up excluded); times 2 sub-blocks x layers: the pairs of calls' tokens"),
    "pfx_train_host_gap_seconds_total": ("counter", "Fit-loop seconds from a blocking log fetch returning to the next step's dispatch having returned (the device has nothing queued)"),
    "pfx_moe_pairs_total": ("counter", "Token-expert pairs the dropless expert layers routed, over all experts and layers"),
    "pfx_moe_pairs_held_total": ("counter", "Routed pairs that landed on experts this process holds"),
    "pfx_moe_buffer_rows_total": ("counter", "Rows of the sorted-pair buffer each expert layer ran (the rung of the ladder its load chose), over layers and steps: pfx_moe_pairs_held_total over it is the buffers' fill"),
    "pfx_moe_load_max_over_mean_sum": ("counter", "Sum over steps of the largest held expert's pairs over the held experts' mean (max over layers)"),
    "pfx_moe_bias_abs_max": ("gauge", "Largest absolute routing bias over experts and layers"),
    "pfx_moe_serve_pairs_total": ("counter", "Serving: token-expert pairs the expert layers routed for live rows and real prompt tokens, over all experts and layers (prefills and decode steps)"),
    "pfx_moe_serve_held_pairs_total": ("counter", "Serving: routed pairs that landed on experts this process holds"),
    "pfx_moe_serve_held_max_pairs_total": ("counter", "Serving: the fullest held expert's pairs x experts held, summed over layers and dispatches (over pfx_moe_serve_held_pairs_total: max over mean)"),
    "pfx_moe_serve_grouped_calls_total": ("counter", "Serving: grouped products over sorted pairs the prefills dispatched (pfx_grouped_matmul): expert layers x matrices an expert, each admission; over pfx_prefill_admits_total: the kernel's calls a prefill"),
    "pfx_ssm_row_steps_total": ("counter", "Serving: live (row, decode step) pairs x state-space layers: the state updates the traffic needed, which are the ones the kernel visits"),
    "pfx_ssm_slot_steps_total": ("counter", "Serving: batch slots x decode steps x state-space layers: the capacity the live pairs are a share of (the kernel skips the rest)"),
    "pfx_ssm_prefill_tokens_total": ("counter", "Serving: prompt tokens x state-space layers the chunked scan of the prefills computed"),
    # slow iterations (StallWatch below; docs/observability.md "Slow
    # iterations"): absent until an iteration is slow, so a sound run's
    # share reads 0
    "pfx_stall_events_total": ("counter", "Iterations that ran far past the median of their own kind (labels: where=train.step|sched.iterate, held=compile|gc|device_wait|data_wait|host_off_cpu|host_on_cpu); each left a pfx.stall event in the flight recorder and the log"),
    "pfx_stall_seconds_total": ("counter", "Seconds the slow iterations ran PAST their kind's median (the excess, not the wall; labels as pfx_stall_events_total): over the loop's non-idle wall seconds the share of a window a stall took"),
    "pfx_token_ledger_total": ("counter", "Admitted-token dispositions (labels: disposition=admitted|delivered|evicted_lost|preempt_refunded|shed_after_admit)"),
    "pfx_token_ledger_in_flight": ("gauge", "Admitted tokens still on the books in live decode slots (the exact-closure remainder)"),
    "pfx_tenant_slot_seconds_total": ("counter", "Decode-slot occupancy in slot-seconds per tenant — billing-grade cost attribution (labels: tenant)"),
    "pfx_tenant_kv_block_seconds_total": ("counter", "KV-block occupancy in block-seconds per tenant (labels: tenant)"),
}

# latency-shaped default buckets (seconds): sub-ms to minutes, exponential-ish
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)
# reservoir per histogram child: enough for stable p50/p99 on /healthz
# without unbounded memory (the old serve.py deque was maxlen=256 too)
_RESERVOIR = 256


def _env_float(name: str, default: float, minimum: float = 0.0) -> float:
    """Loud-parse float env knob (repo convention, utils/resilience.py)."""
    raw = os.environ.get(name) or ""
    if not raw.strip():
        return default
    try:
        val = float(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not a number (loud-parse: unset it or "
            f"pass a valid value)"
        ) from None
    if val < minimum:
        raise ValueError(f"{name}={val} must be >= {minimum}")
    return val


def _env_int(name: str, default: int, minimum: int = 1) -> int:
    """Loud-parse int env knob."""
    raw = os.environ.get(name) or ""
    if not raw.strip():
        return default
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not an integer (loud-parse: unset it or "
            f"pass a valid value)"
        ) from None
    if val < minimum:
        raise ValueError(f"{name}={val} must be >= {minimum}")
    return val


# ---------------------------------------------------------------------------
# metric children
# ---------------------------------------------------------------------------


class Counter:
    """Monotonic counter.  ``set()`` exists for exporter-style cumulative
    imports (a loader's own ``data_wait_s`` total pushed as-is) and must
    only ever be called with non-decreasing values."""

    __slots__ = ("_value", "_lock")

    def __init__(self) -> None:
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, v: float = 1.0) -> None:
        with self._lock:
            self._value += v

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def get(self) -> float:
        with self._lock:
            return self._value


class Gauge(Counter):
    """Settable instantaneous value; ``add()`` for in-flight up/downs."""

    __slots__ = ()

    def add(self, v: float) -> None:
        self.inc(v)


class Histogram:
    """Cumulative-bucket histogram + a bounded reservoir for percentiles.

    Buckets render in Prometheus ``_bucket{le=...}`` form; the reservoir
    (last ``_RESERVOIR`` observations) feeds ``percentile()`` for the
    /healthz p50/p99 fields without a full-series store."""

    __slots__ = ("buckets", "_counts", "_sum", "_count", "_reservoir", "_lock")

    def __init__(self, buckets: Tuple[float, ...] = DEFAULT_BUCKETS) -> None:
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError("histogram needs at least one bucket bound")
        self._counts = [0] * (len(self.buckets) + 1)  # +Inf tail
        self._sum = 0.0
        self._count = 0
        self._reservoir: deque = deque(maxlen=_RESERVOIR)
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        idx = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self._counts[idx] += 1
            self._sum += v
            self._count += 1
            self._reservoir.append(v)

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile over the reservoir (0.0 when empty)."""
        with self._lock:
            vals = sorted(self._reservoir)
        if not vals:
            return 0.0
        idx = min(len(vals) - 1, int(round(q * (len(vals) - 1))))
        return vals[idx]

    def state(self) -> Dict[str, Any]:
        with self._lock:
            cum, total = [], 0
            for c in self._counts:
                total += c
                cum.append(total)
            vals = sorted(self._reservoir)
            sum_ = self._sum

        def pct(q: float) -> float:
            if not vals:
                return 0.0
            return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

        return {
            "buckets": list(zip(self.buckets, cum[:-1])),
            "count": cum[-1],
            "sum": sum_,
            "p50": pct(0.50),
            "p99": pct(0.99),
        }


class _Family:
    """One declared metric: kind + per-labelset children."""

    __slots__ = ("name", "kind", "help", "buckets", "children")

    def __init__(self, name: str, kind: str, help_: str, buckets=None) -> None:
        self.name = name
        self.kind = kind
        self.help = help_
        self.buckets = buckets
        self.children: Dict[Tuple[Tuple[str, str], ...], Any] = OrderedDict()


def _label_key(labels: Dict[str, str]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class Registry:
    """Process-wide metric registry.  One instance per process in
    production (``get_registry()``); tests may build private instances
    for absolute-count isolation."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._families: Dict[str, _Family] = OrderedDict()
        self._collectors: List[weakref.ref] = []

    # -- declaration-checked accessors ---------------------------------
    def _family(self, name: str, kind: str, buckets=None) -> _Family:
        declared = METRICS.get(name)
        if declared is None or declared[0] != kind:
            raise ValueError(
                f"metric {name!r} ({kind}) is not declared in "
                "telemetry.METRICS — every emitted name must be declared "
                "there (and match ^pfx_[a-z0-9_]+$; tools/lint.py E10)"
            )
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = _Family(name, kind, declared[1], buckets)
                self._families[name] = fam
            return fam

    def _child(self, name: str, kind: str, labels: Dict[str, str], buckets=None):
        fam = self._family(name, kind, buckets)
        key = _label_key(labels)
        with self._lock:
            child = fam.children.get(key)
            if child is None:
                if kind == "histogram":
                    child = Histogram(fam.buckets or DEFAULT_BUCKETS)
                elif kind == "gauge":
                    child = Gauge()
                else:
                    child = Counter()
                fam.children[key] = child
            return child

    def counter(self, name: str, **labels: str) -> Counter:
        return self._child(name, "counter", labels)

    def gauge(self, name: str, **labels: str) -> Gauge:
        return self._child(name, "gauge", labels)

    def histogram(self, name: str, buckets: Optional[Tuple[float, ...]] = None,
                  **labels: str) -> Histogram:
        return self._child(name, "histogram", labels, buckets)

    # -- collectors -----------------------------------------------------
    def register_collector(self, obj: Any) -> None:
        """Register an object with a ``collect() -> iterable of
        (metric_name, labels_dict, value)`` method.  Held by WEAK
        reference: a dead GenerationServer/RequestQueue silently drops
        out of the snapshot instead of reporting stale values forever."""
        names = {n for n, _, _ in obj.collect()}
        for n in names:
            if n not in METRICS:
                raise ValueError(
                    f"collector exports undeclared metric {n!r}; declare "
                    "it in telemetry.METRICS"
                )
        with self._lock:
            self._collectors.append(weakref.ref(obj))

    # -- snapshot + exposition -----------------------------------------
    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        """ONE consistent view of every metric: owned children plus live
        collectors, read under the registry lock.  Counters from multiple
        collectors of the same name sum (process-wide total); gauges are
        last-writer-wins.  Shape::

            {name: {"kind": ..., "help": ...,
                    "values": [(labels_dict, value)], ...}}

        histogram entries instead carry ``buckets``/``count``/``sum``/
        ``p50``/``p99`` per labelset.
        """
        snap: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            for name, fam in self._families.items():
                entry = {"kind": fam.kind, "help": fam.help, "values": []}
                for key, child in fam.children.items():
                    labels = dict(key)
                    if fam.kind == "histogram":
                        entry["values"].append((labels, child.state()))
                    else:
                        entry["values"].append((labels, child.get()))
                snap[name] = entry
            live = []
            for ref in self._collectors:
                obj = ref()
                if obj is None:
                    continue
                live.append(ref)
                for name, labels, value in obj.collect():
                    kind, help_ = METRICS[name]
                    entry = snap.setdefault(
                        name, {"kind": kind, "help": help_, "values": []}
                    )
                    labels = dict(labels or {})
                    for i, (lab, old) in enumerate(entry["values"]):
                        if lab == labels:
                            entry["values"][i] = (
                                lab,
                                old + value if kind == "counter" else value,
                            )
                            break
                    else:
                        entry["values"].append((labels, float(value)))
            self._collectors[:] = live
        return snap

    def render_prometheus(self, snap: Optional[Dict[str, Dict[str, Any]]] = None) -> str:
        """Prometheus text exposition (format 0.0.4) of a snapshot —
        pass the snapshot a ``/healthz`` view was built from to guarantee
        the two endpoints agree."""
        snap = snap if snap is not None else self.snapshot()
        lines: List[str] = []
        for name in sorted(snap):
            entry = snap[name]
            lines.append(f"# HELP {name} {entry['help']}")
            lines.append(f"# TYPE {name} {entry['kind']}")
            for labels, value in entry["values"]:
                lstr = _render_labels(labels)
                if entry["kind"] == "histogram":
                    extra = dict(labels)
                    for le, cum in value["buckets"]:
                        bl = _render_labels({**extra, "le": _fmt(le)})
                        lines.append(f"{name}_bucket{bl} {cum}")
                    bl = _render_labels({**extra, "le": "+Inf"})
                    lines.append(f"{name}_bucket{bl} {value['count']}")
                    lines.append(f"{name}_sum{lstr} {_fmt(value['sum'])}")
                    lines.append(f"{name}_count{lstr} {value['count']}")
                else:
                    lines.append(f"{name}{lstr} {_fmt(value)}")
        return "\n".join(lines) + "\n"

    def value(self, name: str, default: Any = 0.0,
              snap: Optional[Dict[str, Dict[str, Any]]] = None,
              **labels: str) -> Any:
        """Convenience read of one metric value — a counter/gauge float,
        or a histogram's state dict.  Pass ``snap`` to read out of an
        already-taken snapshot (tools/serve.py renders /healthz and
        /metrics from ONE snapshot so the endpoints agree)."""
        entry = (snap if snap is not None else self.snapshot()).get(name)
        if not entry:
            return default
        want = {str(k): str(v) for k, v in labels.items()}
        for lab, val in entry["values"]:
            if lab == want:
                return val
        return default

    def reset(self) -> None:
        """Drop every family and collector (test isolation only)."""
        with self._lock:
            self._families.clear()
            self._collectors.clear()


def _fmt(v: float) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


_SAMPLE_LINE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^}]*\})?\s+(?P<value>[^\s]+)\s*$"
)
_LABEL_PAIR_RE = re.compile(r'\s*(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>(?:[^"\\]|\\.)*)"\s*$')


def parse_exposition(text: str) -> List[Tuple[str, Dict[str, str], float]]:
    """Parse Prometheus text exposition into ``(name, labels, value)``
    sample rows, order preserved — the federation scrape's reader
    (core/router.py).  Tolerant the way a scraper must be: comment and
    blank lines skip, a malformed sample line skips (counted into the
    scrape outcome by the caller via the returned rows being fewer, not
    by raising mid-scrape), label escapes (\\\\, \\", \\n) unescape."""
    rows: List[Tuple[str, Dict[str, str], float]] = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_LINE_RE.match(line)
        if not m:
            continue
        labels: Dict[str, str] = {}
        raw = (m.group("labels") or "{}")[1:-1]
        ok = True
        for part in _split_label_pairs(raw):
            lm = _LABEL_PAIR_RE.match(part)
            if not lm:
                ok = False
                break
            # single left-to-right pass: sequential .replace calls
            # would corrupt values containing literal backslashes
            # (\\n must decode to backslash+n, not newline)
            labels[lm.group("k")] = re.sub(
                r"\\(.)",
                lambda m: {"n": "\n"}.get(m.group(1), m.group(1)),
                lm.group("v"),
            )
        if not ok:
            continue
        try:
            val = float(m.group("value").replace("+Inf", "inf")
                        .replace("Inf", "inf"))
        except ValueError:
            continue
        rows.append((m.group("name"), labels, val))
    return rows


def _split_label_pairs(raw: str) -> List[str]:
    """Split ``k="v",k2="v2"`` on commas OUTSIDE quoted values."""
    if not raw.strip():
        return []
    parts, buf, in_q, esc = [], [], False, False
    for ch in raw:
        if esc:
            buf.append(ch)
            esc = False
            continue
        if ch == "\\":
            buf.append(ch)
            esc = True
            continue
        if ch == '"':
            in_q = not in_q
            buf.append(ch)
            continue
        if ch == "," and not in_q:
            parts.append("".join(buf))
            buf = []
            continue
        buf.append(ch)
    if buf:
        parts.append("".join(buf))
    return parts


def _render_labels(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    parts = []
    for k in sorted(labels):
        v = str(labels[k]).replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")
        parts.append(f'{k}="{v}"')
    return "{" + ",".join(parts) + "}"


_registry = Registry()


def get_registry() -> Registry:
    """The process-wide default registry."""
    return _registry


# ---------------------------------------------------------------------------
# StatsView: dict-like per-instance stats exported via a collector
# ---------------------------------------------------------------------------


class StatsView:
    """Per-instance stats with the old hand-rolled-dict interface
    (``stats["requests"] += 1``, ``dict(stats)``, ``**stats``) whose
    numeric keys are ALSO exported onto the registry.

    ``exported`` maps dict key -> declared metric name; keys mapped to
    ``None`` (and any key assigned later, e.g. ``warmup_s``/``last_error``)
    stay instance-local.  The registry holds only a weak reference, so a
    test-scoped server's counters vanish with it."""

    def __init__(
        self,
        exported: Dict[str, Optional[str]],
        init: Optional[Dict[str, Any]] = None,
        registry: Optional[Registry] = None,
    ) -> None:
        self._exported = dict(exported)
        self._lock = threading.Lock()
        self._vals: Dict[str, Any] = {k: 0 for k in exported}
        if init:
            self._vals.update(init)
        (registry or get_registry()).register_collector(self)

    # collector protocol
    def collect(self) -> List[Tuple[str, Dict[str, str], float]]:
        with self._lock:
            return [
                (metric, {}, float(self._vals[key]))
                for key, metric in self._exported.items()
                if metric is not None
                and isinstance(self._vals.get(key), (int, float))
                and not isinstance(self._vals.get(key), bool)
            ]

    # mapping protocol (enough for dict(view), **view, view.items())
    def __getitem__(self, key: str) -> Any:
        with self._lock:
            return self._vals[key]

    def __setitem__(self, key: str, value: Any) -> None:
        with self._lock:
            self._vals[key] = value

    def get(self, key: str, default: Any = None) -> Any:
        with self._lock:
            return self._vals.get(key, default)

    def keys(self):
        with self._lock:
            return list(self._vals.keys())

    def items(self):
        with self._lock:
            return list(self._vals.items())

    def values(self):
        with self._lock:
            return list(self._vals.values())

    def __iter__(self):
        return iter(self.keys())

    def __len__(self) -> int:
        with self._lock:
            return len(self._vals)

    def __contains__(self, key: str) -> bool:
        with self._lock:
            return key in self._vals

    def __repr__(self) -> str:
        return f"StatsView({dict(self.items())!r})"


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class Span:
    """Monotonic-clock phase timing: consecutive ``mark()`` calls define
    phases.  Callers may inject timestamps captured elsewhere (the request
    queue stamps pickup/resolve under its own lock) via ``mark(label, t=)``;
    marks are kept time-ordered so injected stamps slot in correctly."""

    __slots__ = ("name", "marks")

    def __init__(self, name: str, t0: Optional[float] = None) -> None:
        self.name = name
        self.marks: List[Tuple[str, float]] = [
            ("start", time.monotonic() if t0 is None else float(t0))
        ]

    def mark(self, label: str, t: Optional[float] = None) -> None:
        self.marks.append((label, time.monotonic() if t is None else float(t)))
        self.marks.sort(key=lambda m: m[1])

    def phases(self) -> "OrderedDict[str, float]":
        """label -> seconds since the previous mark (phase ENDING at the
        label), insertion-ordered by time."""
        out: "OrderedDict[str, float]" = OrderedDict()
        for (_, t_prev), (label, t) in zip(self.marks, self.marks[1:]):
            out[label] = out.get(label, 0.0) + (t - t_prev)
        return out

    def total(self) -> float:
        return self.marks[-1][1] - self.marks[0][1]

    def event(self, **extra: Any) -> Dict[str, Any]:
        """Shape this span as a flight-recorder event."""
        return {
            "event": "span",
            "span": self.name,
            "total_s": round(self.total(), 6),
            "phases": {k: round(v, 6) for k, v in self.phases().items()},
            **extra,
        }


_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, imported on first use


class ledger_span:
    """One ``with`` = one host span on the profiler's clock AND the same
    two ``time.monotonic()`` stamps added to a ledger bucket, so a span in
    the device trace and the bucket it explains cannot drift apart::

        with ledger_span("pfx.sched.readback", self.stats, "t_readback"):
            window = np.array(fl["window"])

    ``ledger[key]`` grows by the span's duration even when the body
    raises.  ``ledger=None`` only annotates and times (``seconds``,
    ``t0``, ``t1`` stay readable after the block).  ``args`` land in the
    trace event's stats.  While no profiler session is open the
    annotation is a no-op of about a microsecond; the span names are
    listed in docs/observability.md "Goodput ledger"."""

    __slots__ = ("_ann", "_ledger", "_key", "t0", "t1")

    def __init__(self, name: str, ledger=None, key: Optional[str] = None,
                 **args: Any) -> None:
        global _TRACE_ANNOTATION
        if _TRACE_ANNOTATION is None:
            from jax.profiler import TraceAnnotation

            _TRACE_ANNOTATION = TraceAnnotation
        self._ann = _TRACE_ANNOTATION(name, **args)
        self._ledger, self._key = ledger, key
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "ledger_span":
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.monotonic()
        if self._ledger is not None:
            self._ledger[self._key] += self.t1 - self.t0
        self._ann.__exit__(*exc)
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


# ---------------------------------------------------------------------------
# slow iterations (docs/observability.md "Slow iterations")
# ---------------------------------------------------------------------------

# THE RULE's two constants: an iteration is slow when it ran past the
# median of its kind's last STALL_WINDOW wall times by at least
# max(STALL_MIN_EXCESS_S, STALL_MEDIAN_SHARE x median).  Settled on the
# chip (PERF.md section 6, PR 53): no event in a sound window of the
# cells, one for every injected 0.25 s.  At 0.1 s sound windows of the
# mellum and dsv3 cells held two or three iterations of 0.115-0.181 s
# more (collector pauses, a blocked dispatch, a long readback), and at a
# share of 0.5 a 0.6 s train step would hide 0.25 s.
STALL_MIN_EXCESS_S = 0.2
STALL_MEDIAN_SHARE = 0.25
STALL_WINDOW = 64
# the median is recomputed every so many observations of a kind, and a
# kind with fewer judges nothing (the warm-up's compiles come first)
STALL_JUDGE_EVERY = 16
# the machine's counters are read against a baseline at most this old
STALL_BASELINE_S = 1.0
STALL_HELD = ("compile", "gc", "device_wait", "data_wait",
              "host_off_cpu", "host_on_cpu")

_PROC = "/proc"  # tests point it at a directory that is not there

# process-wide sums a slow iteration is read against: seconds and count of
# garbage-collector pauses (one gc.callbacks hook, whatever thread
# collects), and the compile watcher's events
_gc_pauses = [0.0, 0, 0.0]  # seconds, collections, start of the one running
_compile_events = [0]


def _gc_hook(phase: str, info: Dict[str, Any]) -> None:
    if phase == "start":
        _gc_pauses[2] = time.perf_counter()
    else:
        _gc_pauses[0] += time.perf_counter() - _gc_pauses[2]
        _gc_pauses[1] += 1


def count_compile_event() -> None:
    """``CompileWatcher`` calls this once an event, beside its counters."""
    _compile_events[0] += 1


def _machine_counters() -> Dict[str, float]:
    """What the machine did to the calling thread so far, each key absent
    where its source is: seconds the thread waited on a run queue, the
    machine's steal and iowait seconds, the thread's involuntary context
    switches and major faults."""
    out: Dict[str, float] = {}
    try:
        with open(os.path.join(_PROC, "thread-self", "schedstat")) as f:
            out["runq_wait_s"] = int(f.read().split()[1]) / 1e9
    except (OSError, IndexError, ValueError):
        pass
    try:
        with open(os.path.join(_PROC, "stat")) as f:
            cpu = f.readline().split()
        tick = float(os.sysconf("SC_CLK_TCK"))
        out["iowait_s"] = int(cpu[5]) / tick
        out["steal_s"] = int(cpu[8]) / tick
    except (OSError, IndexError, ValueError):
        pass
    try:
        import resource

        ru = resource.getrusage(resource.RUSAGE_THREAD)
        out["invol_ctx_switches"] = ru.ru_nivcsw
        out["major_faults"] = ru.ru_majflt
    except (ImportError, AttributeError, OSError, ValueError):
        pass
    return out


def stall_held(*, excess_s: float, compile_events: int, gc_s: float,
               bucket: str, thread_cpu_grew_s: float,
               device_wait: Tuple[str, ...] = (),
               data_wait: Tuple[str, ...] = ()) -> str:
    """ONE label for what held a slow iteration, first match wins:
    ``compile`` (a compile event in the interval), ``gc`` (collector
    pauses of at least half the excess), ``device_wait`` / ``data_wait``
    (``bucket``, the one that grew most over the kind's last sound
    iteration, blocks on the device / on the loader), ``host_off_cpu``
    (a host bucket, and the thread's CPU clock grew by less than half the
    excess: descheduled or blocked), ``host_on_cpu`` (the thread worked)."""
    if compile_events > 0:
        return "compile"
    if gc_s >= 0.5 * excess_s:
        return "gc"
    if bucket in device_wait:
        return "device_wait"
    if bucket in data_wait:
        return "data_wait"
    if thread_cpu_grew_s < 0.5 * excess_s:
        return "host_off_cpu"
    return "host_on_cpu"


class _StallKind:
    """One kind's last wall times, their median and the limit it sets."""

    __slots__ = ("walls", "n", "median", "limit", "ref")

    def __init__(self) -> None:
        self.walls: deque = deque(maxlen=STALL_WINDOW)
        self.n = 0
        self.median: Optional[float] = None
        self.limit = 0.0
        # thread CPU seconds and bucket seconds of the last sound one
        self.ref: Tuple[float, Tuple[float, ...]] = (0.0, ())


class StallWatch:
    """The slow-iteration watcher of ONE loop on ONE thread (the fit loop,
    the scheduler).  The loop hands :meth:`observe` every iteration's two
    ``time.monotonic()`` stamps (the ones its ``ledger_span`` took) and
    this iteration's own seconds by bucket; an iteration that ran past
    its kind's median by ``max(STALL_MIN_EXCESS_S, STALL_MEDIAN_SHARE x
    median)`` comes back as an event for the loop to complete with its
    step number and counts and :meth:`publish`::

        ev = watch.observe("decode", wall.t0, wall.t1, (dd, dp, rb, sf, hs))
        if ev is not None:
            watch.publish(ev, iter=n, active=rows)

    A sound iteration costs the two CPU clocks, a deque append and a
    comparison; the machine's counters are read when one is slow, against
    a baseline refreshed at most once a second.  ``buckets`` names the
    seconds in order; ``device_wait`` / ``data_wait`` say which of them
    block on the device / on the loader (the rule of ``held``)."""

    def __init__(self, where: str, buckets: Tuple[str, ...], *,
                 device_wait: Tuple[str, ...] = (),
                 data_wait: Tuple[str, ...] = ()) -> None:
        if _gc_hook not in gc.callbacks:
            gc.callbacks.append(_gc_hook)
        self.where = where
        self.buckets = tuple(buckets)
        self.device_wait, self.data_wait = tuple(device_wait), tuple(data_wait)
        self._kinds: Dict[str, _StallKind] = {}
        self.events = 0
        self.seconds = 0.0  # the events' excess, summed
        self.last: deque = deque(maxlen=8)
        self._base_t = float("-inf")  # the first sound iteration takes one
        self._base: Dict[str, float] = {}
        self.stamp()

    def stamp(self) -> None:
        """The next interval starts here: the loop's first iteration, or
        the scheduler's first after a parked wait."""
        self._cpu, self._proc = time.thread_time(), time.process_time()
        # collector seconds, collections, compile events as they stood
        self._seen = (_gc_pauses[0], _gc_pauses[1], _compile_events[0])

    def observe(self, kind: str, t0: float, t1: float,
                buckets: Tuple[float, ...]) -> Optional[Dict[str, Any]]:
        cpu, proc = time.thread_time(), time.process_time()
        thread_cpu_s, process_cpu_s = cpu - self._cpu, proc - self._proc
        self._cpu, self._proc = cpu, proc
        seen, self._seen = self._seen, (
            _gc_pauses[0], _gc_pauses[1], _compile_events[0])
        wall_s = t1 - t0
        st = self._kinds.get(kind)
        if st is None:
            st = self._kinds[kind] = _StallKind()
        st.walls.append(wall_s)
        st.n += 1
        if not st.n % STALL_JUDGE_EVERY:
            st.median = statistics.median(st.walls)
            st.limit = max(STALL_MIN_EXCESS_S, STALL_MEDIAN_SHARE * st.median)
        if st.median is None or wall_s - st.median < st.limit:
            st.ref = (thread_cpu_s, buckets)
            if t1 - self._base_t >= STALL_BASELINE_S:
                self._base_t, self._base = t1, _machine_counters()
            return None
        return self._slow(kind, st, t0, t1, thread_cpu_s, process_cpu_s,
                          buckets, seen)

    def _slow(self, kind: str, st: _StallKind, t0: float, t1: float,
              thread_cpu_s: float, process_cpu_s: float,
              buckets: Tuple[float, ...],
              seen: Tuple[float, int, int]) -> Dict[str, Any]:
        excess = (t1 - t0) - st.median
        gc_s, gc_n, compiles = (now - was for now, was in zip(self._seen, seen))
        # st.ref is set: a kind's first STALL_JUDGE_EVERY - 1 are sound
        ref_cpu, ref_buckets = st.ref
        grew = [b - r for b, r in zip(buckets, ref_buckets)]
        bucket = self.buckets[grew.index(max(grew))]
        ev: Dict[str, Any] = {
            "event": "pfx.stall",
            "where": self.where,
            "kind": kind,
            "held": stall_held(
                excess_s=excess, compile_events=compiles, gc_s=gc_s,
                bucket=bucket, thread_cpu_grew_s=thread_cpu_s - ref_cpu,
                device_wait=self.device_wait, data_wait=self.data_wait),
            "t0_monotonic_ns": int(t0 * 1e9),
            "t1_monotonic_ns": int(t1 * 1e9),
            # the wall clock at t1, as a trace start records both
            "time_ns": time.time_ns() - (time.monotonic_ns() - int(t1 * 1e9)),
            "wall_s": round(t1 - t0, 6),
            "median_s": round(st.median, 6),
            "excess_s": round(excess, 6),
            "thread_cpu_s": round(thread_cpu_s, 6),
            "process_cpu_s": round(process_cpu_s, 6),
            "buckets": {n: round(v, 6) for n, v in zip(self.buckets, buckets)},
            "grew_most": bucket,
            "gc_s": round(gc_s, 6),
            "gc_collections": gc_n,
            "compile_events": compiles,
        }
        # the machine since the baseline (at most STALL_BASELINE_S before
        # the iteration began): a superset of the interval, said beside it
        now = _machine_counters()
        machine = {k: round(now[k] - self._base[k], 6)
                   for k in now if k in self._base}
        machine["since_s"] = round(t1 - self._base_t, 6)
        try:
            machine["loadavg_1m"] = os.getloadavg()[0]
        except OSError:
            pass
        ev["machine"] = machine
        self._base_t, self._base = t1, now
        return ev

    def publish(self, ev: Dict[str, Any], **fields: Any) -> None:
        """Complete the event with the loop's own fields (``iter`` /
        ``step``: the number the open span carries; its counts) and send
        it where events go: the flight recorder's ring, one
        ``pfx.stall {json}`` log line, the two counter families, and this
        watcher's own sums (the step records' ``stall_s`` /
        ``stall_events``, ``/debug/state``'s ``stalls``)."""
        ev.update(fields)
        self.events += 1
        self.seconds += ev["excess_s"]
        self.last.append(ev)
        get_flight_recorder().record(ev)
        logger.warning("pfx.stall " + json.dumps(ev, default=str))
        reg = get_registry()
        labels = {"where": self.where, "held": ev["held"]}
        reg.counter("pfx_stall_events_total", **labels).inc()
        reg.counter("pfx_stall_seconds_total", **labels).inc(ev["excess_s"])

    def summary(self) -> Dict[str, Any]:
        return {"events": self.events, "seconds": round(self.seconds, 6),
                "last": list(self.last)}


# ---------------------------------------------------------------------------
# MFU accounting
# ---------------------------------------------------------------------------

# per-chip dense bf16 peak FLOP/s by device kind substring (lowercased
# containment match against jax's device_kind).  The cpu entry is a NOMINAL
# 1 TFLOP/s so CPU smoke runs still produce a finite, comparable-over-time
# mfu column — it is not a hardware claim (records carry the platform).
PEAK_FLOPS_BY_DEVICE_KIND: Dict[str, float] = {
    "v6e": 918e12,
    "v6 lite": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v4": 275e12,
    "cpu": 1e12,
}


def gpt_param_count(
    *,
    vocab_size: int,
    hidden_size: int,
    num_layers: int,
    ffn_hidden_size: Optional[int] = None,
) -> int:
    """Analytic matmul-bearing parameter count N for a GPT-family stack:
    tied token embedding/LM head counted once, per-layer fused-QKV +
    output projection + 2-matmul MLP with biases, 2 LayerNorms per layer
    plus the final one.  Position embeddings are excluded (lookup, not
    matmul) — this is the N in the 6·N·T FLOPs convention."""
    h = int(hidden_size)
    ffn = int(ffn_hidden_size or 4 * h)
    per_layer = (
        (3 * h * h + 3 * h)      # fused qkv
        + (h * h + h)            # attention output projection
        + (h * ffn + ffn)        # mlp up
        + (ffn * h + h)          # mlp down
        + 4 * h                  # 2 LayerNorms (scale + bias)
    )
    return int(vocab_size) * h + int(num_layers) * per_layer + 2 * h


def model_flops_per_token(config: Any = None, *, backward: bool = True,
                          **fields: int) -> Optional[float]:
    """Model FLOPs per token for a GPT-family config: ``6·N`` for a
    training step (1 fwd + 2 bwd matmul passes, PaLM's MFU convention —
    no remat extra, attention-score FLOPs excluded) or ``2·N`` forward-
    only (``backward=False``, the decode/serving basis).

    Accepts a config object carrying ``vocab_size``/``hidden_size``/
    ``num_layers`` (``ffn_hidden_size`` optional) or the same as kwargs;
    returns None when the fields are missing — non-GPT modules (ViT,
    protein) simply get no MFU column rather than a wrong one."""
    def grab(name):
        if name in fields:
            return fields[name]
        return getattr(config, name, None)

    vocab, hidden, layers = (
        grab("vocab_size"), grab("hidden_size"), grab("num_layers")
    )
    if not vocab or not hidden or not layers:
        return None
    n = gpt_param_count(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        ffn_hidden_size=grab("ffn_hidden_size"),
    )
    return float((6 if backward else 2) * n)


def detect_device_kind() -> str:
    """The backend's device_kind string ('TPU v5e', 'cpu', ...); 'unknown'
    when no backend is reachable.  Lazy jax import: callers that never ask
    for a peak (bench parent, lint) stay jax-free."""
    try:
        import jax

        return str(jax.devices()[0].device_kind)
    except Exception:  # noqa: BLE001 — no backend is a valid state here
        return "unknown"


def peak_flops(default: Optional[float] = None,
               device_kind: Optional[str] = None) -> Optional[float]:
    """Per-chip peak FLOP/s for the MFU denominator.

    Resolution order: ``PFX_PEAK_FLOPS`` env (loud-parse, > 0) ->
    ``PEAK_FLOPS_BY_DEVICE_KIND`` by detected device kind -> ``default``
    (None = caller omits MFU rather than fabricating one)."""
    env = _env_float("PFX_PEAK_FLOPS", 0.0)
    if env > 0.0:
        return env
    kind = (device_kind if device_kind is not None else detect_device_kind()).lower()
    for sub, peak in PEAK_FLOPS_BY_DEVICE_KIND.items():
        if sub in kind:
            return peak
    if default is not None:
        return float(default)
    logger.warning(
        f"peak_flops: unknown device kind {kind!r} and no PFX_PEAK_FLOPS "
        "set; MFU unavailable"
    )
    return None


def mfu(tokens_per_sec: float, flops_per_token: float, n_devices: int,
        peak: Optional[float] = None) -> Optional[float]:
    """Model FLOPs utilization: achieved model FLOP/s over the fleet's
    aggregate peak.  None when no peak is resolvable."""
    peak = peak if peak is not None else peak_flops()
    if not peak or n_devices < 1:
        return None
    return tokens_per_sec * flops_per_token / (peak * n_devices)


# ---------------------------------------------------------------------------
# SLO burn rates
# ---------------------------------------------------------------------------


class SLOTracker:
    """Rolling multi-window burn-rate evaluation of serving SLOs
    (docs/observability.md), Google-SRE style: an objective grants an
    error budget (p99 TTFT <= X allows 1% of requests over X; error
    rate <= Y allows a Y fraction of failures), and the *burn rate* is
    how many times faster than sustainable the current window spends
    it.  Breach = every window burning past ``burn_threshold`` — the
    short window makes the flag flip fast, the long window keeps a
    single slow request from paging anyone.

    ``observe_request`` ingests one served request (called by
    ``tools/serve.py`` per response — the HTTP layer, never the decode
    hot path); ``evaluate`` returns the operator view ``/healthz``
    embeds as its ``slo`` block; ``collect`` exports the same numbers
    as ``pfx_slo_*`` gauges for ``/metrics`` (register the tracker as
    a registry collector).  Explicit ``t``/``now`` injection keeps the
    unit tests wall-clock-free."""

    def __init__(self, *, ttft_p99_s: float = 0.0, error_rate: float = 0.0,
                 windows_s=(60.0, 600.0), burn_threshold: float = 1.0,
                 cap: int = 131072, tenant_label_fn=None) -> None:
        if ttft_p99_s < 0 or error_rate < 0:
            raise ValueError("SLO objectives must be >= 0 (0 disables)")
        ws = tuple(float(w) for w in windows_s)
        if len(ws) < 1 or any(w <= 0 for w in ws):
            raise ValueError(f"SLO windows must be positive, got {windows_s}")
        self.ttft_p99_s = float(ttft_p99_s)
        self.error_rate = float(error_rate)
        self.windows_s = tuple(sorted(ws))
        self.burn_threshold = float(burn_threshold)
        # time-pruned on observe (events older than the LONG window drop
        # off), so the long window is not silently truncated by a count
        # bound under load; ``cap`` is a memory backstop (default bites
        # at ~218 rps sustained over a 600s window) that WARNS when it
        # evicts a still-in-window event — the long-window burn is then
        # computed over less history than configured
        self.cap = int(cap)
        self._cap_warned = False
        self._events: deque = deque()
        self._lock = threading.Lock()
        self._memo: Optional[Tuple[float, Dict[str, Any]]] = None
        # per-tenant burn: events carry a pre-folded tenant label.  The
        # fold fn is injected (tools/serve.py shares ONE TenantLabelCap
        # across SLO/metrics/debug surfaces); when absent, a private
        # cap is built lazily on the first labeled observation so the
        # gauge cardinality is bounded either way
        self._tenant_label_fn = tenant_label_fn

    @property
    def enabled(self) -> bool:
        return self.ttft_p99_s > 0.0 or self.error_rate > 0.0

    def _tenant_label(self, tenant: str) -> str:
        if self._tenant_label_fn is None:
            from paddlefleetx_tpu.core.tenancy import TenantLabelCap
            self._tenant_label_fn = TenantLabelCap().label
        return self._tenant_label_fn(tenant)

    def observe_request(self, *, ttft_s: Optional[float] = None,
                        ok: bool = True, t: Optional[float] = None,
                        tenant: Optional[str] = None) -> None:
        """One served request: ``ok`` means the server answered within
        contract (200); a shed/error (500, 503, 429) is budget spend.
        ``ttft_s`` is set only for requests that delivered tokens — a
        failed request (no first token ever) counts as a TTFT violation
        in :meth:`evaluate`, not as a missing sample.  ``tenant`` (when
        set) joins the event pre-folded through the label cap and feeds
        the per-tenant short-window burn gauges."""
        if not self.enabled:
            return
        now = time.monotonic() if t is None else float(t)
        horizon = self.windows_s[-1]
        label = None if tenant is None else self._tenant_label(tenant)
        with self._lock:
            self._events.append((
                now,
                None if ttft_s is None else float(ttft_s),
                bool(ok),
                label,
            ))
            while self._events and self._events[0][0] < now - horizon:
                self._events.popleft()
            truncated = False
            while len(self._events) > self.cap:
                self._events.popleft()
                truncated = True
            if truncated and not self._cap_warned:
                self._cap_warned = True
                logger.warning(
                    f"SLOTracker: event cap {self.cap} evicted events "
                    f"still inside the {horizon:g}s window — long-window "
                    "burn rates now cover less history than configured "
                    "(sustained rps exceeds cap/window; raise cap= or "
                    "shorten --slo-windows)"
                )

    @staticmethod
    def _window_name(w: float) -> str:
        return f"{w:g}s"

    def evaluate(self, now: Optional[float] = None) -> Dict[str, Any]:
        """The ``/healthz`` ``slo`` block: per-objective burn rates per
        window, the breach flag (+ per-objective ``breached`` map), and
        a human reason naming the burning objective.  Empty windows burn
        0 (a quiesced server recovers).  Live calls (``now=None``) are
        memoized for 0.2s: one /healthz request evaluates once even
        though both the registry collector and the JSON block read it —
        at the event cap a double evaluation is ~1.5M tuple scans."""
        if now is None:
            live = time.monotonic()
            memo = self._memo
            if memo is not None and live - memo[0] < 0.2:
                return memo[1]
            out = self.evaluate(now=live)
            self._memo = (live, out)
            return out
        now = float(now)
        with self._lock:
            events = list(self._events)
        out: Dict[str, Any] = {
            "enabled": self.enabled,
            "windows_s": list(self.windows_s),
            "burn_threshold": self.burn_threshold,
            "objectives": {},
            "burn": {},
            "breached": {},
            "breach": False,
            "reason": None,
        }
        if not self.enabled:
            return out
        reasons = []
        short = self.windows_s[0]
        if self.ttft_p99_s > 0:
            out["objectives"]["ttft_p99"] = self.ttft_p99_s
            burns = {}
            for w in self.windows_s:
                win = [e for e in events if e[0] >= now - w]
                ttfts = [e[1] for e in win if e[1] is not None]
                # a FAILED request (shed/error: no first token, ever) is
                # a TTFT violation, not a missing sample — otherwise a
                # fully wedged server, where every request 503s, would
                # report zero TTFT burn exactly when TTFT is worst
                failed = sum(1 for e in win if e[1] is None and not e[2])
                total = len(ttfts) + failed
                bad = sum(1 for v in ttfts if v > self.ttft_p99_s) + failed
                frac = bad / total if total else 0.0
                # p99 objective => 1% error budget
                burns[self._window_name(w)] = round(frac / 0.01, 3)
            out["burn"]["ttft_p99"] = burns
            # observed p99 over DELIVERED requests only (failures have
            # no finite TTFT; they show up in the burn rate above, and
            # an inf here would break strict Prometheus rendering)
            short_ttfts = sorted(
                e[1] for e in events
                if e[0] >= now - short and e[1] is not None
            )
            out["ttft_p99_s"] = (
                short_ttfts[min(len(short_ttfts) - 1,
                                int(round(0.99 * (len(short_ttfts) - 1))))]
                if short_ttfts else 0.0
            )
            breached = all(b > self.burn_threshold for b in burns.values())
            out["breached"]["ttft_p99"] = breached
            if breached:
                reasons.append(
                    f"ttft_p99: burn {'/'.join(str(b) for b in burns.values())}"
                    f"x over the {self.ttft_p99_s:g}s objective"
                )
        if self.error_rate > 0:
            out["objectives"]["error_rate"] = self.error_rate
            burns = {}
            for w in self.windows_s:
                evs = [e for e in events if e[0] >= now - w]
                bad = sum(1 for e in evs if not e[2])
                frac = bad / len(evs) if evs else 0.0
                burns[self._window_name(w)] = round(frac / self.error_rate, 3)
            out["burn"]["error_rate"] = burns
            breached = all(b > self.burn_threshold for b in burns.values())
            out["breached"]["error_rate"] = breached
            if breached:
                reasons.append(
                    f"error_rate: burn "
                    f"{'/'.join(str(b) for b in burns.values())}x over the "
                    f"{self.error_rate:g} objective"
                )
        # per-tenant short-window burn (labels arrive pre-folded through
        # the TenantLabelCap, so this block is bounded at top-k + 1
        # tenants no matter how many distinct callers exist)
        tenant_labels = sorted({e[3] for e in events if len(e) > 3 and e[3]})
        if tenant_labels:
            short_t0 = now - short
            tview: Dict[str, Any] = {}
            for tn in tenant_labels:
                tev = [e for e in events
                       if len(e) > 3 and e[3] == tn and e[0] >= short_t0]
                row: Dict[str, Any] = {"requests": len(tev)}
                if self.ttft_p99_s > 0:
                    ttfts = [e[1] for e in tev if e[1] is not None]
                    failed = sum(1 for e in tev if e[1] is None and not e[2])
                    total = len(ttfts) + failed
                    bad = sum(1 for v in ttfts if v > self.ttft_p99_s) + failed
                    row["ttft_p99"] = round(
                        (bad / total if total else 0.0) / 0.01, 3
                    )
                if self.error_rate > 0:
                    bad = sum(1 for e in tev if not e[2])
                    row["error_rate"] = round(
                        (bad / len(tev) if tev else 0.0) / self.error_rate, 3
                    )
                tview[tn] = row
            out["tenants"] = tview
        if reasons:
            out["breach"] = True
            out["reason"] = "; ".join(reasons)
        return out

    def collect(self):
        """Registry-collector protocol: the evaluate() numbers as
        ``pfx_slo_*`` gauges (labels: objective, window)."""
        ev = self.evaluate()
        rows = []
        for obj, target in ev["objectives"].items():
            rows.append(("pfx_slo_objective", {"objective": obj}, target))
        for obj, burns in ev["burn"].items():
            for window, burn in burns.items():
                rows.append((
                    "pfx_slo_burn_rate",
                    {"objective": obj, "window": window},
                    burn,
                ))
            rows.append((
                "pfx_slo_breach", {"objective": obj},
                # the structured per-objective flag, NOT a substring
                # match on the human reason text (rewording the message
                # must never zero the gauge)
                1.0 if ev["breached"].get(obj) else 0.0,
            ))
        if "ttft_p99_s" in ev:
            rows.append(("pfx_slo_ttft_p99_seconds", {}, ev["ttft_p99_s"]))
        for tn, row in ev.get("tenants", {}).items():
            for obj in ("ttft_p99", "error_rate"):
                if obj in row:
                    rows.append((
                        "pfx_tenant_slo_burn_rate",
                        {"tenant": tn, "objective": obj},
                        row[obj],
                    ))
        return rows


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

DEFAULT_FLIGHT_DIR = "artifacts"


def flight_dir() -> str:
    """Directory for operational artifacts (flight-recorder dumps, trace
    exports): ``PFX_FLIGHT_DIR``, default ``./artifacts/`` — dumps used
    to land in the process cwd and pollute the repo root."""
    return os.environ.get("PFX_FLIGHT_DIR") or DEFAULT_FLIGHT_DIR


def atomic_artifact_write(path: str, write_fn) -> bool:
    """THE crash-path artifact-write recipe, shared by the flight
    recorder and the trace exporter: makedirs + pid-unique tmp +
    ``os.replace``.  The pid-unique tmp matters on multi-host shared
    storage — a preemption fans a dump out to every process, and each
    must publish whole files only (last writer wins, never a torn
    interleave).  Returns False on OSError (logged, never raised: this
    runs inside crash handlers where a secondary failure must not mask
    the primary); ``write_fn(f)`` does the actual writing."""
    try:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            write_fn(f)
        os.replace(tmp, path)
    except OSError as e:
        logger.warning(f"artifact write to {path} failed: {e}")
        return False
    return True


class FlightRecorder:
    """Bounded ring of recent structured events, dumped as JSONL on the
    bad-day paths (crash, force-quit, watchdog-degraded, rollback).

    ``record()`` is cheap (deque append under a lock) so hot-ish paths —
    step records, request spans — can feed it unconditionally; ``dump()``
    writes atomically (tmp + os.replace) and never raises: it runs inside
    crash handlers where a secondary failure must not mask the primary."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        cap = capacity if capacity is not None else _env_int(
            "PFX_FLIGHT_RECORDER_CAP", 256
        )
        self._events: deque = deque(maxlen=cap)
        self._lock = threading.Lock()
        self._seq = 0
        self._hook_installed = False

    def record(self, event: Dict[str, Any]) -> None:
        with self._lock:
            self._seq += 1
            self._events.append({"seq": self._seq, "ts": time.time(), **event})

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def dump(self, path: Optional[str] = None, reason: str = "") -> Optional[str]:
        """Write the ring to JSONL (newest last) under a dump header.
        Path resolution: ``PFX_FLIGHT_RECORDER`` env first (the operator's
        word wins even over an explicit caller path), then the caller's
        ``path`` (the engine passes its checkpoint ``output_dir``), then
        ``<PFX_FLIGHT_DIR>/flight_recorder.jsonl`` (default
        ``./artifacts/`` — dumps no longer litter the process cwd).
        Returns the path, or None when the write failed (logged, never
        raised — this runs on crash paths)."""
        path = (
            os.environ.get("PFX_FLIGHT_RECORDER") or path
            or os.path.join(flight_dir(), "flight_recorder.jsonl")
        )
        events = self.events()
        header = {
            "event": "flight_recorder_dump",
            "reason": reason,
            "ts": time.time(),
            "pid": os.getpid(),
            "events": len(events),
        }
        def write(f):
            f.write(json.dumps(header) + "\n")
            for ev in events:
                f.write(json.dumps(ev, default=str) + "\n")

        if not atomic_artifact_write(path, write):
            return None
        logger.warning(
            f"flight recorder: {len(events)} event(s) dumped to {path}"
            + (f" ({reason})" if reason else "")
        )
        return path

    def install_excepthook(self, path: Optional[str] = None) -> None:
        """Chain onto sys.excepthook AND threading.excepthook: an
        uncaught exception — main thread or not — dumps the ring (reason
        names the exception) before the normal traceback prints.
        sys.excepthook alone never fires for worker threads, and the
        serving process does its real work in them (scheduler, watchdog,
        HTTP handlers); a watchdog thread dying silently would otherwise
        leave no postmortem AND no degraded-detection.  ``path`` sets the
        dump target (tools/train.py passes its checkpoint output_dir;
        PFX_FLIGHT_RECORDER still wins).  Idempotent per recorder."""
        if self._hook_installed:
            return
        self._hook_installed = True
        prior = sys.excepthook

        def hook(exc_type, exc, tb):
            try:
                self.record({
                    "event": "crash",
                    "error": f"{exc_type.__name__}: {exc}",
                })
                self.dump(path=path, reason=f"uncaught {exc_type.__name__}")
            finally:
                prior(exc_type, exc, tb)

        sys.excepthook = hook
        prior_thread = threading.excepthook

        def thread_hook(args):
            try:
                name = args.thread.name if args.thread else "?"
                self.record({
                    "event": "crash",
                    "thread": name,
                    "error": f"{args.exc_type.__name__}: {args.exc_value}",
                })
                self.dump(
                    path=path,
                    reason=f"uncaught {args.exc_type.__name__} "
                           f"in thread {name}",
                )
            finally:
                prior_thread(args)

        threading.excepthook = thread_hook


_flight = FlightRecorder()


def get_flight_recorder() -> FlightRecorder:
    """The process-wide flight recorder."""
    return _flight
