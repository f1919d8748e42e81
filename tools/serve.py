"""Generation serving CLI: stdin REPL or a minimal HTTP JSON endpoint.

TPU-native counterpart of the reference's deploy path (InferenceEngine
multi-rank predictor + projects/gpt/inference scripts): one process per
host, TP over the serving mesh, bucketed prompts so repeat traffic reuses
compiled decode artifacts (`core/serving.py`).

The HTTP path runs on an admission-controlled request queue
(`core/request_queue.py`): bounded depth (full -> 429 + Retry-After),
per-request deadlines (expired -> 503 before a decode is wasted), a
single scheduler thread that coalesces compatible waiting requests into
one batched decode riding the existing compile buckets, SIGTERM/SIGINT
graceful drain (stop admitting -> answer all admitted work -> exit 0;
second signal force-quits), and a wedged-generation watchdog that flips
`/healthz` to degraded.  Operations runbook: docs/serving.md.

Observability (docs/observability.md): every counter rides the unified
telemetry registry (`utils/telemetry.py`); `GET /metrics` renders it as
Prometheus text exposition and `/healthz` renders the SAME locked
snapshot as operator JSON — the two can never disagree.  Each request's
lifecycle (admission -> queue_wait -> decode -> respond) is recorded as
a span feeding TTFT / per-token-latency histograms and the crash flight
recorder, which dumps its postmortem under PFX_FLIGHT_DIR (default
./artifacts/; PFX_FLIGHT_RECORDER overrides the exact path) on
watchdog-degraded, force-quit, and uncaught crashes.

Deep-dive layer (`utils/tracing.py`): sampled per-request trace
timelines (`PFX_TRACE_SAMPLE`/`PFX_TRACE_CAP`; 200 responses carry
`trace_id`), the continuous scheduler's per-iteration decision log, and
read-only live introspection — `GET /debug/state` (queue ages, per-row
positions, arena occupancy, compile families), `GET /debug/trace?id=`
(one request's timeline), `GET /debug/traces` (the sampled window as
Perfetto-loadable Chrome-trace JSON).  Configured SLOs (`--slo-ttft-p99`,
`--slo-error-rate`) export `pfx_slo_*` burn-rate gauges and an `slo`
block (with breach reason) on `/healthz`.

Usage:
  python tools/serve.py -c configs/gpt/pretrain_gpt_345M_single.yaml            # REPL
  python tools/serve.py -c ... --port 8000                                       # HTTP
      POST /generate {"prompt": "...", "max_tokens": 64, "deadline_s": 30}
      GET  /healthz
      GET  /metrics
      GET  /debug/state | /debug/trace?id=<trace_id> | /debug/traces
      POST /admin/drain            # authenticated remote drain
      POST /admin/adopt_prefixes   # migration receiver (PFXH1 body)

/admin/* and /debug/* are gated by the fleet-shared ``PFX_ADMIN_TOKEN``
bearer token (unset = loopback-only, loudly — core/router.check_admin);
``POST /admin/drain`` is the remote spelling of the SIGTERM drain
contract, so rolling deploys work cross-host (docs/serving.md "Elastic
control plane").
"""

import argparse
import json
import math
import os
import secrets
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddlefleetx_tpu.utils.device import apply_platform_env, device_identity

apply_platform_env()  # tpu unless a CPU pin is set; before backend init


def build_server(config: str, overrides):
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import get_config

    cfg = get_config(config, overrides=overrides)
    mesh = init_dist_env(cfg)
    module = build_module(cfg)

    tok = None
    tokenizer_dir = cfg.get("Generation", {}).get("tokenizer_dir")
    if tokenizer_dir:
        from paddlefleetx_tpu.data.tokenizers.gpt_tokenizer import GPTTokenizer

        tok = GPTTokenizer.from_pretrained(tokenizer_dir)

    # no params: the server restores Engine.save_load.ckpt_dir (or makes
    # a tree from the seed) itself, so that it owns the float32 leaves it
    # casts and frees one by one
    return GenerationServer(cfg, mesh, module, tokenizer=tok)


def clamp_max_tokens(requested, default: int, cap: int) -> int:
    """Resolve a request's max_tokens: the configured default when the
    client sent none, clamped to ``cap`` (> 0) either way, floored at 1.
    A huge client value must not key an enormous decode buffer/compile or
    occupy the scheduler for minutes (Generation.max_tokens_cap /
    --max-tokens-cap)."""
    val = default if requested is None else int(requested)
    if cap > 0:
        val = min(val, cap)
    return max(1, val)


def plan_request(prompts_ids, max_toks: int, *, bucket: int, context: int):
    """Predict `GenerationServer.generate_ids` bucketing for one request:
    returns (trim, coalesce_key) where ``trim`` is the request's own
    decode cap after context clamping and ``coalesce_key`` is
    (prompt-length bucket, 32-bucketed decode length) — two requests with
    equal keys pad identically whether served together or apart, so
    coalescing them reuses an already-compiled artifact and (greedy)
    stays token-identical to sequential serving.  Built on the SAME
    helpers generate_ids pads/clamps with (`bucket_len`, `plan_decode`),
    so the prediction cannot drift from the padding.  Raises ValueError
    when the padded prompt leaves no decode room (HTTP 400, before
    admission)."""
    from paddlefleetx_tpu.core.serving import plan_decode
    from paddlefleetx_tpu.models.gpt.generation import bucket_len

    pbucket = bucket_len(max(len(p) for p in prompts_ids), bucket)
    trim, run = plan_decode(pbucket, max_toks, context=context)
    return trim, (pbucket, run)


# /healthz "queue" block: healthz key -> registry metric (one snapshot
# feeds both /metrics and /healthz, so the two endpoints cannot disagree)
_QUEUE_HEALTH_KEYS = {
    "submitted": "pfx_queue_submitted_total",
    "completed": "pfx_queue_completed_total",
    "batches": "pfx_queue_batches_total",
    "coalesced_batches": "pfx_queue_coalesced_batches_total",
    "coalesced_requests": "pfx_queue_coalesced_requests_total",
    "shed_deadline": "pfx_queue_shed_deadline_total",
    "rejected_full": "pfx_queue_rejected_full_total",
    "rejected_closed": "pfx_queue_rejected_closed_total",
    "gen_errors": "pfx_queue_gen_errors_total",
}


def _record_request_span(reg, recorder, t0, fut, code, tokens=None,
                         streamed=False):
    """Turn one /generate lifecycle into telemetry: span phases
    (admission -> queue_wait -> decode -> respond) from the queue's
    monotonic stamps, TTFT + per-token histograms, and a flight-recorder
    event so the last N request spans survive into a crash dump.  A
    request shed before pickup has no decode phase (labeled ``shed``).
    The request's sampled deep-dive trace (if any) gets its terminal
    ``respond`` stamp here and is finished — ``/debug/trace?id=`` then
    replays the full timeline."""
    from paddlefleetx_tpu.utils.telemetry import Span

    trace = getattr(fut, "trace", None) if fut is not None else None
    if trace is not None:
        trace.event("respond", code=code, tokens=tokens)
        trace.finish()
    span = Span("request", t0=t0)
    times = dict(getattr(fut, "times", {}) or {}) if fut is not None else {}
    if "enqueued" in times:
        span.mark("admission", t=times["enqueued"])
    if "picked" in times:
        span.mark("queue_wait", t=times["picked"])
    if "resolved" in times:
        span.mark("decode" if "picked" in times else "shed",
                  t=times["resolved"])
    span.mark("respond")
    phases = span.phases()
    if "queue_wait" in phases:
        reg.histogram("pfx_request_queue_wait_seconds").observe(
            phases["queue_wait"]
        )
    if "decode" in phases:
        reg.histogram("pfx_request_decode_seconds").observe(phases["decode"])
        if tokens:
            reg.histogram("pfx_request_per_token_seconds").observe(
                phases["decode"] / max(1, tokens)
            )
    if "resolved" in times and code == 200 and not streamed:
        # non-streamed decode: the whole completion lands at once, so
        # first-token time IS resolution time.  STREAMED requests
        # (POST /generate?stream=1, the SSE path) observe their own
        # TTFT at the FIRST token flush and their total latency at
        # stream close — this branch skips them (``streamed``) so
        # nothing double-counts.  Success-only either way, like the
        # latency histogram: a shed request's ~deadline wait is not a
        # "time to first token" — it delivered none, and letting it in
        # would turn TTFT p99 into the shed deadline exactly when
        # operators alert
        reg.histogram("pfx_request_ttft_seconds").observe(
            max(0.0, times["resolved"] - t0)
        )
    recorder.record(span.event(code=code, tokens=tokens))


def build_scheduler(server, scheduler: str, *, queue_depth: int,
                    max_coalesce: int, cb_batch: int = 8,
                    kv_blocks: int = 0, name: str = "serve",
                    role: str = "monolith", prefix_cache_blocks: int = 0,
                    prefill_chunk: int = 0, prefix_spill_bytes: int = 0,
                    tenant_config=None, preempt_min_tokens: int = 8):
    """Construct the serving scheduler behind ``--scheduler``:

    - ``coalesce`` (default): the PR 3 `RequestQueue` — same-bucket
      waiting requests merge into one batched decode.
    - ``continuous``: iteration-level scheduling over the block-paged KV
      cache (`core/continuous_batching.py`) — rows join and leave the
      running decode batch at every step boundary, so a request arriving
      mid-decode no longer waits a full decode (head-of-line blocking).
      Flips to the default once the paged drills have soaked on a chip
      window (docs/serving.md).

    ``role="prefill"`` (disaggregated serving, docs/serving.md
    "Multi-host serving") instead wires a `RequestQueue` whose runner is
    `PagedDecodeEngine.prefill_export`: each admitted request prefills
    one prompt into the arena and leaves as a KV-handoff payload — the
    whole admission/deadline/drain contract rides the queue unchanged.

    All spellings expose the same surface (submit/try_remove/depth/
    busy_seconds/close/join/stats), so the HTTP layer below is
    scheduler-agnostic."""
    from paddlefleetx_tpu.core.request_queue import RequestQueue

    if role == "prefill":
        from paddlefleetx_tpu.core.continuous_batching import (
            PagedDecodeEngine,
        )

        engine = PagedDecodeEngine(
            server, max_batch=cb_batch, num_blocks=kv_blocks,
            # prefix reuse on the prefill pool: a shared system prefix
            # is computed once per prefill replica — prefill_export
            # consults/publishes the radix index (docs/serving.md
            # "Disaggregated operations")
            prefix_cache_blocks=prefix_cache_blocks,
            prefill_chunk=prefill_chunk,
            prefix_spill_bytes=prefix_spill_bytes,
        )

        def prefill_runner(prompts, max_new):
            # per-prompt traces ride RequestQueue.batch_traces (set by
            # the scheduler thread for the duration of this call), so
            # the export's fine-grained prefill_export span lands on
            # the request's own timeline — the prefill leg a stitched
            # fleet trace shows is the real export window, not just
            # the queue's coarse decode envelope
            traces = queue.batch_traces or [None] * len(prompts)
            return [
                engine.prefill_export(p, max_new, trace=tr)
                for p, tr in zip(prompts, traces)
            ]

        queue = RequestQueue(
            prefill_runner, max_depth=queue_depth, max_coalesce=1,
            name=name, tenant_config=tenant_config,
        )
        queue.engine = engine  # warmup + /debug introspection
        return queue
    if scheduler == "coalesce":
        return RequestQueue(
            lambda prompts, max_new: server.generate_ids(
                prompts, max_dec_len=max_new
            ),
            max_depth=queue_depth, max_coalesce=max_coalesce, name=name,
            tenant_config=tenant_config,
        )
    if scheduler == "continuous":
        from paddlefleetx_tpu.core.continuous_batching import (
            ContinuousScheduler,
            PagedDecodeEngine,
        )

        engine = PagedDecodeEngine(
            server, max_batch=cb_batch, num_blocks=kv_blocks,
            prefix_cache_blocks=prefix_cache_blocks,
            prefill_chunk=prefill_chunk,
            prefix_spill_bytes=prefix_spill_bytes,
        )
        return ContinuousScheduler(
            engine, max_depth=queue_depth, name=name,
            tenant_config=tenant_config,
            preempt_min_tokens=preempt_min_tokens,
        )
    raise ValueError(
        f"unknown scheduler {scheduler!r}; valid: coalesce, continuous"
    )


def serve_http(server, port: int, host: str = "127.0.0.1", *,
               queue_depth: int = 64, max_coalesce: int = 8,
               default_deadline_s: float = 120.0, max_deadline_s: float = 600.0,
               shed_slack_s: float = 2.0,
               watchdog_s: float = 300.0, max_tokens_cap: int = 0,
               scheduler: str = "coalesce", cb_batch: int = 8,
               kv_blocks: int = 0, prefix_cache_blocks: int = 0,
               prefill_chunk: int = 0, prefix_spill_bytes: int = 0,
               cb_warmup=(),
               slo_ttft_p99_s: float = 0.0, slo_error_rate: float = 0.0,
               slo_windows_s=(60.0, 600.0),
               role: str = "monolith", replica_id: str = "",
               tenants_path: str = "", preempt_min_tokens: int = 8,
               router_url: str = ""):
    import signal
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
    from queue import Empty as SinkEmpty, Queue as SinkQueue
    from urllib.parse import parse_qs, urlsplit

    from paddlefleetx_tpu.core.request_queue import (
        DeadlineExceeded,
        QueueClosed,
        QueueFull,
    )
    from paddlefleetx_tpu.core.router import check_admin
    from paddlefleetx_tpu.core.tenancy import (
        PRIORITY_HEADER,
        TENANT_HEADER,
        TenantConfig,
        TenantLabelCap,
        normalize_tenant,
        parse_priority,
    )
    from paddlefleetx_tpu.utils.log import log_server_error
    from paddlefleetx_tpu.utils.telemetry import (
        SLOTracker,
        atomic_artifact_write,
        flight_dir,
        get_flight_recorder,
        get_registry,
    )
    from paddlefleetx_tpu.utils import tracing
    from paddlefleetx_tpu.utils.tracing import (
        SPAN_SUMMARY_HEADER,
        chrome_trace,
        get_trace_buffer,
        parse_span_summaries,
        remote_parent,
        remote_parent_from_headers,
        span_summary,
    )

    reg = get_registry()
    recorder = get_flight_recorder()
    # a crash anywhere in the serving process leaves a postmortem ring
    recorder.install_excepthook()
    # retrace attribution (utils/model_stats.py): mid-traffic compiles
    # land in the flight ring + pfx_compile_* with the aval diff that
    # keyed them (PFX_COMPILE_LOG=0 disables)
    from paddlefleetx_tpu.utils.model_stats import install_compile_watcher

    install_compile_watcher()
    trace_buffer = get_trace_buffer()

    # SLO burn-rate layer (docs/observability.md): objectives evaluated
    # over rolling multi-window burn rates, exported as pfx_slo_* gauges
    # and surfaced as the /healthz "slo" block.  Observed per RESPONSE
    # in the HTTP layer — the decode hot path never touches it.
    # multi-tenant isolation (docs/serving.md): quota/weight config +
    # the process-wide label fold the per-tenant series ride
    tenant_config = (TenantConfig.from_file(tenants_path)
                     if tenants_path else TenantConfig())
    tenant_labels = TenantLabelCap(seed=tenant_config.known_tenants())
    slo = SLOTracker(
        ttft_p99_s=slo_ttft_p99_s, error_rate=slo_error_rate,
        windows_s=slo_windows_s, tenant_label_fn=tenant_labels.label,
    )
    if slo.enabled:
        reg.register_collector(slo)

    def _slo_observe(code, fut, t0, tenant=None):
        # per-tenant TTFT is observed regardless of SLO objectives: the
        # flood drill reads isolation off this histogram
        ttft = None
        times = getattr(fut, "times", {}) if fut is not None else {}
        if code == 200 and "resolved" in times:
            ttft = max(0.0, times["resolved"] - t0)
        if ttft is not None:
            reg.histogram(
                "pfx_tenant_ttft_seconds",
                tenant=tenant_labels.label(normalize_tenant(tenant)),
            ).observe(ttft)
        if not slo.enabled:
            return
        # contract outcomes: 200 is budget-neutral; 429/500/503 spend the
        # error budget; 400/404 are the client's fault and observe nothing
        if code in (400, 404):
            return
        slo.observe_request(ttft_s=ttft, ok=code == 200, tenant=tenant)

    cap = max_tokens_cap or int(
        server.cfg.get("Generation", {}).get("max_tokens_cap", 0) or 0
    )
    context = int(server.module.config.max_position_embeddings)
    bucket = server.bucket

    # the scheduler thread is the ONLY caller of generation once traffic
    # starts: generation mutates server state (RNG key split, stats,
    # cache pool / paged arena) and shares one compiled-artifact cache,
    # so the queue replaces the old global gen_lock outright.  Behind
    # --scheduler this is either the PR 3 coalescing RequestQueue or the
    # continuous-batching ContinuousScheduler (same surface).
    queue = build_scheduler(
        server, scheduler, queue_depth=queue_depth,
        max_coalesce=max_coalesce, cb_batch=cb_batch, kv_blocks=kv_blocks,
        name="serve", role=role, prefix_cache_blocks=prefix_cache_blocks,
        prefill_chunk=prefill_chunk, prefix_spill_bytes=prefix_spill_bytes,
        tenant_config=tenant_config, preempt_min_tokens=preempt_min_tokens,
    )
    # the paged engine behind the scheduler (None on the coalesce path):
    # the /healthz prefix-affinity advertisement and the drain-time
    # prefix migration read it directly
    engine = getattr(queue, "engine", None)
    # token streaming (docs/serving.md "Token streaming"): only the
    # continuous scheduler has a per-step commit hook (submit(stream=));
    # the coalesce scheduler resolves whole completions, so its streamed
    # responses degrade to a single flush at completion — still SSE, so
    # clients need one code path
    stream_capable = scheduler == "continuous" and role != "prefill"

    # /healthz identity block (docs/serving.md "Multi-host serving"):
    # the router (and a human with curl) can tell replicas apart, and
    # the pid is what lets `tools/router.py drain` ride the SIGTERM
    # drain contract on same-host topologies
    # boot_id is random PER PROCESS START: pid+boot_id names this exact
    # incarnation, so the router's re-adoption and legacy drain-by-pid
    # paths can never mistake a recycled pid for this replica
    # (docs/serving.md "Control-plane recovery")
    identity = {
        "replica_id": replica_id or f"{host}:{port}",
        "role": role,
        "scheduler": "queue" if role == "prefill" else scheduler,
        "listen": f"{host}:{port}",
        "pid": os.getpid(),
        "boot_id": secrets.token_hex(8),
        "started_at": round(time.time(), 3),
        # the device JAX found (platform / device_kind / device_count):
        # a replica that came up on the host's CPU says so here
        **device_identity(),
    }
    # label this process's spans for cross-process exports: the fleet's
    # stitched timelines name their Perfetto lanes off this identity
    tracing.set_process_identity(
        replica_id=identity["replica_id"], role=role,
    )

    # in-flight /generate requests (admission + wait + response write);
    # /healthz surfaces it so an operator tells "busy" from "wedged".
    # All HTTP accounting lives on the telemetry registry: /healthz and
    # /metrics read ONE locked snapshot instead of the old half-locked
    # Counter + latency deque (the reservoir rides the latency histogram)
    in_flight_gauge = reg.gauge("pfx_http_requests_in_flight")
    client_gone = reg.counter("pfx_http_client_gone_total")
    latency_hist = reg.histogram("pfx_request_latency_seconds")
    draining_gauge = reg.gauge("pfx_serve_draining")
    degraded_gauge = reg.gauge("pfx_serve_degraded")
    # health state flags (process-local booleans drive control flow; the
    # gauges mirror them for scrapes)
    flags = {"draining": False, "degraded": False}
    stop_event = threading.Event()

    # direct prefill->decode transfer (docs/serving.md "Disaggregated
    # operations"): one process-wide send counter so
    # PFX_FAULT=handoff_drop:K targets the Kth direct send exactly —
    # locked, because handler threads increment it concurrently
    direct_state = {"n": 0}
    direct_lock = threading.Lock()

    def _direct_handoff(payload: bytes, url: str, fwd_deadline: float,
                        parent=None, extra_headers=None):
        """POST one KV-handoff payload straight to the ticketed decode
        replica (auth via the fleet PFX_ADMIN_TOKEN rule, bounded
        timeout, ONE retry for sends that provably never arrived).
        Returns ``(code, body, content_type, headers)`` for the
        /prefill response:

          - decode answered 200 -> relay its JSON completion (the
            payload bytes never transit the router);
          - send never arrived (refused / injected drop / not sent),
            twice, or decode answered 429/503 (capacity/draining) or
            401/403 (this replica's admin token rejected — the router
            authenticates the proxy leg itself) -> PROXY FALLBACK:
            return the payload octet-stream for the router to carry —
            any decode replica can take it, nothing was adopted;
          - any other non-200 -> relay the decode replica's verdict
            (a 400 payload rejection repeats at every pool member);
          - lost MID-exchange -> structured 502 naming the decode leg:
            the row may be adopted there, so the router must run its
            re-prefill failover through a healthy pair instead of ever
            replaying at that replica."""
        from paddlefleetx_tpu.core.router import (
            ReplicaUnavailable,
            RequestNotSent,
            _http_request,
            admin_headers,
        )
        from paddlefleetx_tpu.utils.resilience import maybe_fire

        with direct_lock:
            direct_state["n"] += 1
            seq = direct_state["n"]
        # the direct hop carries the ROUTER's trace identity onward so
        # the decode leg's spans stitch under the same fleet timeline
        # (prefill -> decode is the one hop the router never sees)
        fwd_trace = dict(parent and {
            tracing.TRACE_ID_HEADER: parent["trace_id"],
            tracing.PARENT_SPAN_HEADER: "handoff_direct",
        } or {})
        last_err = "send failed"
        t_send = time.monotonic()
        for _attempt in range(2):  # the send + one retry
            # the ticket budget keeps burning across attempts: a retry
            # after a stalled first send must not offer /decode the
            # full budget again (the router's clock expired with the
            # stall — a doomed decode would just pin arena blocks)
            left = fwd_deadline - (time.monotonic() - t_send)
            if left <= 0:
                last_err = (f"{last_err}; ticket budget spent before "
                            "retry")
                break
            if maybe_fire("handoff_drop", seq):
                # deterministic drop drill: this send never goes out
                last_err = "injected handoff_drop"
                continue
            try:
                status, body, _, hdrs = _http_request(
                    url, "POST",
                    f"/decode?deadline_s={left:.3f}",
                    body=payload,
                    headers={
                        "Content-Type": "application/octet-stream",
                        "X-Handoff-Transport": "direct",
                        # tenant/priority ride the prefill->decode hop
                        # verbatim (the one hop the router never sees)
                        **(extra_headers or {}),
                        **admin_headers(),
                        **fwd_trace,
                    },
                    # the remaining ticket budget is bounded by the
                    # router's --max-deadline: give the socket the same
                    # grace the proxy leg gets — a cap below the
                    # deadline would misclassify a slow but legitimate
                    # decode as a dead replica
                    timeout=left + 5.0,
                )
            except ConnectionRefusedError as e:
                last_err = f"refused: {e}"
                continue
            except RequestNotSent as e:
                last_err = str(e)
                continue
            except ReplicaUnavailable as e:
                reg.counter("pfx_handoff_direct_total",
                            outcome="decode_dead").inc()
                return (502, json.dumps({
                    "error": f"direct decode leg lost mid-exchange ({e})",
                    "handoff_leg": "decode",
                }).encode(), "application/json", None)
            if status == 200:
                reg.counter("pfx_handoff_bytes_total",
                            transport="direct").inc(len(payload))
                reg.counter("pfx_handoff_direct_total",
                            outcome="ok").inc()
                # the decode replica's span summary rides the relay back
                # (the /prefill response appends this replica's own, so
                # the router stitches both legs off one hop)
                child = hdrs.get(SPAN_SUMMARY_HEADER)
                return (200, body, "application/json",
                        {SPAN_SUMMARY_HEADER: child} if child else None)
            if status in (401, 403, 429, 503):
                # 429/503: capacity/draining — any pool member can take
                # the payload off the router's proxy leg. 401/403: the
                # decode pool rejected THIS replica's admin token; the
                # router authenticates the proxy leg with its OWN
                # credentials, so a prefill-side token misconfiguration
                # must degrade to the carry, not surface as a
                # transport-specific client error
                last_err = f"decode answered HTTP {status}"
                break
            reg.counter("pfx_handoff_direct_total",
                        outcome="rejected").inc()
            return (status, body, "application/json", None)
        reg.counter("pfx_handoff_direct_total", outcome="fallback").inc()
        # loud on the replica, not just a response header the router
        # consumes: a PERSISTENT degradation (token misconfiguration,
        # firewalled decode pool) defeats the direct transport's whole
        # point while every request still succeeds via the proxy carry
        print(f"DIRECT-TRANSFER DEGRADED to proxy carry "
              f"(send #{seq}): {last_err}", flush=True)
        return (200, payload, "application/octet-stream",
                {"X-Direct-Error": last_err})

    class Handler(BaseHTTPRequestHandler):
        timeout = 120  # a silent client can't pin a handler thread forever

        def log_message(self, *a):  # route through our logger instead
            pass

        def _send(self, code: int, body: bytes, ctype: str, headers=None):
            if code >= 500:
                # one structured line per 5xx (utils/log.log_server_error):
                # greppable key=value carrying whatever the handler knew —
                # trace_id when the request was sampled (it rides the
                # response headers), tenant, and the error body as outcome
                outcome = None
                if ctype == "application/json":
                    try:
                        outcome = json.loads(body.decode()).get("error")
                    except (ValueError, UnicodeDecodeError):
                        pass
                log_server_error(
                    "serve", code, self.path,
                    replica_id=identity["replica_id"],
                    tenant=self.headers.get(TENANT_HEADER),
                    trace_id=(headers or {}).get("X-Trace-Id"),
                    outcome=outcome,
                )
            # disconnect-tolerant: a client that hung up while we write
            # (including on an error path) is counted as client_gone —
            # never a stack trace, never a skewed http_* counter
            try:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)
            except (BrokenPipeError, ConnectionResetError, TimeoutError):
                # TimeoutError: the handler socket timeout fired while a
                # stalled client refused our bytes — same client_gone class
                client_gone.inc()
            else:
                reg.counter("pfx_http_responses_total", code=str(code)).inc()

        def _json(self, code: int, obj, headers=None):
            self._send(code, json.dumps(obj).encode(), "application/json",
                       headers)

        def do_GET(self):
            parts = urlsplit(self.path)
            if parts.path == "/healthz":
                # ONE registry snapshot renders the whole health view —
                # the same snapshot function /metrics exposes, so the two
                # endpoints agree and no field is read outside a lock
                snap = reg.snapshot()
                state = ("draining" if flags["draining"]
                         else "degraded" if flags["degraded"] else "ok")
                counts = {}
                for lab, v in snap.get(
                    "pfx_http_responses_total", {"values": []}
                )["values"]:
                    counts[f"http_{lab.get('code', '?')}"] = int(v)
                gone = int(reg.value("pfx_http_client_gone_total", snap=snap))
                if gone:
                    counts["client_gone"] = gone
                lat = reg.value(
                    "pfx_request_latency_seconds",
                    default={"p50": 0.0, "p99": 0.0}, snap=snap,
                )
                ttft = reg.value(
                    "pfx_request_ttft_seconds",
                    default={"p50": 0.0, "p99": 0.0}, snap=snap,
                )
                itl = reg.value(
                    "pfx_request_itl_seconds",
                    default={"p50": 0.0, "p99": 0.0}, snap=snap,
                )
                # serving numerics come from the SAME snapshot (not a
                # second read of server.stats) so /healthz and /metrics
                # can never disagree; instance-local extras (last_error,
                # warmup_s) overlay from the stats view
                serving_keys = {
                    "requests": ("pfx_serving_requests_total", int),
                    "tokens_out": ("pfx_serving_tokens_out_total", int),
                    "time_s": ("pfx_serving_gen_seconds_total", float),
                    "traces": ("pfx_serving_traces_total", int),
                    "gen_errors": ("pfx_serving_gen_errors_total", int),
                    "last_latency_s":
                        ("pfx_serving_last_latency_seconds", float),
                }
                serving_view = {
                    k: v for k, v in server.stats.items()
                    if k not in serving_keys
                }
                serving_view.update({
                    k: cast(reg.value(m, snap=snap))
                    for k, (m, cast) in serving_keys.items()
                })
                body = {
                    "ok": not flags["degraded"],
                    "state": state,
                    "identity": identity,
                    "in_flight": int(reg.value(
                        "pfx_http_requests_in_flight", snap=snap)),
                    "queue_depth": int(reg.value("pfx_queue_depth",
                                                 snap=snap)),
                    "busy_s": round(
                        reg.value("pfx_queue_busy_seconds", snap=snap), 3),
                    # elastic-control signal (core/controller.py): the
                    # continuous scheduler's rows/capacity (0 elsewhere)
                    "occupancy": round(float(reg.value(
                        "pfx_batch_occupancy", snap=snap)), 4),
                    # decode-pool scale + routing signal: arena blocks
                    # an admission can actually obtain (continuous
                    # scheduler replicas only; absent elsewhere)
                    **({"available_blocks": int(reg.value(
                        "pfx_kv_blocks_available", snap=snap))}
                       if "pfx_kv_blocks_available" in snap else {}),
                    # a model with window layers: the second class of
                    # pages (a row's ring), which an admission needs too
                    **({"available_ring_blocks":
                        int(engine.cache.ring_allocator.free_count()),
                        "ring_pages_per_row": int(engine.ring_pages)}
                       if engine is not None
                       and getattr(engine, "ring_pages", 0) else {}),
                    # prefix-affinity routing signal (core/router.py):
                    # how many shared-prefix blocks this replica has
                    # published, plus a compact digest of the hottest
                    # cached prefixes (crc32 path hashes) — the router
                    # scores requests toward the replica already
                    # holding their prefill (absent when the prefix
                    # cache is off)
                    **({"prefix_cached_blocks": int(reg.value(
                        "pfx_prefix_cached_blocks", snap=snap))}
                       if "pfx_prefix_cached_blocks" in snap else {}),
                    **({"prefix_hashes": engine.cache.prefix.digest(),
                        "prefix_block": int(engine.block)}
                       if engine is not None
                       and getattr(engine, "prefix_enabled", False)
                       else {}),
                    "queue": {
                        k: int(reg.value(m, snap=snap))
                        for k, m in _QUEUE_HEALTH_KEYS.items()
                    },
                    "counters": counts,
                    "latency_p50_s": round(lat["p50"], 4),
                    "latency_p99_s": round(lat["p99"], 4),
                    "ttft_p50_s": round(ttft["p50"], 4),
                    "ttft_p99_s": round(ttft["p99"], 4),
                    # inter-token latency (streamed /generate flushes):
                    # first-class next to TTFT — the fleet log + report
                    # panels read these per replica
                    "itl_p50_s": round(itl["p50"], 4),
                    "itl_p99_s": round(itl["p99"], 4),
                    **serving_view,
                }
                if slo.enabled:
                    # burn-rate view with the breach reason: an operator
                    # reads WHY /healthz is angry without a dashboard
                    body["slo"] = slo.evaluate()
                if parse_qs(parts.query).get("metrics", ["0"])[0] not in (
                    "0", "",
                ):
                    # fleet federation source (core/router.py): the FULL
                    # Prometheus exposition rendered from the SAME
                    # snapshot the health fields above came from — the
                    # router's poll loop scores routing on these fields
                    # and re-exports these samples, and because both
                    # ride one snapshot they can never tell two stories
                    body["metrics_text"] = reg.render_prometheus(snap)
                self._json(200, body)
            elif parts.path == "/metrics":
                # Prometheus text exposition of the same registry snapshot
                self._send(
                    200, reg.render_prometheus().encode(),
                    "text/plain; version=0.0.4; charset=utf-8",
                )
            elif parts.path.startswith("/debug/"):
                self._debug_get()
            else:
                self._json(404, {"error": "unknown path"})

        def _authorized(self, what: str) -> bool:
            """Gate an /admin or /debug request on the shared
            PFX_ADMIN_TOKEN (core/router.check_admin): token set ->
            bearer match required; unset -> loopback-only, loudly.
            Answers 401/403 itself when the check fails."""
            ok, code, msg = check_admin(
                self.headers, self.client_address, what=what
            )
            if not ok:
                self._json(code, {"error": msg})
            return ok

        def _debug_get(self):
            """Live introspection (docs/observability.md): read-only,
            lock-consistent snapshots that never block the scheduler
            thread; prompt/token CONTENTS are never exposed.  Gated by
            the same PFX_ADMIN_TOKEN rule as /admin/* — introspection
            must not ship unauthenticated on a non-loopback bind."""
            if not self._authorized("/debug"):
                return
            parts = urlsplit(self.path)
            if parts.path == "/debug/state":
                # one registry snapshot rides along so the debug view and
                # the scraped gauges can be compared from a single read
                snap = reg.snapshot()
                dbg = queue.debug_state()
                dbg["serving"] = {
                    "compiled_families": len(getattr(server, "_compiled", {})),
                    "traces": int(server.stats["traces"]),
                    "gen_errors": int(server.stats["gen_errors"]),
                }
                dbg["flags"] = dict(flags)
                # the newest compile events (fn, aval diff, seconds): which
                # function a post-warmup request paid a compile for
                from paddlefleetx_tpu.utils.model_stats import (
                    get_compile_watcher,
                )

                dbg["compile_events"] = get_compile_watcher().snapshot()[-32:]
                dbg["trace_buffer"] = {
                    "sample": trace_buffer.sample,
                    "cap": trace_buffer.cap,
                    "retained": len(trace_buffer.traces()),
                }
                if slo.enabled:
                    dbg["slo"] = slo.evaluate()
                gauges = {}
                for name in (
                    "pfx_queue_depth", "pfx_queue_busy_seconds",
                    "pfx_http_requests_in_flight", "pfx_batch_occupancy",
                    "pfx_kv_blocks_used", "pfx_kv_blocks_free",
                    "pfx_kv_bytes", "pfx_prefill_admits_total",
                    "pfx_request_evictions_total", "pfx_spec_accept_rate",
                    "pfx_spec_accepted_total", "pfx_spec_proposed_total",
                    "pfx_prefix_hits_total", "pfx_prefix_misses_total",
                    "pfx_prefix_hit_tokens_total",
                    "pfx_prefix_evictions_total", "pfx_prefix_cached_blocks",
                    "pfx_prefill_chunks_total",
                    "pfx_prefix_spill_bytes", "pfx_prefix_spill_entries",
                    "pfx_prefix_spills_total", "pfx_prefix_readmits_total",
                    "pfx_prefix_spill_discards_total",
                    "pfx_migrate_sent_total", "pfx_migrate_adopted_total",
                    "pfx_migrate_failed_total",
                ):
                    if name in snap:
                        gauges[name] = reg.value(name, snap=snap)
                dbg["metrics"] = gauges
                return self._json(200, dbg)
            if parts.path == "/debug/trace":
                tid = (parse_qs(parts.query).get("id") or [""])[0]
                if not tid:
                    return self._json(400, {"error": "need ?id=<trace_id>"})
                tc = trace_buffer.get(tid)
                if tc is None:
                    return self._json(404, {
                        "error": f"trace {tid!r} not in the sampled window "
                                 f"(cap {trace_buffer.cap}, sample "
                                 f"{trace_buffer.sample:g})"
                    })
                return self._json(200, tc.timeline())
            if parts.path == "/debug/traces":
                # the retained window as Perfetto/chrome://tracing JSON
                return self._json(200, chrome_trace(trace_buffer.traces()))
            return self._json(404, {"error": "unknown debug path"})

        def _parse_prompts(self, req):
            """(prompts_ids, mode) from a /generate body; raises
            ValueError with a client-facing message (HTTP 400)."""
            if "prompt" in req or "prompts" in req:
                if server.tokenizer is None:
                    raise ValueError(
                        "no tokenizer configured (Generation.tokenizer_dir); "
                        "send prompt_ids/prompts_ids"
                    )
                if "prompt" in req:
                    texts, mode = [req["prompt"]], "prompt"
                else:
                    texts, mode = list(req["prompts"]), "prompts"
                if not texts or not all(
                    isinstance(t, str) and t for t in texts
                ):
                    raise ValueError("prompts must be non-empty strings")
                return [server.tokenizer.encode(t) for t in texts], mode
            if "prompt_ids" in req:
                ids, mode = [req["prompt_ids"]], "prompt_ids"
            elif "prompts_ids" in req:
                ids, mode = list(req["prompts_ids"]), "prompts_ids"
            else:
                raise ValueError("need prompt(s) or prompt(s)_ids")
            if not ids or any(not p for p in ids):
                raise ValueError(
                    "prompts must be a non-empty list of non-empty id lists"
                )
            return [[int(t) for t in p] for p in ids], mode

        def _check_batch_cap(self, prompts_ids):
            # one request may not smuggle an unbounded batch past the
            # admission bounds: a 4096-prompt entry would occupy ONE
            # queue slot yet key a giant padded-batch compile that wedges
            # the single scheduler thread for everyone else
            if len(prompts_ids) > max_coalesce:
                raise ValueError(
                    f"too many prompts in one request "
                    f"({len(prompts_ids)} > {max_coalesce}); split the batch"
                )

        def do_POST(self):
            parts = urlsplit(self.path)
            if parts.path.startswith("/admin/"):
                return self._admin(parts)
            if parts.path == "/generate":
                if role == "prefill":
                    # a prefill replica has no decode loop to finish a
                    # request: an honest 400 beats a silent wrong answer
                    return self._json(400, {
                        "error": "--role prefill serves POST /prefill "
                                 "only (disaggregated topology; see "
                                 "docs/serving.md)"
                    })
                return self._generate(parts)
            if parts.path == "/prefill":
                if role != "prefill":
                    return self._json(404, {"error": "not a prefill replica"})
                # fabric-internal endpoint: the fleet PFX_ADMIN_TOKEN
                # rule applies (token set -> bearer match; unset ->
                # loopback-only, loudly) — a KV-handoff surface must not
                # ship unauthenticated on a non-loopback bind
                if not self._authorized("/prefill"):
                    return
                return self._prefill()
            if parts.path == "/decode":
                if role != "decode":
                    return self._json(404, {"error": "not a decode replica"})
                if not self._authorized("/decode"):
                    return
                return self._decode(parts)
            return self._json(404, {"error": "unknown path"})

        def _admin(self, parts):
            """POST /admin/* — the authenticated operations surface
            (docs/serving.md "Elastic control plane").  ``/admin/drain``
            is the remote spelling of SIGTERM: the response is written
            first (the caller learns the drain STARTED), then admission
            closes, every admitted request is answered, and the process
            exits 0 — rolling deploys no longer need to share a host
            with the replica."""
            if not self._authorized("/admin"):
                return
            if parts.path == "/admin/drain":
                # optional JSON body: {"migrate_to": [peer_url, ...]}
                # names surviving peers to ship the hottest published
                # prefixes to before the listener dies (KV migration,
                # docs/serving.md "KV lifecycle").  Read BEFORE the
                # response — the body is gone once we answer.
                n = int(self.headers.get("Content-Length", 0))
                try:
                    req = json.loads(self.rfile.read(n) or b"{}")
                except json.JSONDecodeError:
                    req = {}  # a bare drain must keep working
                peers = tuple(
                    str(u) for u in (req.get("migrate_to") or []) if u
                )
                # response FIRST, then the drain: an idle replica can
                # finish its drain in milliseconds, and the caller must
                # learn the drain started before the listener dies
                already = flags["draining"]
                self._json(200, {
                    "state": "draining",
                    "already_draining": already,
                    "queued": queue.depth(),
                })
                # a drain initiated over a traced hop names the caller's
                # trace in the postmortem, so an operator can tie this
                # replica's drain_start to the router action behind it
                parent = remote_parent_from_headers(self.headers)
                initiate_drain(
                    "admin drain" + (
                        f" (trace {parent['trace_id']})" if parent else ""
                    ),
                    migrate_to=peers,
                )
                return
            if parts.path == "/admin/adopt_prefixes":
                return self._adopt_prefixes()
            if parts.path == "/admin/profile":
                return self._profile()
            return self._json(404, {"error": "unknown admin path"})

        def _profile(self):
            """POST /admin/profile {"seconds": T} — capture a
            jax.profiler trace of THIS live serving process and answer
            with the parsed summary (docs/observability.md "On-demand
            profiling").  ``"summary": false`` skips the in-process
            parse (the reply carries ``trace_dir`` and ``seconds``
            only); ``"python_tracer": false`` keeps the profiler's
            Python call tracer off.  Safety rails live in
            utils/profiler.capture_profile: one capture at a time
            (ProfileBusy -> 409) and the PFX_PROFILE_MAX_SECONDS hard
            cap (-> 400).  The capture observes the running scheduler —
            it drives nothing, so profiling a production replica under
            load is bounded and safe."""
            from paddlefleetx_tpu.utils.profiler import (
                ProfileBusy,
                capture_profile,
            )

            n = int(self.headers.get("Content-Length", 0))
            try:
                req = json.loads(self.rfile.read(n) or b"{}")
            except json.JSONDecodeError:
                return self._json(400, {"error": "body must be JSON"})
            seconds = req.get("seconds", 3.0)
            top = int(req.get("top", 20))
            # one dir per capture under the flight dir: the trace is a
            # postmortem artifact and lands next to the crash ring
            prof_dir = os.path.join(
                flight_dir(), "profiles",
                time.strftime("%Y%m%d-%H%M%S"),
            )
            try:
                summary = capture_profile(
                    seconds, prof_dir, top=top,
                    summary=bool(req.get("summary", True)),
                    python_tracer=bool(req.get("python_tracer", True)),
                    # the continuous engine's work counters at both ends
                    # of the capture (numbers only: steps, rows, cached
                    # tokens attended, expert pairs)
                    probe=None if engine is None else lambda: {
                        k: v for k, v in engine.stats.items()
                        if isinstance(v, (int, float))},
                )
            except ProfileBusy as e:
                print(f"[serve] /admin/profile refused: {e}", flush=True)
                return self._json(409, {"error": str(e)})
            except ValueError as e:
                return self._json(400, {"error": str(e)})
            summary["replica_id"] = identity["replica_id"]
            # durable copy next to the trace itself, torn-write-proof,
            # so a fleet report can inline the op table later
            atomic_artifact_write(
                os.path.join(prof_dir, "profile_summary.json"),
                lambda f: json.dump(summary, f, indent=1),
            )
            recorder.record({
                "event": "profile_capture",
                "seconds": summary["seconds"],
                "trace_dir": prof_dir,
                "source": summary.get("source", "not parsed"),
                "started_monotonic_ns": summary["started_monotonic_ns"],
                "started_time_ns": summary["started_time_ns"],
            })
            return self._json(200, summary)

        def _adopt_prefixes(self):
            """POST /admin/adopt_prefixes — the migration-receiver half
            of KV durability (docs/serving.md "KV lifecycle"): a
            draining peer's exported prefix payload (PFXH1 binary body)
            is validated IN FULL before anything touches the arena,
            then folded in on the scheduler thread at an iteration
            boundary.  A torn or incompatible payload gets an honest
            400 and nothing is half-adopted; a draining/closed replica
            answers 503 so the sender's failover ladder moves on."""
            from paddlefleetx_tpu.core.paged_cache import unpack_handoff

            if not hasattr(queue, "submit_prefix_adoption"):
                return self._json(400, {
                    "error": "prefix adoption requires --scheduler "
                             "continuous (paged KV arena)"
                })
            n = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(n)
            try:
                meta, arrays = unpack_handoff(body)
                fut = queue.submit_prefix_adoption(meta, arrays)
            except ValueError as e:
                # torn payload / wrong block size / pool-shape mismatch:
                # rejected whole, before any arena mutation
                return self._json(400, {"error": str(e)})
            except QueueClosed:
                return self._json(
                    503, {"error": "draining: not adopting prefixes"},
                    headers={"Retry-After": "5"},
                )
            try:
                adopted = fut.result(timeout=default_deadline_s)
            except TimeoutError:
                return self._json(
                    503, {"error": "adoption still pending; scheduler "
                                   "busy"},
                    headers={"Retry-After": "1"},
                )
            except Exception as e:  # noqa: BLE001 — arena reset et al.
                return self._json(500, {"error": str(e)})
            return self._json(200, {"adopted_blocks": int(adopted)})

        def _fail(self, code: int, msg: str, fut, t0, retry=None):
            """One failed-request epilogue: span + SLO accounting (400s
            are the client's fault and spend no SLO budget) + response."""
            _record_request_span(reg, recorder, t0, fut, code)
            if code != 400:
                _slo_observe(code, fut, t0)
            self._json(code, {"error": msg},
                       headers={"Retry-After": retry} if retry else None)

        def _await_result(self, fut, deadline_s: float, t0):
            """THE result-wait ladder, shared by /generate, /prefill and
            /decode: block bounded by deadline + scheduling slack; on any
            failure send the honest error (503 shed / 400 / 500) and
            return None — an unanswerable request never hangs a
            connection."""
            try:
                return fut.result(timeout=deadline_s + shed_slack_s)
            except TimeoutError:
                queue.try_remove(fut)  # shed it if still queued
                self._fail(503, f"deadline {deadline_s:g}s exceeded",
                           fut, t0, retry="1")
            except DeadlineExceeded as e:
                self._fail(503, str(e), fut, t0, retry="1")
            except QueueClosed as e:  # flushed by a forced shutdown
                self._fail(503, str(e), fut, t0, retry="5")
            except ValueError as e:  # bad request that got past checks
                self._fail(400, str(e), fut, t0)
            except Exception as e:  # noqa: BLE001 — report, keep serving
                self._fail(500, str(e), fut, t0)
            return None

        def _read_deadline(self, raw):
            """Validate a client deadline: positive, finite, capped by
            the server ceiling (raises ValueError -> HTTP 400)."""
            deadline_s = float(raw)
            if not (deadline_s > 0 and math.isfinite(deadline_s)):
                raise ValueError(
                    "deadline_s must be a positive finite number"
                )
            return min(deadline_s, max_deadline_s)

        def _submit_guarded(self, submit, t0):
            """THE admission-rejection contract, shared by /generate,
            /prefill and /decode: run the queue-submit callable and
            return its future, or answer 429 (full) / 503 (draining) /
            400 (pre-admission validation) and return None."""
            try:
                return submit()
            except QueueFull:
                _slo_observe(429, None, t0)
                self._json(
                    429,
                    {"error": f"queue full ({queue_depth} waiting); "
                              "retry later"},
                    headers={"Retry-After": "1"},
                )
            except QueueClosed:
                _slo_observe(503, None, t0)
                self._json(
                    503,
                    {"error": "draining: not admitting new requests"},
                    headers={"Retry-After": "5"},
                )
            except ValueError as e:
                # pre-admission validation (could-never-fit budget,
                # incompatible handoff payload): the client's fault
                self._json(400, {"error": str(e)})
            return None

        def _remote_parent_authed(self):
            """Parse the trace-propagation headers, honored only when
            the request passes the fleet admin rule (token set ->
            bearer match; unset -> loopback-only): an unauthenticated
            client must not force-sample traces past the accumulator
            or receive internal span summaries.  Degrades to untraced
            (no 401 — propagation is fabric plumbing, not a client
            API).  /prefill and /decode parse the headers directly:
            those surfaces are already behind ``_authorized``."""
            parent = remote_parent_from_headers(self.headers)
            if parent is None:
                return None
            ok, _, _ = check_admin(self.headers, self.client_address,
                                   what="trace propagation")
            return parent if ok else None

        def _span_headers(self, fut, parent, carried=None):
            """Fabric-internal response headers for a traced hop: this
            process's span summary (appended to any ``carried`` header
            value a downstream leg returned) + the local trace id.
            None for plain client traffic — summaries ride only hops
            that arrived with propagation headers."""
            if fut is None or fut.trace is None:
                return None
            headers = {"X-Trace-Id": fut.trace.trace_id}
            if parent is not None:
                summaries = (parse_span_summaries(carried)
                             if carried else [])
                summaries.append(span_summary(fut.trace))
                headers[SPAN_SUMMARY_HEADER] = json.dumps(summaries)
            return headers

        def _tenant_of(self):
            """The request's tenant label + clamped priority, from the
            X-Tenant / X-Priority headers (absent -> the anonymous
            tenant at priority 0).  The RAW header value also rides
            back out on forwarded hops, verbatim."""
            raw = self.headers.get(TENANT_HEADER)
            return (normalize_tenant(raw),
                    parse_priority(self.headers.get(PRIORITY_HEADER)))

        def _wants_stream(self, parts) -> bool:
            """Streamed response requested: ``POST /generate?stream=1``
            or ``Accept: text/event-stream`` (docs/serving.md)."""
            if parts is not None and parse_qs(parts.query).get(
                "stream", ["0"]
            )[0] not in ("0", ""):
                return True
            return "text/event-stream" in (
                self.headers.get("Accept") or ""
            )

        def _generate(self, parts=None):
            in_flight_gauge.add(1)
            t0 = time.monotonic()
            fut = None
            observed = False  # span + SLO recorded for this request
            parent = self._remote_parent_authed()
            tenant, priority = self._tenant_of()
            try:
                n = int(self.headers.get("Content-Length", 0))
                try:
                    req = json.loads(self.rfile.read(n) or b"{}")
                except json.JSONDecodeError as e:
                    return self._json(400, {"error": f"bad JSON: {e}"})
                # ---- validate BEFORE admission: a malformed request
                # must never occupy a queue slot or a decode ----
                try:
                    prompts_ids, mode = self._parse_prompts(req)
                    self._check_batch_cap(prompts_ids)
                    max_toks = clamp_max_tokens(
                        req.get("max_tokens"), server.gen.max_dec_len, cap
                    )
                    # finite floor AND server-side ceiling: an unbounded
                    # client deadline (or JSON Infinity) would pin the
                    # handler thread + connection for as long as the
                    # scheduler stays busy — the hung-connection mode
                    # this queue exists to prevent
                    deadline_s = self._read_deadline(
                        req.get("deadline_s", default_deadline_s)
                    )
                    trim, key = plan_request(
                        prompts_ids, max_toks, bucket=bucket, context=context
                    )
                except (ValueError, TypeError) as e:
                    return self._json(400, {"error": str(e)})
                if self._wants_stream(parts):
                    self._generate_stream(
                        prompts_ids, mode, trim, key, deadline_s,
                        parent, t0, tenant, priority,
                    )
                    observed = True  # the stream path did its accounting
                    return
                # ---- admission control ---- (a hop that arrived with
                # X-Trace-Id binds its parent so the attached trace is
                # force-sampled into the caller's stitched timeline)
                with remote_parent(parent):
                    fut = self._submit_guarded(
                        lambda: queue.submit(
                            prompts_ids, trim,
                            coalesce_key=key, deadline_s=deadline_s,
                            tenant=tenant, priority=priority,
                        ),
                        t0,
                    )
                if fut is None:
                    observed = True  # _submit_guarded answered + spent SLO
                    return
                # ---- wait, bounded by the deadline + scheduling slack:
                # an unanswerable request gets an honest 503, never a
                # hung connection ----
                rows = self._await_result(fut, deadline_s, t0)
                if rows is None:
                    observed = True  # _await_result spent the span + SLO
                    return
                if mode in ("prompt", "prompts"):
                    texts = [server.tokenizer.decode(r) for r in rows]
                    payload = ({"completion": texts[0]} if mode == "prompt"
                               else {"completions": texts})
                else:
                    payload = ({"completion_ids": rows[0]}
                               if mode == "prompt_ids"
                               else {"completions_ids": rows})
                if fut.trace is not None:
                    # the handle for GET /debug/trace?id= (sampled only)
                    payload["trace_id"] = fut.trace.trace_id
                latency_hist.observe(time.monotonic() - t0)
                _record_request_span(
                    reg, recorder, t0, fut, 200,
                    tokens=sum(len(r) for r in rows),
                )
                _slo_observe(200, fut, t0, tenant=tenant)
                observed = True
                return self._json(200, payload,
                                  headers=self._span_headers(fut, parent))
            except Exception as e:  # noqa: BLE001 — last-resort guard
                # a failure AFTER decode (tokenizer decode, payload
                # build) is still a failed request: it must spend SLO
                # budget and close its trace, or a bug here would be
                # invisible to the burn gauges exactly like the old
                # wedged-503 blind spot
                if not observed:
                    _record_request_span(reg, recorder, t0, fut, 500)
                    _slo_observe(500, fut, t0, tenant=tenant)
                return self._json(500, {"error": str(e)})
            finally:
                in_flight_gauge.add(-1)

        def _generate_stream(self, prompts_ids, mode, trim, key,
                             deadline_s, parent, t0,
                             tenant=None, priority=0):
            """SSE token streaming (docs/serving.md "Token streaming"):
            tokens leave the box as the engine commits them instead of
            when the row finishes.  The body is HTTP/1.0
            close-delimited (no Content-Length): ``event: token``
            frames carry ``{"row", "index", "tokens"}`` with per-row
            monotone indices, and a terminal ``event: summary`` frame
            carries usage plus — on authed traced hops — the span
            summaries the router stitches (the streamed stand-in for
            the X-Span-Summary header, which cannot be complete before
            the body starts).  Accounting: TTFT at the FIRST flush,
            per-gap ITL at every later flush, total latency at stream
            close; success-only, like the non-streamed path.  The
            coalesce scheduler has no per-step commit hook, so its
            stream degrades to a single flush at completion (same SSE
            framing either way)."""
            sink = SinkQueue()
            submit_kw = {"coalesce_key": key, "deadline_s": deadline_s,
                         "tenant": tenant, "priority": priority}
            if stream_capable:
                submit_kw["stream"] = (
                    lambda row, start, toks: sink.put((row, start, toks))
                )
            with remote_parent(parent):
                fut = self._submit_guarded(
                    lambda: queue.submit(prompts_ids, trim, **submit_kw),
                    t0,
                )
            if fut is None:
                return  # 429/503/400 answered + accounted
            try:
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                if fut.trace is not None:
                    self.send_header("X-Trace-Id", fut.trace.trace_id)
                self.end_headers()
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError, TimeoutError):
                client_gone.inc()
                queue.try_remove(fut)
                return
            itl_hist = reg.histogram("pfx_request_itl_seconds")
            ttft_hist = reg.histogram("pfx_request_ttft_seconds")
            first_flush = None
            last_flush = None
            flushes = 0
            sent_tokens = 0
            client_lost = False
            stream_err = None
            code = 200
            hard_deadline = t0 + deadline_s + shed_slack_s

            def emit(event, obj):
                nonlocal client_lost
                if client_lost:
                    return False
                frame = (f"event: {event}\n"
                         f"data: {json.dumps(obj)}\n\n").encode()
                try:
                    self.wfile.write(frame)
                    self.wfile.flush()
                    return True
                except (BrokenPipeError, ConnectionResetError,
                        TimeoutError):
                    client_gone.inc()
                    client_lost = True
                    return False

            def flush_tokens(row, start, toks):
                nonlocal first_flush, last_flush, flushes, sent_tokens
                now = time.monotonic()
                if first_flush is None:
                    # TTFT at the moment bytes actually leave for the
                    # client — not at future resolution
                    first_flush = now
                    ttft_hist.observe(max(0.0, now - t0))
                else:
                    itl_hist.observe(max(0.0, now - last_flush))
                last_flush = now
                flushes += 1
                sent_tokens += len(toks)
                obj = {"row": row, "index": start, "tokens": toks}
                if mode in ("prompt", "prompts"):
                    obj["text"] = server.tokenizer.decode(toks)
                return emit("token", obj)

            while not (fut.done() and sink.empty()):
                try:
                    row, start, toks = sink.get(timeout=0.05)
                except SinkEmpty:
                    if time.monotonic() > hard_deadline and not fut.done():
                        queue.try_remove(fut)  # shed it if still queued
                        code = 503
                        stream_err = f"deadline {deadline_s:g}s exceeded"
                        break
                    continue
                if not flush_tokens(row, start, toks):
                    break  # client hung up: stop draining, decode finishes
            rows = None
            if stream_err is None:
                try:
                    rows = fut.result(timeout=deadline_s + shed_slack_s)
                except DeadlineExceeded as e:
                    code, stream_err = 503, str(e)
                except QueueClosed as e:
                    code, stream_err = 503, str(e)
                except TimeoutError:
                    queue.try_remove(fut)
                    code = 503
                    stream_err = f"deadline {deadline_s:g}s exceeded"
                except ValueError as e:
                    code, stream_err = 400, str(e)
                except Exception as e:  # noqa: BLE001 — report, keep serving
                    code, stream_err = 500, str(e)
            if stream_err is not None:
                # mid-stream failure (deadline shed, eviction, drain):
                # an honest terminal error frame — status PLUS how many
                # tokens were already committed to the wire, so a
                # client whose row was evicted mid-decode always sees a
                # closed stream with an accounting, never a silent hang
                # (the status line already said 200 — SSE's reality)
                emit("error", {"error": stream_err, "code": code,
                               "tokens_committed": sent_tokens})
                _record_request_span(reg, recorder, t0, fut, code,
                                     tokens=sent_tokens or None,
                                     streamed=True)
                if code != 400:
                    _slo_observe(code, fut, t0, tenant=tenant)
                return
            if flushes == 0 and not client_lost:
                # single-flush degradation (coalesce scheduler, or a
                # zero-token completion): everything arrives at once,
                # in the same frame shape
                for i, r in enumerate(rows):
                    if not flush_tokens(i, 0, list(r)):
                        break
            # success epilogue: total latency at stream CLOSE (the
            # non-streamed path observes at response build — same
            # success-only rule), span + SLO with the first-flush TTFT
            latency_hist.observe(time.monotonic() - t0)
            _record_request_span(
                reg, recorder, t0, fut, 200,
                tokens=sum(len(r) for r in rows), streamed=True,
            )
            if first_flush is not None:
                reg.histogram(
                    "pfx_tenant_ttft_seconds",
                    tenant=tenant_labels.label(normalize_tenant(tenant)),
                ).observe(max(0.0, first_flush - t0))
            if slo.enabled:
                slo.observe_request(
                    ttft_s=(max(0.0, first_flush - t0)
                            if first_flush is not None else None),
                    ok=True, tenant=tenant,
                )
            summary = {
                "usage": {
                    "prompts": len(rows),
                    "tokens": sum(len(r) for r in rows),
                },
                "flushes": flushes,
            }
            if fut.trace is not None:
                summary["trace_id"] = fut.trace.trace_id
                if parent is not None:
                    # computed AFTER _record_request_span finished the
                    # trace, exactly like _span_headers on the
                    # non-streamed path
                    summary["spans"] = [span_summary(fut.trace)]
            emit("summary", summary)

        def _prefill(self):
            """POST /prefill (role=prefill): run one prompt's paged
            prefill and answer with the binary KV-handoff payload the
            router hands to a decode replica.  Same admission surface
            as /generate: bounded queue (429), deadlines (503 shed),
            graceful drain.

            With a ``forward`` placement ticket in the request (the
            router's direct-transfer topology), the payload is POSTed
            STRAIGHT to the named decode replica instead — handoff
            bytes never transit the router — and the decode replica's
            JSON completion is relayed back.  A send that provably
            failed before the decode replica read it degrades to the
            proxy leg (the payload is returned, octet-stream, for the
            router to carry); a send lost MID-exchange answers a
            structured 502 naming the decode leg, so the router can run
            its re-prefill failover without ever replaying at the dead
            replica."""
            from paddlefleetx_tpu.core.paged_cache import pack_handoff

            in_flight_gauge.add(1)
            t0 = time.monotonic()
            fut = None
            parent = remote_parent_from_headers(self.headers)
            tenant, priority = self._tenant_of()
            try:
                n = int(self.headers.get("Content-Length", 0))
                try:
                    req = json.loads(self.rfile.read(n) or b"{}")
                except json.JSONDecodeError as e:
                    return self._json(400, {"error": f"bad JSON: {e}"})
                try:
                    ids = req.get("prompt_ids")
                    if not ids:
                        raise ValueError("need a non-empty prompt_ids list")
                    prompt_ids = [int(t) for t in ids]
                    max_toks = clamp_max_tokens(
                        req.get("max_tokens"), server.gen.max_dec_len, cap
                    )
                    deadline_s = self._read_deadline(
                        req.get("deadline_s", default_deadline_s)
                    )
                    fwd = req.get("forward") or None
                    fwd_url = fwd_deadline = None
                    if fwd is not None:
                        fwd_url = str(fwd["url"])
                        fwd_deadline = self._read_deadline(
                            fwd.get("deadline_s", deadline_s)
                        )
                except (KeyError, ValueError, TypeError) as e:
                    return self._json(400, {"error": str(e)})
                with remote_parent(parent):
                    fut = self._submit_guarded(
                        lambda: queue.submit(
                            [prompt_ids], max_toks,
                            coalesce_key=None, deadline_s=deadline_s,
                            tenant=tenant, priority=priority,
                        ),
                        t0,
                    )
                if fut is None:
                    return
                exports = self._await_result(fut, deadline_s, t0)
                if exports is None:
                    return
                payload = pack_handoff(*exports[0])
                if fwd_url is not None:
                    # the ticket's deadline burns down with queue wait
                    # and prefill compute: hand the decode replica only
                    # what is LEFT, and shed honestly when the export
                    # itself spent the budget — nothing was adopted
                    # anywhere, and the router has given up on its own
                    # clock already
                    fwd_left = fwd_deadline - (time.monotonic() - t0)
                    if fwd_left <= 0:
                        _record_request_span(reg, recorder, t0, fut, 503)
                        _slo_observe(503, fut, t0, tenant=tenant)
                        return self._json(503, {
                            "error": "deadline exhausted after prefill "
                                     "export (forward ticket spent)",
                        })
                    fwd_tenant = {
                        h: v for h, v in (
                            (TENANT_HEADER,
                             self.headers.get(TENANT_HEADER)),
                            (PRIORITY_HEADER,
                             self.headers.get(PRIORITY_HEADER)),
                        ) if v
                    }
                    code, body, ctype, headers = _direct_handoff(
                        payload, fwd_url, fwd_left, parent=parent,
                        extra_headers=fwd_tenant,
                    )
                    latency_hist.observe(time.monotonic() - t0)
                    _record_request_span(reg, recorder, t0, fut, code)
                    # every 5xx here is a DECODE-side verdict (a death
                    # report or a relayed decode error; this replica's
                    # own failures take the generic 500 path below) and
                    # must not spend the PREFILL SLO budget: the breach
                    # signal is always live, and burning it here would
                    # scale the prefill pool on decode-pool failures
                    _slo_observe(200 if code >= 500 else code, fut, t0,
                                 tenant=tenant)
                    # append THIS replica's summary to the decode leg's
                    # (carried back by _direct_handoff): one relayed
                    # header stitches both legs at the router
                    carried = (headers or {}).get(SPAN_SUMMARY_HEADER)
                    span_h = self._span_headers(fut, parent, carried)
                    if span_h or headers:
                        headers = {**(headers or {}), **(span_h or {})}
                    return self._send(code, body, ctype, headers)
                latency_hist.observe(time.monotonic() - t0)
                _record_request_span(reg, recorder, t0, fut, 200)
                _slo_observe(200, fut, t0, tenant=tenant)
                return self._send(
                    200, payload, "application/octet-stream",
                    headers=self._span_headers(fut, parent),
                )
            except Exception as e:  # noqa: BLE001 — last-resort guard
                _record_request_span(reg, recorder, t0, fut, 500)
                _slo_observe(500, fut, t0, tenant=tenant)
                return self._json(500, {"error": str(e)})
            finally:
                in_flight_gauge.add(-1)

        def _decode(self, parts):
            """POST /decode (role=decode): adopt a KV-handoff payload
            into the continuous scheduler's arena and decode it to
            completion — the other half of the disaggregated topology.
            ``?deadline_s=`` rides the query string (the body is the
            binary payload)."""
            from paddlefleetx_tpu.core.paged_cache import unpack_handoff

            in_flight_gauge.add(1)
            t0 = time.monotonic()
            fut = None
            parent = remote_parent_from_headers(self.headers)
            tenant, priority = self._tenant_of()
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(n)
                # handoff bytes through THIS replica, by transport: the
                # direct-transfer acceptance evidence (router-side byte
                # counters stay flat while these account the payload)
                transport = (self.headers.get("X-Handoff-Transport")
                             or "proxy")
                reg.counter(
                    "pfx_handoff_bytes_total",
                    transport="direct" if transport == "direct"
                    else "proxy",
                ).inc(len(body))
                try:
                    raw = (parse_qs(parts.query).get("deadline_s")
                           or [default_deadline_s])[0]
                    deadline_s = self._read_deadline(raw)
                    meta, arrays = unpack_handoff(body)
                except (ValueError, TypeError) as e:
                    return self._json(400, {"error": str(e)})
                with remote_parent(parent):
                    fut = self._submit_guarded(
                        lambda: queue.submit_handoff(
                            meta, arrays, deadline_s=deadline_s,
                            tenant=tenant, priority=priority,
                        ),
                        t0,
                    )
                if fut is None:
                    return
                rows = self._await_result(fut, deadline_s, t0)
                if rows is None:
                    return
                payload = {"completion_ids": rows[0]}
                if fut.trace is not None:
                    payload["trace_id"] = fut.trace.trace_id
                latency_hist.observe(time.monotonic() - t0)
                _record_request_span(
                    reg, recorder, t0, fut, 200, tokens=len(rows[0])
                )
                _slo_observe(200, fut, t0, tenant=tenant)
                return self._json(200, payload,
                                  headers=self._span_headers(fut, parent))
            except Exception as e:  # noqa: BLE001 — last-resort guard
                _record_request_span(reg, recorder, t0, fut, 500)
                _slo_observe(500, fut, t0, tenant=tenant)
                return self._json(500, {"error": str(e)})
            finally:
                in_flight_gauge.add(-1)

    class Server(ThreadingHTTPServer):
        # NON-daemon handler threads: socketserver only tracks (and
        # server_close only joins) non-daemon threads, and the drain
        # contract requires every admitted request's response bytes to be
        # written before the process exits.  A wedged handler cannot block
        # a force-quit — the second signal's default SIGTERM action kills
        # the process without waiting on threads — and the Handler socket
        # timeout bounds how long a stalled client can delay a drain.
        daemon_threads = False
        block_on_close = True  # graceful drain joins in-flight responses

        def handle_error(self, request, client_address):
            exc = sys.exc_info()[1]
            if isinstance(exc, (BrokenPipeError, ConnectionResetError,
                                TimeoutError)):
                client_gone.inc()
                return
            super().handle_error(request, client_address)

    httpd = Server((host, port), Handler)

    def _watchdog():
        # a generation stuck past the watchdog budget flips /healthz to
        # degraded (ok=false) so orchestrators stop routing here; flips
        # back if the scheduler ever comes unstuck
        while not stop_event.wait(1.0):
            busy = queue.busy_seconds()
            if busy > watchdog_s and not flags["degraded"]:
                flags["degraded"] = True
                degraded_gauge.set(1)
                print(
                    f"WATCHDOG: generation wedged for {busy:.0f}s "
                    f"(budget {watchdog_s:.0f}s); /healthz degraded",
                    flush=True,
                )
                # postmortem while the wedge is live: the dump carries
                # the degrade event plus the last N request spans, so a
                # later kill -9 still leaves evidence on disk
                recorder.record({
                    "event": "watchdog_degraded",
                    "busy_s": round(busy, 3),
                    "budget_s": watchdog_s,
                })
                recorder.dump(reason="watchdog_degraded")
            elif flags["degraded"] and busy < watchdog_s:
                # recovered: the wedged generation finished.  Compare
                # against the budget, not exact idle — under a steady
                # backlog a 1 Hz sampler may never catch busy == 0
                flags["degraded"] = False
                degraded_gauge.set(0)
                recorder.record({"event": "watchdog_recovered"})
                print("WATCHDOG: generation recovered; /healthz ok",
                      flush=True)

    orig_handlers = {}
    drain_lock = threading.Lock()

    def _migrate_prefixes(peers) -> None:
        """Drain-time KV migration (docs/serving.md "KV lifecycle"):
        ship the hottest published prefixes to the first surviving peer
        that will take them.  STRICTLY best-effort and deadline-bounded
        — runs AFTER queue.join() (the scheduler thread has exited, so
        the index walk is single-threaded) and BEFORE httpd.shutdown(),
        and NO failure mode here may stall the drain contract: every
        send is capped by what remains of ``PFX_MIGRATE_DEADLINE_S``,
        a wedged receiver (PFX_FAULT=migrate_stall) burns the budget
        and the drain proceeds, and any exception is caught by the
        caller.  Counters: pfx_migrate_sent_total on the accepted send,
        pfx_migrate_failed_total when no peer adopted."""
        import urllib.request

        from paddlefleetx_tpu.core.paged_cache import pack_handoff
        from paddlefleetx_tpu.core.router import admin_headers
        from paddlefleetx_tpu.utils.resilience import maybe_fire

        deadline_s = float(os.environ.get("PFX_MIGRATE_DEADLINE_S",
                                          "10") or 10)
        top = int(os.environ.get("PFX_MIGRATE_TOP", "64") or 64)
        t_end = time.monotonic() + max(0.0, deadline_s)
        export = engine.export_hot_prefixes(top)
        if export is None:
            return  # nothing cached — nothing to migrate
        payload = pack_handoff(*export)
        nblocks = len(export[0]["prefixes"])
        attempts = 0
        for peer in peers:
            url = peer.rstrip("/") + "/admin/adopt_prefixes"
            backoff = 0.2
            for _ in range(2):  # bounded retry per peer
                left = t_end - time.monotonic()
                if left <= 0:
                    break
                attempts += 1
                if maybe_fire("migrate_stall", attempts):
                    # a wedged receiver, modeled here at the send site:
                    # the hang is capped at the REMAINING migration
                    # budget, so the drain deadline holds no matter
                    # what PFX_FAULT_HANG_S says
                    hang = float(os.environ.get("PFX_FAULT_HANG_S",
                                                "30") or 30)
                    time.sleep(min(hang,
                                   max(0.0, t_end - time.monotonic())))
                    left = t_end - time.monotonic()
                    if left <= 0:
                        break
                try:
                    req = urllib.request.Request(
                        url, data=payload, method="POST",
                        headers={
                            "Content-Type": "application/octet-stream",
                            **admin_headers(),
                        },
                    )
                    with urllib.request.urlopen(
                        req, timeout=max(0.1, left)
                    ) as resp:
                        body = json.loads(resp.read() or b"{}")
                    adopted = int(body.get("adopted_blocks", 0))
                    reg.counter("pfx_migrate_sent_total").inc()
                    recorder.record({
                        "event": "migrate_sent", "peer": peer,
                        "blocks": nblocks, "adopted_blocks": adopted,
                    })
                    print(
                        f"migrate: {peer} adopted {adopted} of "
                        f"{nblocks} prefix block(s)", flush=True,
                    )
                    return
                except Exception as e:  # noqa: BLE001 — ladder moves on
                    print(f"migrate: send to {peer} failed ({e})",
                          flush=True)
                    time.sleep(min(backoff,
                                   max(0.0,
                                       t_end - time.monotonic())))
                    backoff *= 2
        reg.counter("pfx_migrate_failed_total").inc()
        recorder.record({"event": "migrate_failed",
                         "peers": list(peers), "blocks": nblocks})
        print(
            f"migrate: no surviving peer adopted within "
            f"{deadline_s:g}s; {nblocks} prefix block(s) will be "
            f"recomputed on demand", flush=True,
        )

    # -- replica self-registration (docs/serving.md "Control-plane
    # recovery"): with --router-url, this replica announces itself to
    # the router on an admin-gated heartbeat, so a router restarted with
    # a lost or stale journal rediscovers the fleet from the replicas
    # themselves; on drain it says goodbye instead of making the router
    # wait out --eject-after failed polls ---------------------------------
    advertise_host = ("127.0.0.1" if host in ("0.0.0.0", "::", "")
                      else host)
    advertise_url = f"http://{advertise_host}:{port}"

    def _post_register(payload: dict, timeout: float) -> None:
        import urllib.request

        from paddlefleetx_tpu.core.router import admin_headers

        req = urllib.request.Request(
            router_url.rstrip("/") + "/admin/register",
            data=json.dumps(payload).encode(), method="POST",
            headers={"Content-Type": "application/json",
                     **admin_headers()},
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            resp.read()

    def _register_heartbeat():
        interval = float(os.environ.get("PFX_REGISTER_INTERVAL_S", "2")
                         or 2)
        warned = False
        payload = {"url": advertise_url, "role": role,
                   "identity": identity}
        while not stop_event.is_set() and not flags["draining"]:
            try:
                _post_register(payload, timeout=5.0)
                warned = False
            except Exception as e:  # noqa: BLE001 — best-effort forever
                if not warned:
                    warned = True
                    print(
                        f"register: heartbeat to {router_url} failed "
                        f"({e}); retrying every {interval:g}s",
                        flush=True,
                    )
            stop_event.wait(interval)

    def _deregister_from_router():
        """Best-effort goodbye on drain exit — identity rides along so
        a delayed goodbye can never eject a redeployed successor."""
        try:
            _post_register({"deregister": True, "url": advertise_url,
                            "identity": identity}, timeout=3.0)
            print("register: deregistered from router", flush=True)
        except Exception as e:  # noqa: BLE001 — the drain must finish
            print(
                f"register: deregister failed ({e}); the router will "
                "eject this replica after failed polls", flush=True,
            )

    def initiate_drain(source: str, migrate_to=()) -> bool:
        """THE drain initiation, shared by the signal handler and the
        authenticated ``POST /admin/drain`` (the remote transport that
        makes rolling deploys work cross-host): close admission, answer
        every admitted request, exit 0 — the PR 3 contract unchanged.
        ``migrate_to`` (surviving-peer base URLs from the drain body)
        additionally ships the hottest published prefixes to a peer
        before the listener dies — best-effort, hard-bounded by
        PFX_MIGRATE_DEADLINE_S, and NEVER able to fail the drain.
        Idempotent: returns False when a drain is already underway."""
        with drain_lock:
            if flags["draining"]:
                return False
            flags["draining"] = True
        draining_gauge.set(1)
        recorder.record({"event": "drain_start", "source": source,
                         "queued": queue.depth(),
                         "migrate_to": list(migrate_to)})
        print(
            f"{source}: draining — admission closed, "
            f"{queue.depth()} queued request(s) will finish",
            flush=True,
        )

        def _drain():
            queue.close()
            queue.join()
            if migrate_to and engine is not None:
                try:
                    _migrate_prefixes(migrate_to)
                except Exception as e:  # noqa: BLE001 — drain wins
                    reg.counter("pfx_migrate_failed_total").inc()
                    print(f"migrate: failed ({e}); drain continues",
                          flush=True)
            if router_url:
                _deregister_from_router()
            httpd.shutdown()

        threading.Thread(target=_drain, name="serve-drain",
                         daemon=True).start()
        return True

    def _on_signal(signum, frame):
        # mirror the PR 2 engine contract: first signal drains (stop
        # admitting -> finish admitted work -> exit 0), handlers are
        # restored immediately so a second signal force-quits
        for sig, h in orig_handlers.items():
            signal.signal(sig, h)
        if initiate_drain(f"signal {signum}"):
            print("(send again to force-quit)", flush=True)

    try:
        for sig in (signal.SIGTERM, signal.SIGINT):
            orig_handlers[sig] = signal.signal(sig, _on_signal)
    except ValueError:
        print("warning: not on the main thread; graceful drain handlers "
              "unavailable", flush=True)

    if cb_warmup and role == "prefill":
        # compile the prefill-export family per bucket before the
        # listener opens (blocks are freed per export — nothing stays)
        queue.engine.warmup_prefill([int(n) for n in cb_warmup])
    elif cb_warmup and scheduler == "continuous":
        # compile (prefill, step) per bucket BEFORE the listener opens —
        # the continuous counterpart of the coalesce-path server.warmup
        queue.warmup([int(n) for n in cb_warmup])
    queue.start()
    threading.Thread(target=_watchdog, name="serve-watchdog",
                     daemon=True).start()
    if router_url:
        threading.Thread(target=_register_heartbeat,
                         name="serve-register", daemon=True).start()
    endpoint = {"prefill": "POST /prefill", "decode": "POST /decode + /generate"}.get(
        role, "POST /generate"
    )
    print(
        f"serving on {host}:{port} ({endpoint}, GET /healthz; "
        f"role {role}, replica {identity['replica_id']}, "
        f"scheduler {identity['scheduler']}, queue depth {queue_depth}, "
        f"coalesce {max_coalesce}, "
        f"deadline {default_deadline_s:g}s, watchdog {watchdog_s:g}s)",
        flush=True,
    )
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        # second Ctrl-C (the first restored default handlers): honor the
        # promised force-quit.  server_close would join non-daemon
        # handler threads — one blocked on a wedged decode would hold
        # the process for up to max_deadline + slack instead of quitting.
        print("force-quit on second interrupt", flush=True)
        # last act before the hard exit: the flight recorder ring (request
        # spans, watchdog events, the drain attempt) becomes a postmortem
        recorder.record({"event": "force_quit", "signum": int(signal.SIGINT)})
        recorder.dump(reason="force_quit")
        os._exit(130)
    finally:
        stop_event.set()
        # joins in-flight handler threads: every admitted request gets
        # its response bytes before the process exits
        httpd.server_close()
    if flags["draining"]:
        print("drained cleanly: all admitted requests answered", flush=True)
    return 0


def _csv_ints(raw: str):
    return [int(x) for x in raw.split(",") if x.strip()]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("-c", "--config", required=True)
    ap.add_argument("-o", "--override", action="append", default=[])
    ap.add_argument("--port", type=int, default=0, help="HTTP port (0 = stdin REPL)")
    # loopback by default: the endpoint is unauthenticated, so exposing it
    # on all interfaces must be an explicit operator decision
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address (use 0.0.0.0 to expose externally)")
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--warmup-buckets", default="",
                    help="comma-separated prompt-length buckets to compile "
                    "at boot (default: 8); warmup fails loudly if any "
                    "bucket cannot compile")
    ap.add_argument("--warmup-batches", default="",
                    help="comma-separated batch-size buckets to warm per "
                    "prompt bucket (default under --port: powers of two "
                    "up to --max-coalesce, so the first coalesced burst "
                    "never pays a mid-traffic compile; default REPL: 1)")
    ap.add_argument("--queue-depth", type=int, default=64,
                    help="bounded admission queue depth; a request "
                    "arriving when full gets HTTP 429 + Retry-After")
    ap.add_argument("--max-coalesce", type=int, default=8,
                    help="max prompts merged into one batched decode "
                    "(same-bucket waiting requests coalesce)")
    ap.add_argument("--deadline", type=float, default=120.0,
                    help="default per-request deadline seconds (client "
                    "overrides with deadline_s); expired requests are "
                    "shed with HTTP 503 before a decode is wasted")
    ap.add_argument("--max-deadline", type=float, default=600.0,
                    help="server-side ceiling on client deadline_s — an "
                    "unbounded deadline would pin a handler thread and "
                    "its connection indefinitely")
    ap.add_argument("--shed-slack", type=float, default=2.0,
                    help="scheduling slack added to the deadline before "
                    "the handler gives up waiting and sheds with 503")
    ap.add_argument("--watchdog", type=float, default=300.0,
                    help="seconds a single generation may run before "
                    "/healthz flips to degraded (wedged-decode detector)")
    ap.add_argument("--max-tokens-cap", type=int, default=0,
                    help="hard per-request max_tokens ceiling (0 = use "
                    "Generation.max_tokens_cap from the config, which "
                    "defaults to uncapped-within-context)")
    ap.add_argument("--scheduler", choices=("coalesce", "continuous"),
                    default="coalesce",
                    help="serving scheduler: 'coalesce' batches same-"
                    "bucket WAITING requests (PR 3); 'continuous' is "
                    "iteration-level scheduling over the block-paged KV "
                    "cache — requests join/leave the running decode "
                    "batch at step boundaries (docs/serving.md; flips "
                    "to default after chip-window soak)")
    ap.add_argument("--cb-batch", type=int, default=8,
                    help="continuous scheduler: running-batch row "
                    "capacity (fixed compile shape)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="continuous scheduler: total KV arena blocks "
                    "(0 = auto: cb-batch full-context rows + null "
                    "block); the block size is the model's own "
                    "(GPTConfig.kv_block_default), else 16")
    ap.add_argument("--prefix-cache-blocks", type=int, default=0,
                    help="continuous scheduler: shared-prefix KV cache "
                    "budget in arena blocks (finished rows publish their "
                    "prompt-prefix blocks; later admissions reuse them "
                    "and prefill only the suffix; 0 disables — "
                    "docs/serving.md)")
    ap.add_argument("--prefix-spill-bytes", type=int, default=0,
                    help="continuous scheduler: host-RAM budget (bytes) "
                    "for the prefix-spill tier — LRU-evicted prefix "
                    "blocks demote to pinned host memory and readmit "
                    "on a later prefix match instead of recomputing "
                    "(requires --prefix-cache-blocks; 0 disables — "
                    "docs/serving.md 'KV lifecycle')")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="continuous scheduler: admit long prompts in "
                    "chunks of this many tokens (multiple of the "
                    "KV block size), one chunk per scheduler iteration "
                    "interleaved with decode steps; 0 = monolithic "
                    "prefill")
    ap.add_argument("--draft-k", type=int, default=-1,
                    help="speculative decoding: draft tokens per verify "
                    "step (overrides Generation.speculative.draft_k; "
                    "0 disables, -1 = leave the config value)")
    ap.add_argument("--kv-dtype", choices=("bf16", "int8"), default="",
                    help="KV-cache storage dtype (overrides Generation."
                    "speculative.kv_dtype; int8 halves decode HBM "
                    "bytes — docs/decode_path.md)")
    ap.add_argument("--slo-ttft-p99", type=float, default=0.0,
                    help="SLO objective: p99 time-to-first-token seconds "
                    "(0 = off).  Breach when >1%% of requests exceed it "
                    "on EVERY --slo-windows window — /healthz grows an "
                    "'slo' block and pfx_slo_* gauges appear in /metrics")
    ap.add_argument("--slo-error-rate", type=float, default=0.0,
                    help="SLO objective: allowed fraction of failed "
                    "requests (429/500/503; 0 = off), burn-rate "
                    "evaluated like --slo-ttft-p99")
    ap.add_argument("--slo-windows", default="60,600",
                    help="comma-separated rolling burn-rate window "
                    "seconds, short first (default 60,600)")
    ap.add_argument("--role", choices=("monolith", "prefill", "decode"),
                    default="monolith",
                    help="disaggregated serving role (docs/serving.md "
                    "'Multi-host serving'): 'prefill' serves POST "
                    "/prefill (prompt -> KV-handoff payload), 'decode' "
                    "adopts payloads via POST /decode and decodes them "
                    "on the continuous scheduler; 'monolith' (default) "
                    "is the single-process path")
    ap.add_argument("--replica-id", default="",
                    help="stable identity for the /healthz identity "
                    "block (default host:port) — how tools/router.py "
                    "and humans tell replicas apart")
    ap.add_argument("--tenants", default="",
                    help="per-tenant weight/quota config JSON "
                    "(docs/serving.md 'Multi-tenant isolation'); the "
                    "scheduler serves tenants deficit-round-robin by "
                    "weight; unset = one anonymous tenant, FCFS")
    ap.add_argument("--preempt-min-tokens", type=int, default=8,
                    help="protected minimum progress: an active row "
                    "must have committed at least this many tokens "
                    "since its last admission before a higher-priority "
                    "arrival may preempt it")
    ap.add_argument("--router-url", default="",
                    help="base URL of the fleet router (e.g. "
                    "http://127.0.0.1:8000): this replica self-registers "
                    "on an admin-gated POST /admin/register heartbeat "
                    "(every PFX_REGISTER_INTERVAL_S seconds) so a "
                    "restarted router rediscovers the fleet even with a "
                    "lost journal, and deregisters on drain exit instead "
                    "of waiting out the router's --eject-after "
                    "(docs/serving.md 'Control-plane recovery')")
    args = ap.parse_args(argv)
    # crash-loop fault site (PFX_FAULT=boot_crash:0, docs/
    # fault_tolerance.md): a replica that can never come up — drives
    # the supervisor's flap-budget quarantine drill
    from paddlefleetx_tpu.utils.resilience import maybe_fire

    maybe_fire("boot_crash", 0)
    # spec/quant CLI flags become plain config overrides so BOTH
    # schedulers (GenerationServer + PagedDecodeEngine read the same
    # Generation.speculative section) see one source of truth
    if args.draft_k >= 0:
        args.override.append(f"Generation.speculative.draft_k={args.draft_k}")
    if args.kv_dtype:
        args.override.append(f"Generation.speculative.kv_dtype={args.kv_dtype}")

    if args.role != "monolith" and not args.port:
        ap.error(f"--role {args.role} requires --port (HTTP serving); "
                 "the stdin REPL has no handoff transport")
    if args.role == "decode" and args.scheduler != "continuous":
        # adoption needs the paged arena + iteration-level scheduler;
        # force it loudly instead of booting a replica that 400s
        print(
            "note: --role decode forces --scheduler continuous "
            "(KV-handoff adoption runs on the paged engine)",
            file=sys.stderr, flush=True,
        )
        args.scheduler = "continuous"

    if args.scheduler == "continuous" and not args.port:
        # the REPL serves one prompt at a time through the contiguous
        # path — iteration-level scheduling only exists behind --port.
        # Fall back loudly rather than silently skipping warmup.
        print(
            "warning: --scheduler continuous requires --port (HTTP "
            "serving); REPL mode uses the contiguous path",
            file=sys.stderr, flush=True,
        )
        args.scheduler = "coalesce"

    server = build_server(args.config, args.override)
    if not args.no_warmup and (
        args.scheduler == "continuous" or args.role == "prefill"
    ):
        # the coalesce-path warmup would compile artifacts continuous/
        # prefill serving never calls; the engine warms its own families
        # inside serve_http before the listener opens
        pass
    elif not args.no_warmup:
        batches = _csv_ints(args.warmup_batches)
        if not batches and args.port:
            # HTTP serving coalesces: warm every power-of-two batch
            # bucket a coalesced burst can land on, so the first burst
            # rides compiled artifacts instead of paying a mid-traffic
            # compile on the single scheduler thread
            b, batches = 1, []
            while b < max(1, args.max_coalesce):
                batches.append(b)
                b *= 2
            batches.append(b)
        server.warmup(
            _csv_ints(args.warmup_buckets) or [8],
            batch_sizes=batches or [1],
        )

    if args.port:
        cb_warmup = ()
        if not args.no_warmup and (
            args.scheduler == "continuous" or args.role == "prefill"
        ):
            cb_warmup = tuple(_csv_ints(args.warmup_buckets) or [8])
        return serve_http(
            server, args.port, args.host,
            queue_depth=args.queue_depth,
            max_coalesce=args.max_coalesce,
            default_deadline_s=args.deadline,
            max_deadline_s=args.max_deadline,
            shed_slack_s=args.shed_slack,
            watchdog_s=args.watchdog,
            max_tokens_cap=args.max_tokens_cap,
            scheduler=args.scheduler,
            cb_batch=args.cb_batch,
            kv_blocks=args.kv_blocks,
            prefix_cache_blocks=args.prefix_cache_blocks,
            prefill_chunk=args.prefill_chunk,
            prefix_spill_bytes=args.prefix_spill_bytes,
            cb_warmup=cb_warmup,
            slo_ttft_p99_s=args.slo_ttft_p99,
            slo_error_rate=args.slo_error_rate,
            slo_windows_s=tuple(
                float(x) for x in args.slo_windows.split(",") if x.strip()
            ),
            role=args.role,
            replica_id=args.replica_id,
            tenants_path=args.tenants,
            preempt_min_tokens=args.preempt_min_tokens,
            router_url=args.router_url,
        )

    # REPL: one prompt per line -> completion (ids mode when no tokenizer)
    try:
        print("prompt> ", end="", flush=True)
        for line in sys.stdin:
            line = line.strip()
            if not line:
                break
            try:
                if server.tokenizer is not None:
                    print(server.generate_text([line])[0], flush=True)
                else:
                    ids = [int(t) for t in line.split()]
                    print(" ".join(map(str, server.generate_ids([ids])[0])),
                          flush=True)
            except ValueError as e:  # bad ids / empty prompt: report, keep serving
                print(f"error: {e}", flush=True)
            except Exception as e:  # noqa: BLE001 — a tokenizer/runtime
                # failure is reported without tearing down the session
                print(f"generation failed ({type(e).__name__}): {e}",
                      flush=True)
            print("prompt> ", end="", flush=True)
    except (EOFError, KeyboardInterrupt):
        pass  # clean exit on ^C / closed stdin
    print("", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
