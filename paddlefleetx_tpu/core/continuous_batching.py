"""Continuous batching: iteration-level scheduling over a paged KV cache.

The PR 3 coalescer (`core/request_queue.py`) merges requests that happen
to be WAITING together — a request arriving one token after a decode
started waits the entire decode (head-of-line blocking).  Orca's
iteration-level scheduling (Yu et al., OSDI 2022) fixes that by making
the decode STEP the scheduling unit: at every step boundary the running
batch can admit new rows (prefill-on-admit) and retire finished or shed
ones.  vLLM's PagedAttention (Kwon et al., SOSP 2023) supplies the
memory model that makes mid-flight membership cheap: each row owns a
block table into a shared arena (`core/paged_cache.py`), so admission
allocates blocks, eviction frees them, and no row pays another row's
length.

Two layers here:

  - :class:`PagedDecodeEngine` — the device side: owns the arena
    (`PagedPools`), the per-slot row state, and the compiled
    (prefill, step) functions.  ONE fixed-shape step per
    (batch capacity, table-width bucket): batch capacity is static,
    table width buckets to the next power of two of the widest active
    row's allocation (which only changes at admit/evict), so the
    retrace count is bounded by the bucket count and counted in
    ``stats["traces"]`` exactly like `core/serving.py`.
  - :class:`ContinuousScheduler` — the host side: the same admission
    surface as :class:`~paddlefleetx_tpu.core.request_queue.RequestQueue`
    (bounded ``submit`` -> 429/503, deadlines, ``try_remove``, graceful
    ``close``/``join`` drain, ``busy_seconds`` wedge probe) so
    `tools/serve.py` swaps schedulers behind ``--scheduler`` without
    touching the HTTP layer.  Its loop runs one iteration per decode
    step: shed expired waiting entries, EVICT expired active rows
    (mid-decode — their blocks return to the pool immediately), admit
    from the queue head while slots and blocks allow, then step.

Observability (docs/observability.md): the scheduler appends one
structured row per iteration to a bounded **decision log** (admissions,
evictions, sheds, block/width-bucket state, spec proposed/accepted
deltas — replaying an untruncated log reproduces
pfx_prefill_admits_total / pfx_request_evictions_total /
pfx_spec_accepted_total EXACTLY via `utils/tracing.replay_decision_log`;
shed rows cover scheduler-side sheds, while a handler-side
``try_remove`` shed lands between iterations and only in the counter),
stamps sampled
per-request trace contexts (admission → prefill → per-chunk decode),
and publishes a read-only ``debug_state()`` snapshot (queue ages,
per-row positions/budgets, arena occupancy, compile-key families) that
`tools/serve.py` exposes as ``GET /debug/state`` without ever blocking
this thread.

Dispatch-ahead decode (docs/decode_path.md): under the scheduler the
engine leaves each dispatched step IN FLIGHT and fetches its sampled
tokens one call later, chaining the next dispatch on device-resident row
state — the host's scheduling work (the admission/eviction scans) runs
in the device's shadow instead of on the decode critical path.
Committed tokens can stream to a per-request sink as they land
(``submit(..., stream=...)``).  Decision-log rows account every event in
COMMIT order, so ``replay_decision_log`` folds to identical totals with
overlap on or off (``ContinuousScheduler(dispatch_ahead=False)`` and a
directly driven engine step synchronously: the reference the tests hold
the totals to).

Greedy outputs are token-identical to the sequential/coalesced path
(same logits-processor chain per row, per-row positions equal to the
contiguous path's real-token positions); sampling rows draw from a
per-step engine subkey — deterministic, but a different stream than the
contiguous path's.  Every PR 2/3 contract holds: admission bounds,
deadline shed (now also MID-decode via eviction), graceful drain, and
drop-donated-state-on-error (a step failure resets the arena rather
than ever reusing donation-invalidated pools).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from paddlefleetx_tpu.core.paged_cache import (
    BlockPoolExhausted,
    NULL_BLOCK,
    PagedCacheManager,
    blocks_for,
    check_handoff_meta,
    kv_block_size,
)
from paddlefleetx_tpu.core.request_queue import (
    DeadlineExceeded,
    QueueClosed,
    QueueFull,
    RequestFuture,
)
from paddlefleetx_tpu.ops.decode_attention import (
    kv_cache_dtype,
    mla_tokens_computed,
    paged_tokens_computed,
)
from paddlefleetx_tpu.ops.speculative import SpecConfig, ngram_propose_host
from paddlefleetx_tpu.parallel.sharding import place_on_mesh
from paddlefleetx_tpu.utils.log import logger
from paddlefleetx_tpu.utils.resilience import maybe_fire
from paddlefleetx_tpu.core.tenancy import (
    DEFAULT_TENANT,
    DeficitRoundRobin,
    TenantConfig,
    TenantLabelCap,
    normalize_tenant,
)
from paddlefleetx_tpu.utils.telemetry import (
    StallWatch,
    StatsView,
    _env_int,
    get_registry,
    ledger_span,
)
from paddlefleetx_tpu.utils.tracing import (
    attach_request_trace,
    discard_request_trace,
    get_trace_buffer,
)


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class ArenaReset(RuntimeError):
    """A donating dispatch failed and the arena was rebuilt: every row
    that was live died with it.  ``dead_rows`` lets the scheduler fail
    exactly the affected requests; the original failure is chained as
    ``__cause__``."""

    def __init__(self, msg: str, dead_rows: List["_Row"]) -> None:
        super().__init__(msg)
        self.dead_rows = dead_rows


class TokenGapBooks:
    """The token gap's books (docs/observability.md "Goodput ledger").

    A row's token gap is the time between two consecutive commits that
    each delivered it a frame.  Every gap is booked once, at the later
    commit, under what the interval between the two held: ``admission``
    (a prefill, a prefill chunk, an adoption or a COW copy was
    dispatched in it: the device spent part of it on somebody else's
    prompt), ``flush`` (no admission, but the step in flight was
    committed early for a membership change: the next step ran after
    the host instead of under it) or ``decode`` (neither).  A row's
    first frame, and its first after a commit it sat out (a chunked
    prefill, a preemption), is no gap and books nothing.

    Passive: fed by the scheduler thread alone, from stamps the loop
    already takes (``commit``: where the readback span ends), and read
    by ``ContinuousScheduler.collect`` without a lock.  It dispatches
    and fetches nothing, and a fault inside it is counted in ``errors``
    (``pfx_sched_gap_books_errors_total``) and logged once, never
    raised into the decode loop."""

    HELD = ("decode", "admission", "flush")

    def __init__(self) -> None:
        self.gaps: Dict[str, int] = dict.fromkeys(self.HELD, 0)
        self.seconds: Dict[str, float] = dict.fromkeys(self.HELD, 0.0)
        # host seconds of the admission path (the scheduler's stamps):
        # pfx_sched_admit_host_seconds_total
        self.admit_host_s = 0.0
        self.errors = 0
        self._held = "decode"
        self._t_prev = 0.0
        self._prev: frozenset = frozenset()  # seq ids framed last commit

    def note(self, held: str) -> None:
        """Something other than decoding happened since the last commit:
        an admission always marks the interval, a flush only one that
        holds no admission."""
        if held == "admission" or self._held == "decode":
            self._held = held

    def commit(self, t: float, framed: Sequence[int]) -> None:
        """One commit at stamp ``t`` delivered a frame to the rows
        ``framed`` (seq ids): those framed at the previous commit too
        book one gap each under what the interval held."""
        try:
            now = frozenset(framed)
            n = len(now & self._prev)
            if n:
                self.gaps[self._held] += n
                self.seconds[self._held] += n * (t - self._t_prev)
            self._prev, self._t_prev, self._held = now, t, "decode"
        except Exception as exc:  # noqa: BLE001 — the books never fail a step
            self._fault(exc)

    def admit_host(self, seconds: float) -> None:
        """Host seconds of one iteration's admissions, device unqueued."""
        self.admit_host_s += max(0.0, seconds)

    def _fault(self, exc: BaseException) -> None:
        # the interval in progress is lost, not mis-booked
        self._prev, self._held = frozenset(), "decode"
        self.errors += 1
        if self.errors == 1:
            logger.warning(
                f"token-gap books: {type(exc).__name__}: {exc}; counted in "
                "pfx_sched_gap_books_errors_total, serving goes on"
            )


@dataclasses.dataclass(eq=False)
class _Row:
    """One active decode row (slot) in the running batch."""

    seq_id: int
    entry: "_CBEntry"
    row_idx: int  # index into the entry's prompts
    prompt_len: int
    max_new: int
    table: List[int]
    tokens: List[int] = dataclasses.field(default_factory=list)
    # prompt ids kept host-side for the self-drafting n-gram lookup
    # (the speculative drafter reads prompt + tokens between steps)
    prompt_ids: List[int] = dataclasses.field(default_factory=list)
    # sampled deep-dive trace context (utils/tracing.py) or None: the
    # engine stamps prefill + per-chunk decode events onto it
    trace: Any = None
    # prefix reuse / chunked prefill (docs/serving.md): tokens matched
    # against the prefix index (their KV was mapped shared, never
    # recomputed), prompt tokens still to prefill, the per-row chunk
    # width its chunk compiles key on, and whether prefill finished
    # (only then is the row decode-active and its prefix publishable)
    prefix_hit: int = 0
    pending: List[int] = dataclasses.field(default_factory=list)
    prefill_pos: int = 0
    chunk: int = 0
    prefill_done: bool = True
    # a model with window layers: the row's ring of window-layer pages (the
    # second class; ``PagedCacheManager.ring``), fixed for its life
    ring: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass(eq=False)
class _CBEntry:
    """One admitted client request (1..n prompts, answered atomically)."""

    prompts: List[List[int]]
    max_new: int
    deadline: Optional[float]
    future: RequestFuture
    enqueued_at: float
    next_row: int = 0  # rows [0, next_row) admitted so far
    done_rows: int = 0
    results: List[Optional[List[int]]] = dataclasses.field(default_factory=list)
    # disaggregated serving: a (meta, arrays) KV-handoff payload instead
    # of a prompt to prefill — the admission loop ADOPTS the exported
    # blocks (engine.adopt) rather than running paged_prefill
    handoff: Optional[tuple] = None
    # token streaming (docs/serving.md): a callable
    # ``stream(row_idx, start, tokens)`` invoked on the SCHEDULER thread
    # as each step's commits land (start = index of tokens[0] in the
    # row's output so far).  Sinks must be fast and never raise into the
    # batch — the engine logs and drops a failing sink's push, the
    # tokens are already committed either way.
    stream: Optional[Any] = None
    # multi-tenant isolation (core/tenancy.py): the fair-share queue the
    # entry waits in and its priority class (higher may preempt lower)
    tenant: str = DEFAULT_TENANT
    priority: int = 0
    # preempt-resume state: tokens a preempted row had already committed
    # (row_idx -> tokens), and the row indices waiting to re-enter the
    # batch as re-prefill continuations.  The continuation's prompt is
    # ``prompts[row_idx] + row_prefill[row_idx]`` with the max_new
    # budget reduced by the committed count, so the resumed greedy
    # decode continues the undisturbed token stream exactly; results and
    # stream offsets are rebased onto the committed prefix below.
    row_prefill: Dict[int, List[int]] = dataclasses.field(default_factory=dict)
    requeue_rows: List[int] = dataclasses.field(default_factory=list)

    def __post_init__(self) -> None:
        self.results = [None] * len(self.prompts)

    def emit_stream(self, row_idx: int, start: int, tokens: List[int]) -> None:
        """Engine-side streaming hook: rebases ``start`` past any tokens
        this row streamed BEFORE a preemption, so SSE clients see one
        monotone token index across a preempt-resume."""
        base = len(self.row_prefill.get(row_idx, ()))
        self.stream(row_idx, base + start, tokens)

    def finished_tokens(self, row_idx: int, tokens: List[int]) -> List[int]:
        """The row's full output: preempt-committed prefix + the tokens
        decoded since the (last) resume."""
        pre = self.row_prefill.get(row_idx)
        return (pre + tokens) if pre else tokens


class PagedDecodeEngine:
    """Device-side continuous-batching engine over a GenerationServer's
    params/mesh/config.  Host code drives it one decode step at a time;
    all compiled shapes are bucketed and counted (``stats["traces"]``).

    The arena pools are DONATED through both compiled entry points
    (prefill writes blocks, the step writes one slot per row): any
    exception after a donating dispatch leaves the pools
    donation-invalidated, so :meth:`reset` rebuilds the arena and the
    caller fails the affected requests — never reuse a maybe-deleted
    buffer (the `core/serving.py` drop-on-error contract).
    """

    def __init__(self, server, *, max_batch: int = 8, block: int = 0,
                 num_blocks: int = 0, spec="auto", kv_dtype: str = "",
                 prefix_cache_blocks: int = 0,
                 prefill_chunk: int = 0,
                 prefix_spill_bytes: int = 0) -> None:
        from paddlefleetx_tpu.parallel.mesh import data_parallel_world

        self.server = server
        self.mcfg = server.module.config
        self.gen = server.gen
        self.ctx = server.ctx
        self.mesh = server.mesh
        self.bucket = server.bucket
        # what a page holds comes from the model: a latent page is one
        # vector a token, so it takes more tokens to be worth a DMA
        self.block = kv_block_size(block, default=self.mcfg.kv_block_default)
        # speculation + KV quantization: default ("auto"/"") inherits the
        # server's ALREADY-PARSED Generation.speculative settings (ONE
        # parse site — core/serving.py — so both schedulers can never
        # drift apart on the same config); explicit args override (None
        # disables speculation)
        if spec == "auto":
            spec = server.spec
        if spec is not None and not isinstance(spec, SpecConfig):
            raise ValueError(f"spec must be a SpecConfig or None, got {spec!r}")
        self.spec = spec
        self.kv_dtype = (
            kv_cache_dtype(kv_dtype) if kv_dtype else server.kv_dtype
        )
        context = int(self.mcfg.max_position_embeddings)
        self.max_row_blocks = blocks_for(
            context + (self.spec.draft_k if self.spec else 0), self.block
        )
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        dpw = data_parallel_world(self.mesh)
        # fixed batch capacity (dp-world multiple): the step's batch dim
        # NEVER changes shape, so traffic mix cannot key batch retraces
        self.capacity = -(-int(max_batch) // dpw) * dpw
        # a model with window layers keeps a SECOND class of pages, a ring of
        # ring_pages a row (docs/mellum2.md).  The arena divides between the
        # classes by what a full row of the served cap needs of each: on auto
        # every slot's growing pages and every slot's ring; under --kv-blocks
        # (the growing class's count) the rings of as many rows as that holds
        self.ring_pages = self.mcfg.ring_pages(self.block)
        ring_rows = self.capacity
        if num_blocks <= 0:
            num_blocks = self.capacity * self.max_row_blocks + 1
        elif self.ring_pages:
            ring_rows = min(self.capacity, max(1, (num_blocks - 1) // self.max_row_blocks))
        ring_blocks = ring_rows * self.ring_pages + 1 if self.ring_pages else 0
        # shared-prefix KV reuse + chunked prefill (docs/serving.md):
        # prefix_cache_blocks > 0 lets finished rows publish their
        # prompt-prefix blocks into a radix index later admissions map
        # as SHARED (refcounted) table entries; prefill_chunk > 0
        # (block-multiple) streams long prompts in chunk-sized pieces,
        # one per scheduler iteration, interleaved with decode steps
        if prefix_cache_blocks < 0:
            raise ValueError(
                f"prefix_cache_blocks must be >= 0, got {prefix_cache_blocks}"
            )
        if prefill_chunk and (prefill_chunk < self.block
                              or prefill_chunk % self.block):
            raise ValueError(
                f"prefill_chunk {prefill_chunk} must be 0 or a positive "
                f"multiple of the KV block size {self.block}"
            )
        self.prefill_chunk = int(prefill_chunk)
        # what a row keeps per batch SLOT beside its pages (a state-space
        # layer's recurrent state): () for a block whose every layer caches
        # tokens.  It lives in the pools, is overwritten by the prefill that
        # admits a row into the slot, and is carried and donated through
        # every dispatch with the arena (docs/decode_path.md)
        self.row_state = self.mcfg.row_state
        if not self.mcfg.classic_block:
            # the described block is served by prefill-on-admit and the
            # one-token decode step; what else the GPT-2 block's pools
            # offer is refused by name (docs/serving.md, ROADMAP queue 2)
            refused = {
                "--draft-k (speculative verify chunk)": self.spec is not None,
                "--kv-dtype int8": self.kv_dtype == "int8",
                "--prefix-cache-blocks": bool(prefix_cache_blocks),
                "--prefill-chunk": bool(prefill_chunk),
                "tensor parallelism (a mesh of more than one device)": self.ctx is not None,
            }
            for option, asked in refused.items():
                if asked:
                    raise ValueError(
                        f"{option} is not written for {self._unwritten_for()} yet; "
                        "serve this block without it")
        # host-RAM spill tier (docs/serving.md "KV lifecycle"): evicted
        # prefix blocks demote to a bounded host store and readmit on a
        # later match instead of recomputing.  Spilling without an index
        # to evict FROM is a config error, loudly
        if prefix_spill_bytes and not prefix_cache_blocks:
            raise ValueError(
                "prefix_spill_bytes requires prefix_cache_blocks > 0 "
                "(the spill tier shadows the radix index)"
            )
        self.cache = PagedCacheManager(
            num_blocks, self.block, prefix_blocks=prefix_cache_blocks,
            spill_bytes=prefix_spill_bytes,
            ring_blocks=ring_blocks, ring_pages=self.ring_pages,
        )
        if self.cache.spill.enabled:
            self.cache.prefix.spill_hook = self._spill_block
        self._spill_probes = 0
        import jax
        import jax.numpy as jnp

        self._jnp = jnp
        self._jax = jax
        self._init_device_state()
        B = self.capacity
        self.positions = np.zeros((B,), np.int32)
        self.gen_steps = np.zeros((B,), np.int32)
        self.max_news = np.zeros((B,), np.int32)
        self.forced_steps = np.zeros((B,), np.int32)
        self.active = np.zeros((B,), bool)
        self.slots: List[Optional[_Row]] = [None] * B
        self._seq_counter = 0
        self._compiled_step: Dict = {}
        self._compiled_prefill: Dict = {}
        self._compiled_adopt: Dict = {}
        self._compiled_chunk: Dict = {}
        self._compiled_copy = None
        # trace-time entries across the compiled families — the bounded-
        # retrace contract's probe, like GenerationServer.stats["traces"]
        # ("exports"/"adopts" count disaggregated KV handoffs served;
        # "prefill_tokens" counts prompt tokens actually COMPUTED — a
        # prefix hit's shared span never enters it, the reuse evidence;
        # "prefill_chunks" counts chunk dispatches)
        # ("host_gap_s" measures host time the device sat idle between
        # consuming one step's results and receiving the next dispatch —
        # the benchmark's sched.host_gap_share)
        # (goodput time-ledger accumulators: wall time THIS thread spent
        # in each phase — t_device_decode covers decode dispatches,
        # t_device_prefill every donating dispatch (prefill / chunk /
        # adopt / COW), t_readback the commit fetch barrier,
        # t_stream_flush the SSE sink calls.  The scheduler baseline-
        # diffs them per iteration, so warmup/driver time outside an
        # iteration never enters the ledger.  "ledger_admitted" counts
        # tokens COMMITTED into scheduler-owned rows — the token
        # ledger's admission side, folded by _fold_admitted())
        # (work counters, one update per committed decode step:
        # "row_steps"/"slot_steps" = live rows / capacity, "kv_tokens" =
        # the live rows' context lengths, "grid_tokens" = the KV tokens
        # per head the paged kernel computed on: each LIVE slot's context
        # rounded up to the kernel's grid step — its grid visits the live
        # rows only; grid steps past a row's context run nothing, and the
        # latent kernel's work list has none of them)
        self.stats: Dict[str, Any] = {
            "traces": 0, "steps": 0, "prefills": 0,
            "spec_proposed": 0, "spec_accepted": 0,
            "exports": 0, "adopts": 0,
            "prefill_tokens": 0, "prefill_chunks": 0,
            "host_gap_s": 0.0,
            "migrate_adopted": 0,
            "t_device_decode": 0.0, "t_device_prefill": 0.0,
            "t_readback": 0.0, "t_stream_flush": 0.0,
            "ledger_admitted": 0,
            "row_steps": 0, "slot_steps": 0,
            "kv_tokens": 0, "grid_tokens": 0,
            # tokens the window layers' calls attended (a model with window
            # layers): the live rows' contexts, each capped at the window
            "kv_window_tokens": 0,
            # expert layers (prefills and decode steps, warm-up excluded):
            # pairs routed, pairs on held experts, the fullest held
            # expert's pairs x experts held (generation._moe_counts)
            "moe_pairs": 0, "moe_held_pairs": 0, "moe_held_max_pairs": 0,
            # grouped products over sorted pairs the prefills dispatched
            # (pfx_grouped_matmul: expert layers x matrices an expert, each)
            "moe_grouped_calls": 0,
            # state-space layers (warm-up excluded): live (row, step)
            # pairs x layers (what the state kernel visits), slots x
            # steps x layers (the capacity), prompt tokens x layers
            "ssm_row_steps": 0, "ssm_slot_steps": 0, "ssm_prefill_tokens": 0,
            # a residual stream of several copies (hc_mult; warm-up
            # excluded): tokens that went through the maps, once a forward
            # (a prefill's real prompt tokens, a decode step's live rows;
            # every token passes 2 sub-blocks x layers pairs of kernels)
            "hc_tokens": 0,
        }
        # True only inside warmup(): warmup admits/steps are not traffic
        # and must not bump the traffic-facing registry counters (the
        # decision-log replay must reproduce them EXACTLY)
        self._warmup = False
        self._key = jax.random.fold_in(
            jax.random.key(int(server.cfg.get("Global", {}).get("seed", 0))),
            0x9a6ed,
        )
        # decode_step never reads max_dec_len (budgets are per-row DATA):
        # normalize it out of the compile key
        self._gen_key = dataclasses.replace(self.gen, max_dec_len=0)
        # dispatch-ahead decode (docs/decode_path.md): when True, step()
        # leaves the dispatched step IN FLIGHT and fetches its sampled
        # tokens on the NEXT call (or at flush()), so host scheduling
        # work runs in the device's shadow.  Defaults to synchronous —
        # direct drivers (tests, benches) see tokens after every call;
        # ContinuousScheduler turns it on.
        self.dispatch_ahead = False
        self._inflight: Optional[Dict[str, Any]] = None
        self._t_results: Optional[float] = None
        self._moe_pending: List[Any] = []  # expert counts not fetched yet
        # every token gap booked by what its interval held (TokenGapBooks):
        # fed here at each commit and each donating dispatch, by the
        # scheduler at a flush and an admission, exported by its collect()
        self.gap_books = TokenGapBooks()
        # prefill buckets dispatched since the scheduler last took the sum
        # (_dispatch_donating adds, ContinuousScheduler._watch_stall takes)
        self.prefill_bucket_sum = 0

    def _init_device_state(self) -> None:
        """Fresh arena + per-row device state (boot and every ArenaReset),
        placed on the mesh like the step's own outputs so the compiled
        families key ONE compile each (``place_on_mesh``)."""
        from paddlefleetx_tpu.models.gpt.generation import init_paged_pools

        jnp = self._jnp
        B, vocab = self.capacity, int(self.mcfg.vocab_size)
        self.pools, self._logits, self._counts, self._reject = place_on_mesh(
            (
                init_paged_pools(
                    self.mcfg, self.cache.allocator.num_blocks, self.block,
                    kv_dtype=self.kv_dtype,
                    slots=B,  # a block with row state keeps it per batch slot
                    # a model with window layers: its second class of pages
                    **({"ring_blocks": self.cache.ring_allocator.num_blocks}
                       if self.ring_pages else {}),
                ),
                jnp.zeros((B, vocab), jnp.float32),
                jnp.zeros((B, vocab), jnp.int32),
                jnp.full((B,), -1, jnp.int32),
            ),
            self.mesh,
        )
        self._pool_fields = self.pools.fields()

    # -- capacity queries ----------------------------------------------
    def row_capacity_tokens(self, prompt_len: int, max_new: int) -> int:
        """Cache slots a row reserves IN THE GROWING CLASS of pages (every
        layer's, or the full attention layers' of a model with window layers,
        whose ring of ``ring_pages`` pages a row the manager reserves beside
        them whatever this number is): its full decode budget plus the
        prefill bucket width (pad junk lands in the row's own blocks).
        The budget is clamped to the context room like admit() clamps it
        (plan_decode's trim), so reservation == allocation.  With
        speculation on, draft_k slack slots absorb the verify chunk's
        rejected-tail overrun (paged_forward_step also null-routes any
        write past the table — belt and braces)."""
        from paddlefleetx_tpu.models.gpt.generation import bucket_len

        P = bucket_len(prompt_len, self.bucket)
        limit = int(self.mcfg.max_position_embeddings) - P
        slack = self.spec.draft_k if self.spec else 0
        return max(prompt_len + min(max_new, max(1, limit)) + slack, P)

    def kv_block_bytes(self) -> int:
        """Payload bytes per arena block over all layers and pools (what
        the decode kernels stream from HBM; int8 halves this vs bf16;
        a latent pool has no V).  The per-(slot, head) scale planes are
        excluded — they are the small constant overhead documented in
        docs/decode_path.md."""
        return self.kv_bytes_per_token() * self.block

    def kv_bytes_per_token(self) -> int:
        """Bytes one cached token takes over all layers WHOSE PAGES GROW WITH
        THE ROW: the model's ``cached_token`` (per-head K and V, or one
        latent) in the pools' dtype.  (Window layers: :meth:`ring_bytes_per_row`.)"""
        k = self.pools.k
        per_layer = sum(heads * width for heads, width in self.mcfg.cached_token)
        return int(k.shape[0]) * per_layer * k.dtype.itemsize

    def ring_bytes_per_row(self) -> int:
        """Bytes a row's rings take over all window layers, whatever its
        length (0 for a model without window layers)."""
        if not self.ring_pages:
            return 0
        wk = self.pools.wk  # from the shapes alone, like state_bytes_per_row
        page = int(np.prod(wk.shape[2:])) * wk.dtype.itemsize
        return int(wk.shape[0]) * self.ring_pages * 2 * page

    def _unwritten_for(self) -> str:
        if self.ring_pages:
            return ("two classes of pages (window layers keep a ring of pages a row beside "
                    "the full layers' growing ones: the prefix index, a prompt or verify chunk, "
                    "int8 pools and handoff payloads know one class), shared KV heads and "
                    "expert layers")
        if self.row_state:
            return ("a block with row state (state-space layers keep a recurrent state a "
                    "slot, which pages, prefix blocks and handoff payloads do not carry), "
                    "shared KV heads and expert layers")
        return "latent pools and expert layers"

    def _classic_only(self, what: str) -> None:
        """Refuse, by name, what only the GPT-2 block's pools can do."""
        if not self.mcfg.classic_block:
            raise ValueError(
                f"{what} is not written for {self._unwritten_for()} yet "
                "(docs/serving.md \"What is refused\")")

    def state_bytes_per_row(self) -> int:
        """Bytes a row keeps beside its pages, whatever its length, over
        all state-space layers (0 for a block without row state)."""
        # from the shapes alone: the collector's thread reads this while a
        # dispatch may hold the (donated) arrays
        total = 0
        for name, _, _ in self.row_state:
            arr = getattr(self.pools, name)  # [layers, slots, ...]
            total += int(arr.shape[0]) * int(np.prod(arr.shape[2:])) * arr.dtype.itemsize
        return total

    def _count_moe(self, counts, fetch: bool = True) -> None:
        """Fold one dispatch's expert-layer counts into stats.  A prefill's
        (``fetch=False``) wait for the next commit's fetch: reading them
        at the admission would hold the host until the prefill has run.
        So would the commit of a step the prefill was queued BEHIND: its
        counts ride that step's record and wait for the commit after."""
        if counts is None or self._warmup:
            return
        if not fetch:
            (self._moe_pending if self._inflight is None
             else self._inflight["moe_behind"]).append(counts)
            return
        pending, self._moe_pending = self._moe_pending + [counts], []
        for c in pending:
            for key, n in zip(("moe_pairs", "moe_held_pairs", "moe_held_max_pairs"),
                              np.asarray(c).tolist()):
                self.stats[key] += int(n)

    def _pools_tuple(self):
        return tuple(x for x in self.pools if x is not None)

    def _pools_of(self, pools_t):
        """The pools a compiled entry point took or gave back as their
        arrays alone (:meth:`_pools_tuple`)."""
        from paddlefleetx_tpu.models.gpt.generation import PagedPools

        return PagedPools.of(self._pool_fields, pools_t)

    def free_slots(self) -> int:
        return sum(1 for r in self.slots if r is None)

    def active_rows(self) -> int:
        return int(self.active.sum())

    def can_admit(self, prompt_len: int, max_new: int) -> bool:
        return self.free_slots() > 0 and self.cache.can_admit(
            self.row_capacity_tokens(prompt_len, max_new)
        )

    def validate_request(self, prompt_len: int, max_new: int) -> None:
        """Reject (loudly, pre-admission) a row that could NEVER fit."""
        need = blocks_for(
            self.row_capacity_tokens(prompt_len, max_new), self.block
        )
        usable = self.cache.allocator.num_blocks - 1
        if need > usable:
            raise ValueError(
                f"request needs {need} KV blocks but the pool has {usable}; "
                f"raise --kv-blocks or lower max_tokens"
            )

    # -- compiled entry points -----------------------------------------
    # the arena rides through both families as ONE donated pytree arg
    # (k, v[, k_scale, v_scale]) so the int8 scale planes donate with
    # their payload
    def _prefill_fn(self, P: int, PB: int):
        key = (self._gen_key, P, PB)
        fn = self._compiled_prefill.get(key)
        if fn is None:
            from paddlefleetx_tpu.models.gpt.generation import (
                paged_prefill,
            )

            def traced(p, prompt, plen, pools_t, table_row, *slot):
                self.stats["traces"] += 1
                pools, last, counts, moe = paged_prefill(
                    p, prompt, plen, self._pools_of(pools_t), table_row,
                    self.mcfg, ctx=self.ctx, return_moe=True,
                    # a block with row state prefills INTO its batch slot
                    **({"slot": slot[0]} if slot else {}),
                )
                out = tuple(x for x in pools if x is not None)
                # a block with expert layers also hands back their counts
                return (out, last, counts) + (() if moe is None else (moe,))

            fn = self._jax.jit(traced, donate_argnums=(3,))
            self._compiled_prefill[key] = fn
            get_registry().counter("pfx_serving_traces_total").inc()
        return fn

    def _step_fn(self, M: int):
        key = (self._gen_key, self.capacity, M)
        fn = self._compiled_step.get(key)
        if fn is None:
            from paddlefleetx_tpu.models.gpt.generation import (
                PagedRows,
                decode_step,
                decode_step_spec,
            )

            spec = self.spec

            def traced(p, pools_t, tables, logits, counts, positions,
                       gen_steps, max_news, active, forced_steps, reject,
                       drafts, rng):
                self.stats["traces"] += 1
                if spec is not None:
                    rows = PagedRows(logits, counts, positions, gen_steps,
                                     max_news, active, forced_steps, reject)
                    window, ncommit, pools, rows2 = decode_step_spec(
                        p, self._pools_of(pools_t), tables, rows, drafts,
                        self.mcfg, self._gen_key, key=rng, ctx=self.ctx,
                    )
                    rej2 = rows2.reject
                else:
                    rows = PagedRows(logits, counts, positions, gen_steps,
                                     max_news, active, forced_steps)
                    nxt, pools, rows2 = decode_step(
                        p, self._pools_of(pools_t), tables, rows, self.mcfg,
                        self._gen_key, key=rng, ctx=self.ctx,
                    )
                    window = nxt[:, None]
                    ncommit = active.astype(self._jnp.int32)
                    rej2 = reject
                out = tuple(x for x in pools if x is not None)
                moe = () if rows2.moe is None else (rows2.moe,)
                return (window, ncommit, out, rows2.logits, rows2.counts,
                        rows2.positions, rows2.gen_steps, rows2.active, rej2) + moe

            fn = self._jax.jit(traced, donate_argnums=(1,))
            self._compiled_step[key] = fn
            get_registry().counter("pfx_serving_traces_total").inc()
        return fn

    def _chunk_fn(self, t: int, M: int):
        """Compiled chunk-prefill family, keyed (chunk width t, table
        width bucket M) — bounded like the step family and counted the
        same way."""
        key = (self._gen_key, t, M)
        fn = self._compiled_chunk.get(key)
        if fn is None:
            from paddlefleetx_tpu.models.gpt.generation import (
                paged_chunk_prefill,
            )

            def traced(p, tokens, pools_t, table, position, n_valid,
                       last_idx):
                self.stats["traces"] += 1
                pools, last = paged_chunk_prefill(
                    p, tokens, self._pools_of(pools_t), table, position,
                    n_valid, last_idx, self.mcfg, ctx=self.ctx,
                )
                return tuple(x for x in pools if x is not None), last

            fn = self._jax.jit(traced, donate_argnums=(2,))
            self._compiled_chunk[key] = fn
            get_registry().counter("pfx_serving_traces_total").inc()
        return fn

    def _copy_fn(self):
        """Compiled single-block arena copy (COW: a row diverging
        mid-block gets a PRIVATE copy of the cached block to overwrite
        from the divergence slot on).  Block ids are runtime data — one
        compile, ever."""
        fn = self._compiled_copy
        if fn is None:
            def traced(pools_t, src, dst):
                self.stats["traces"] += 1
                pools = self._pools_of(pools_t)
                out = tuple(
                    x.at[:, dst].set(x[:, src])
                    for x in pools if x is not None
                )
                return out

            fn = self._jax.jit(traced, donate_argnums=(0,))
            self._compiled_copy = fn
            get_registry().counter("pfx_serving_traces_total").inc()
        return fn

    # -- row lifecycle --------------------------------------------------
    @property
    def prefix_enabled(self) -> bool:
        return self.cache.prefix.enabled

    def _publish_prefix(self, tokens, table) -> None:
        """`PrefixIndex.publish` with the budget-eviction accounting
        kept registry-synced (the release/export publish sites share
        this so the decision-log replay cannot drift)."""
        ev0 = self.cache.prefix.stats["evictions"]
        self.cache.prefix.publish(tokens, table)
        evicted = self.cache.prefix.stats["evictions"] - ev0
        if evicted:
            get_registry().counter(
                "pfx_prefix_evictions_total"
            ).inc(evicted)

    def _spill_block(self, path: tuple, block_id: int) -> None:
        """PrefixIndex eviction hook: demote one evicted FULL block's KV
        to the host-RAM spill store before its arena reference drops.
        Runs inside ``_evict_node`` — the gather reads a block whose
        reference is still held, and ``clear()`` (ArenaReset) never
        routes through here, so a dead arena's blocks cannot spill.
        Warmup evictions never spill either (synthetic KV must not
        readmit into traffic).  Any failure degrades to a plain
        eviction behind the discard counter — the graceful-degradation
        contract: spilling is an optimization, never a failure mode."""
        spill = self.cache.spill
        if self._warmup or not spill.enabled:
            return
        from paddlefleetx_tpu.models.gpt.generation import gather_kv_blocks

        sp0 = spill.stats["spills"]
        dc0 = spill.stats["discards"]
        try:
            spill.put(path, gather_kv_blocks(self.pools, [int(block_id)]))
        except Exception as exc:  # noqa: BLE001 — degrade, never block
            logger.warning(                       # the eviction
                f"prefix spill failed ({type(exc).__name__}: {exc}); "
                "block evicted without a host copy"
            )
            spill.stats["discards"] += 1
        reg = get_registry()
        d = spill.stats["spills"] - sp0
        if d:
            reg.counter("pfx_prefix_spills_total").inc(d)
        d = spill.stats["discards"] - dc0
        if d:
            reg.counter("pfx_prefix_spill_discards_total").inc(d)

    def _readmit_spilled(self, prompt_ids: List[int], m: int) -> int:
        """Promote spilled host copies of this prompt's next full blocks
        back into the arena, extending the radix match from ``m`` tokens
        on.  Each hit allocates one block, scatters the host copy in
        (the one-compile-ever ``_adopt_fn(1)`` family), and inserts the
        node — the caller re-runs ``match()`` so the readmitted blocks
        flow through the normal shared-admission and exact-replay hit
        accounting.  Every failure — checksum mismatch, the
        ``spill_corrupt`` drill, pool pressure — degrades to recompute
        behind the discard counter; only :class:`ArenaReset` propagates
        (a donated dispatch died, the engine-wide contract)."""
        spill = self.cache.spill
        limit = len(prompt_ids) - 1  # match's cap: >= 1 token recomputes
        readmitted = 0
        rd0 = spill.stats["readmits"]
        dc0 = spill.stats["discards"]
        jnp = self._jnp
        try:
            while m + self.block <= limit:
                key = tuple(int(t) for t in prompt_ids[:m + self.block])
                self._spill_probes += 1
                # deterministic corruption drill (docs/fault_tolerance.md
                # spill_corrupt): the Kth probe treats the entry as torn —
                # discarded loudly, the request recomputes and succeeds
                if maybe_fire("spill_corrupt", self._spill_probes):
                    spill.discard(key)
                    break
                arrays = spill.get(key)  # checksum-verified; None = miss
                if arrays is None:
                    break
                try:
                    fresh = self.cache.allocator.alloc(1)
                except BlockPoolExhausted:
                    break  # recompute; the entry waits for calmer pressure
                names = ("k", "v", "k_scale", "v_scale")
                blocks_t = tuple(
                    jnp.asarray(arrays[n]) for n in names if n in arrays
                )
                fn = self._adopt_fn(1)
                try:
                    pools_t = self._dispatch_donating(
                        lambda: fn(
                            self._pools_tuple(),
                            jnp.asarray(fresh, jnp.int32),
                            blocks_t,
                        ),
                        "spill readmit",
                    )
                except ArenaReset:
                    # reset() released every row and cleared the index,
                    # but this orphan allocation is ours to return
                    self.cache.allocator.free(fresh)
                    raise
                self.pools = self._pools_of(pools_t)
                self.cache.prefix.insert_block(key, fresh[0])
                spill.pop(key)  # back on device; counted as a readmit
                readmitted += 1
                m += self.block
            if readmitted:
                self.cache.prefix.evict_to_budget()
        finally:
            reg = get_registry()
            d = spill.stats["readmits"] - rd0
            if d:
                reg.counter("pfx_prefix_readmits_total").inc(d)
            d = spill.stats["discards"] - dc0
            if d:
                reg.counter("pfx_prefix_spill_discards_total").inc(d)
        return readmitted

    def _prefix_admit(self, prompt_ids: List[int], capacity_tokens: int,
                      label: str = "prefix"
                      ) -> Tuple[int, List[int], List[int],
                                 Optional[Tuple[int, int]], int]:
        """The shared admission prelude of :meth:`admit` and
        :meth:`prefill_export`: radix-prefix lookup, block reservation,
        the landed-admission hit/miss accounting, and the copy-on-write
        block copy for a mid-block divergence.  Returns ``(seq_id,
        table, shared, cow, m)`` with ``self.pools`` already holding the
        COW copy.

        Warmup admissions neither hit nor publish: their synthetic
        prompts must not pollute the index, and the pfx_prefix_*
        counters stay traffic-only.  Index stats and registry counters
        commit together AFTER the reservation landed (a failed
        allocation raises before either moved — stats and counters can
        never desync, the exact-replay contract)."""
        shared: List[int] = []
        cow = None
        m = 0
        if self.prefix_enabled and not self._warmup:
            shared, cow, m = self.cache.prefix.match(prompt_ids)
            # spill-tier readmit: when the on-device trie runs dry at a
            # block boundary (no COW divergence), promote spilled host
            # copies of the NEXT blocks, then re-match so shared/m flow
            # through the one hit-accounting path below
            if (self.cache.spill.enabled and cow is None
                    and len(self.cache.spill)
                    and self._readmit_spilled(prompt_ids, m)):
                shared, cow, m = self.cache.prefix.match(prompt_ids)
        self._seq_counter += 1
        seq_id = self._seq_counter
        table = self._cache_admit(seq_id, capacity_tokens, shared=shared)
        if self.prefix_enabled and not self._warmup:
            self.cache.prefix.record_lookup(m)
            reg = get_registry()
            if m:
                reg.counter("pfx_prefix_hits_total").inc()
                reg.counter("pfx_prefix_hit_tokens_total").inc(m)
            else:
                reg.counter("pfx_prefix_misses_total").inc()
        if cow is not None:
            # copy-on-write: the diverging cached block is copied into
            # the row's first PRIVATE block; the suffix prefill
            # overwrites it from the divergence slot on, so the cached
            # original (and every row sharing it) is never touched
            src, _keep = cow
            dst = table[len(shared)]
            fn = self._copy_fn()
            jnp = self._jnp
            pools_t = self._dispatch_donating(
                lambda: fn(
                    self._pools_tuple(), jnp.int32(src), jnp.int32(dst)
                ),
                f"{label} COW copy", release_seq=seq_id,
            )
            self.pools = self._pools_of(pools_t)
        return seq_id, table, shared, cow, m

    def seats_behind_step(self, prompt_ids: Sequence[int]) -> bool:
        """Whether :meth:`admit` of this prompt may run while a step is in
        flight, its prefill queued on the device behind that step.  Only
        the monolithic prefill may: it fetches nothing, sets the new row's
        logits and counts on the step's output futures, and
        :meth:`_commit` merges by the dispatch-time mask, so the row's
        fresh host values win.  Speculation, a chunked prefill and a
        prefix hit (or a spilled prefix that may become one) keep the
        flush in front of them; so does the scheduler for an adoption and
        a resumed row."""
        if not self.inflight_rows or self.spec is not None or self.prefill_chunk:
            return False
        if self.prefix_enabled:
            if self.cache.spill.enabled and len(self.cache.spill):
                return False
            return self.cache.prefix.match(prompt_ids)[2] == 0
        return True

    def _cache_admit(self, seq_id: int, tokens: int,
                     shared: Optional[List[int]] = None) -> List[int]:
        """`PagedCacheManager.admit` with the eviction accounting kept
        registry-synced: any cached prefixes the admission displaced
        under pool pressure bump pfx_prefix_evictions_total together
        with the index stats — EVERY admission spelling (admit / adopt /
        prefill_export) must route through here or the decision-log
        replay and /metrics drift apart."""
        ev0 = self.cache.prefix.stats["evictions"]
        try:
            return self.cache.admit(seq_id, tokens, shared=shared)
        finally:
            evicted = self.cache.prefix.stats["evictions"] - ev0
            if evicted and not self._warmup:
                get_registry().counter(
                    "pfx_prefix_evictions_total"
                ).inc(evicted)

    def _dispatch_donating(self, thunk, what: str,
                           release_seq: Optional[int] = None,
                           **span_args):
        """Run one donating dispatch under the arena error contract: any
        failure means the pools may be donation-invalidated — release a
        not-yet-slotted row's allocation first (``release_seq``; a row
        already in ``slots`` is released by :meth:`reset` itself),
        rebuild the arena, and raise :class:`ArenaReset` carrying the
        dead rows.  ONE spelling for the COW-copy / monolithic-prefill /
        chunk dispatches so the recovery contract cannot drift between
        them.  Time ledger: every donating dispatch is prefill-side
        device work (decode steps go through _dispatch instead);
        ``span_args`` ride the ``pfx.sched.prefill`` trace span.  Token-gap
        books: the interval between two commits that holds one of these
        is an ``admission`` interval.  Queued BEHIND a step in flight
        (``behind``), the device runs it after that step, so the interval
        it falls in begins at that step's commit: the mark is carried on
        the in-flight record and :meth:`_commit` sets it."""
        if self._inflight is not None:
            self._inflight["behind"] = True
        elif not self._warmup:
            self.gap_books.note("admission")
        if not self._warmup:
            # the scheduler names a slow iteration's kind by it
            self.prefill_bucket_sum += span_args.get("bucket", 0)
        with ledger_span("pfx.sched.prefill", self.stats, "t_device_prefill",
                         what=what, **span_args):
            try:
                with self.mesh:
                    return thunk()
            except BaseException as exc:
                if release_seq is not None:
                    self.cache.release(release_seq)
                dead = self.reset()
                raise ArenaReset(
                    f"{what} failed ({type(exc).__name__}: {exc}); "
                    "arena reset",
                    dead,
                ) from exc

    def admit(self, prompt_ids: Sequence[int], max_new: int,
              entry: Optional[_CBEntry] = None, row_idx: int = 0) -> int:
        """Allocate blocks + a batch slot and prefill the prompt into the
        arena.  Raises :class:`BlockPoolExhausted` / RuntimeError("no
        free slot") when full — callers check :meth:`can_admit` first.

        With the prefix cache on, the radix index is consulted first:
        the matched span's cached blocks map into the new row's table as
        SHARED entries (their KV is never recomputed — only the suffix
        runs through the model), a mid-block divergence gets a private
        copy-on-write block, and the suffix rides the chunk family.
        With ``prefill_chunk`` set, a long prompt is admitted
        mid-prefill: one chunk runs now, the rest stream one per
        scheduler iteration interleaved with decode steps."""
        from paddlefleetx_tpu.models.gpt.generation import bucket_len

        # an admission legitimately sits between a commit and the next
        # decode dispatch (prefill is device work) — drop the gap timer
        # so host_gap_s measures only decode-loop scheduling gaps
        self._t_results = None
        jnp = self._jnp
        prompt_ids = [int(t) for t in prompt_ids]
        plen = len(prompt_ids)
        if plen < 1:
            raise ValueError("prompt must be non-empty")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        P = bucket_len(plen, self.bucket)
        context = int(self.mcfg.max_position_embeddings)
        limit = context - P
        if limit < 1:
            raise ValueError(
                f"prompt bucket {P} leaves no decode room in context "
                f"{context}"
            )
        # the COALESCE path trims an over-budget request to the context
        # room (core/serving.plan_decode); deliver the identical count —
        # the HTTP layer pre-clamps, this covers direct library callers
        max_new = min(max_new, limit)
        slot = next((i for i, r in enumerate(self.slots) if r is None), None)
        if slot is None:
            raise RuntimeError("no free slot in the running batch")
        seq_id, table, shared, cow, m = self._prefix_admit(
            prompt_ids, self.row_capacity_tokens(plen, max_new)
        )
        trace = entry.future.trace if entry is not None else None

        if m == 0 and self.prefill_chunk == 0:
            # no reuse, no chunking: the original monolithic prefill
            # (contiguous forward + block repack), kept bit-identical
            PB = blocks_for(P, self.block)
            # prefill scatters PB blocks (bucket width incl. pad junk,
            # which lands in the row's own blocks — row_capacity_tokens
            # reserves at least the bucket width, so the table covers PB)
            prefill_table = jnp.asarray(table[:PB], jnp.int32)
            ring = self.cache.ring(seq_id)
            if ring:  # a model with window layers prefills into both classes
                prefill_table = (prefill_table, jnp.asarray(ring, jnp.int32))
            prompt = np.full((1, P), self.gen.pad_token_id, np.int32)
            prompt[0, :plen] = prompt_ids  # RIGHT-pad (paged rows are unpadded)
            fn = self._prefill_fn(P, PB)
            t_prefill = time.monotonic()
            pools_t, last, counts, *moe = self._dispatch_donating(
                lambda: fn(
                    self.server.params,
                    jnp.asarray(prompt),
                    jnp.int32(plen),
                    self._pools_tuple(),
                    prefill_table,
                    *((jnp.int32(slot),) if self.row_state else ()),
                ),
                "prefill", release_seq=seq_id,
                slot=slot, prompt_len=plen, bucket=P,
                # a sampled request's spans share one id across the
                # /debug/traces timeline and the profiler's host plane
                **({"trace_id": trace.trace_id} if trace is not None else {}),
            )
            self.pools = self._pools_of(pools_t)
            self._count_moe(moe[0] if moe else None, fetch=False)
            if moe and not self._warmup:
                self.stats["moe_grouped_calls"] += self.mcfg.sorted_pair_products
            self._logits = self._logits.at[slot].set(last)
            self._counts = self._counts.at[slot].set(counts)
            self._reject = self._reject.at[slot].set(-1)
            self.positions[slot] = plen
            self.gen_steps[slot] = 0
            self.max_news[slot] = max_new
            # forced-EOS fires where the COALESCE path fires it: the
            # bucketed run end of core/serving.plan_decode (min(ceil32(
            # budget), context room)) — NOT the raw budget, whose step
            # the contiguous path's trimmed output usually never shows
            self.forced_steps[slot] = min(-(-max_new // 32) * 32, limit) - 1
            self.active[slot] = True
            if trace is not None:
                trace.span(
                    "prefill", t0=t_prefill, t1=time.monotonic(),
                    prompt_len=plen, bucket=P, blocks=len(table), slot=slot,
                )
            self.slots[slot] = _Row(
                seq_id=seq_id, entry=entry, row_idx=row_idx, prompt_len=plen,
                max_new=max_new, table=table, prompt_ids=prompt_ids,
                trace=trace, ring=ring,
            )
            self.stats["prefills"] += 1
            self.stats["prefill_tokens"] += plen
            if self.row_state and not self._warmup:
                self.stats["ssm_prefill_tokens"] += plen * int(self.mcfg.ssm_layers)
            if self.mcfg.hyper_connections and not self._warmup:
                self.stats["hc_tokens"] += plen
            return slot

        # prefix-hit / chunked path: only the unmatched suffix
        # [m, plen) ever runs through the model, in chunk-sized pieces
        # riding the compiled chunk family.  The row sits decode-INACTIVE
        # until its last chunk lands (a fixed-shape decode step ignores
        # it), so decode latency stays flat while the prompt streams in.
        chunk = self.prefill_chunk or bucket_len(plen - m, self.bucket)
        self.positions[slot] = m
        self.gen_steps[slot] = 0
        self.max_news[slot] = max_new
        self.forced_steps[slot] = min(-(-max_new // 32) * 32, limit) - 1
        self.active[slot] = False
        self.slots[slot] = _Row(
            seq_id=seq_id, entry=entry, row_idx=row_idx, prompt_len=plen,
            max_new=max_new, table=table, prompt_ids=prompt_ids,
            trace=trace, prefix_hit=m, pending=prompt_ids[m:],
            prefill_pos=m, chunk=chunk, prefill_done=False,
        )
        self.stats["prefills"] += 1
        if trace is not None and m:
            trace.event(
                "prefix_hit", slot=slot, hit_tokens=m,
                shared_blocks=len(shared), cow=cow is not None,
            )
        # first chunk runs NOW (admission = work started); the rest ride
        # step(), one per scheduler iteration, interleaved with decode
        self._tick_prefill(slot)
        return slot

    def _padded_chunk_table(self, table: List[int]) -> np.ndarray:
        """Pad a row's block table to the power-of-two width the chunk
        family is compiled for."""
        M = min(
            _pow2_at_least(len(table)),
            _pow2_at_least(self.max_row_blocks),
        )
        tbl = np.full((M,), NULL_BLOCK, np.int32)
        tbl[: len(table)] = table
        return tbl

    def _run_prefill_chunk(self, chunk: int, tbl: np.ndarray, pos: int,
                           pending, *, label: str,
                           release_seq: Optional[int] = None):
        """Dispatch ONE compiled prefill chunk — the shared body of the
        scheduler's :meth:`_tick_prefill` and the export path's suffix
        loop, so the chunk-call contract and its stats/counter
        accounting live in exactly one place.  Returns ``(last_logits,
        take)``."""
        jnp = self._jnp
        take = min(chunk, len(pending))
        toks = np.full((1, chunk), self.gen.pad_token_id, np.int32)
        toks[0, :take] = pending[:take]
        fn = self._chunk_fn(chunk, len(tbl))
        pools_t, last = self._dispatch_donating(
            lambda: fn(
                self.server.params,
                jnp.asarray(toks),
                self._pools_tuple(),
                jnp.asarray(tbl),
                jnp.int32(pos),
                jnp.int32(take),
                jnp.int32(max(take - 1, 0)),
            ),
            label, release_seq=release_seq,
            position=pos, prompt_len=take, bucket=chunk,
        )
        self.pools = self._pools_of(pools_t)
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens"] += take
        if not self._warmup:
            get_registry().counter("pfx_prefill_chunks_total").inc()
        return last, take

    def _tick_prefill(self, slot: int) -> None:
        """Run ONE chunk of a mid-prefill row's prompt suffix.  The
        final chunk seeds the row's pending logits (last REAL prompt
        token) + repetition counts and flips it decode-active."""
        jnp = self._jnp
        self._t_results = None  # prefill chunk between commit and dispatch
        row = self.slots[slot]
        final = min(row.chunk, len(row.pending)) == len(row.pending)
        t0 = time.monotonic()
        # no release_seq: this row already sits in slots, so reset()
        # releases it with the other dead rows
        last, take = self._run_prefill_chunk(
            row.chunk, self._padded_chunk_table(row.table),
            row.prefill_pos, row.pending, label="chunk prefill",
        )
        from paddlefleetx_tpu.models.gpt.generation import (
            prefix_token_counts,
        )

        row.pending = row.pending[take:]
        row.prefill_pos += take
        self.positions[slot] = row.prefill_pos
        if row.trace is not None:
            row.trace.span(
                "prefill_chunk", t0=t0, t1=time.monotonic(), slot=slot,
                tokens=take, position=row.prefill_pos, final=final,
            )
        if final:
            counts = prefix_token_counts(
                row.prompt_ids, int(self.mcfg.vocab_size)
            )
            self._logits = self._logits.at[slot].set(last)
            self._counts = self._counts.at[slot].set(jnp.asarray(counts))
            self._reject = self._reject.at[slot].set(-1)
            self.positions[slot] = row.prompt_len
            self.active[slot] = True
            row.prefill_done = True

    # -- disaggregated prefill/decode (KV handoff) ----------------------
    def _pool_sig(self) -> List[int]:
        """[layers, heads, block, head_dim] — the arena compatibility
        signature a handoff payload must match (num_blocks excluded: the
        two replicas' pools may legitimately differ in size)."""
        layers, _, heads, bs, d = self.pools.k.shape
        return [int(layers), int(heads), int(bs), int(d)]

    def _clamp_budget(self, prompt_len: int, max_new: int):
        """(P, PB, limit, clamped max_new) — THE admit-side budget clamp,
        shared by admit/export/adopt so a payload clamped on the prefill
        replica re-clamps to the identical value on the decode replica."""
        from paddlefleetx_tpu.models.gpt.generation import bucket_len

        if prompt_len < 1:
            raise ValueError("prompt must be non-empty")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        P = bucket_len(prompt_len, self.bucket)
        context = int(self.mcfg.max_position_embeddings)
        limit = context - P
        if limit < 1:
            raise ValueError(
                f"prompt bucket {P} leaves no decode room in context "
                f"{context}"
            )
        return P, blocks_for(P, self.block), limit, min(max_new, limit)

    def prefill_export(self, prompt_ids: Sequence[int], max_new: int,
                       trace: Any = None):
        """Prefill-replica half of the disaggregated handoff: run ONE
        row's prompt through `paged_prefill` into this arena, then copy
        the prefilled blocks + row state out as ``(meta, arrays)`` for
        `core/paged_cache.pack_handoff` and free the blocks.  Only the
        prompt bucket's blocks are held (and only for the duration of
        the export), so a prefill pool stays small regardless of decode
        budgets.  ``meta["max_new"]`` carries the ALREADY-clamped budget;
        the adopting engine re-clamps with the same formula, so the two
        agree whenever the replicas share a Model config (and
        `check_handoff_meta` has already insisted they do).

        With the prefix cache on (``--prefix-cache-blocks`` on a
        ``--role prefill`` replica), the radix index is consulted
        exactly like :meth:`admit`: the matched span's cached blocks map
        SHARED into the export table (a fleet-shared system prefix is
        computed once per prefill replica, not once per request), a
        mid-block divergence gets a private copy-on-write block, and
        ONLY the unmatched suffix runs through the chunk family.  The
        exported bytes are identical either way — `gather_kv_blocks`
        copies shared and private blocks alike, and `pack_handoff`'s
        pool signature already guards cross-replica compatibility."""
        self._classic_only("KV handoff (--role prefill)")
        prompt_ids = [int(t) for t in prompt_ids]
        plen = len(prompt_ids)
        P, PB, _, max_new = self._clamp_budget(plen, int(max_new))
        jnp = self._jnp
        t0 = time.monotonic()
        # reserve ONLY the prompt bucket: the decode budget is the
        # decode replica's to hold
        seq_id, table, _shared, _cow, m = self._prefix_admit(
            prompt_ids, P, label="export prefix"
        )
        if m == 0:
            prompt = np.full((1, P), self.gen.pad_token_id, np.int32)
            prompt[0, :plen] = prompt_ids
            fn = self._prefill_fn(P, PB)
            pools_t, last, counts = self._dispatch_donating(
                lambda: fn(
                    self.server.params,
                    jnp.asarray(prompt),
                    jnp.int32(plen),
                    self._pools_tuple(),
                    jnp.asarray(table, jnp.int32),
                ),
                "prefill export", release_seq=seq_id,
            )
            self.pools = self._pools_of(pools_t)
            self.stats["prefill_tokens"] += plen
            counts = np.asarray(counts, np.int32)
        else:
            from paddlefleetx_tpu.models.gpt.generation import (
                prefix_token_counts,
            )

            last = self._export_suffix_chunks(prompt_ids, m, table, seq_id)
            counts = np.asarray(
                prefix_token_counts(prompt_ids, int(self.mcfg.vocab_size)),
                np.int32,
            )
        from paddlefleetx_tpu.models.gpt.generation import gather_kv_blocks

        arrays = gather_kv_blocks(self.pools, table)
        arrays["logits"] = np.asarray(last, np.float32)
        arrays["counts"] = counts
        if self.prefix_enabled and not self._warmup:
            # publish BEFORE release: the index takes its own refs while
            # the row's table still pins the blocks
            self._publish_prefix(prompt_ids, table)
        self.cache.release(seq_id)  # contents copied out; blocks free
        meta = {
            "prompt_ids": prompt_ids,
            "prompt_len": plen,
            "max_new": int(max_new),
            "block": self.block,
            "kv_dtype": self.kv_dtype,
            "pool_sig": self._pool_sig(),
        }
        self.stats["prefills"] += 1
        self.stats["exports"] += 1
        if not self._warmup:
            get_registry().counter("pfx_handoff_exports_total").inc()
        if trace is not None:
            trace.span("prefill_export", t0=t0, t1=time.monotonic(),
                       prompt_len=plen, bucket=P, blocks=PB,
                       prefix_hit=m)
        return meta, arrays

    def _export_suffix_chunks(self, prompt_ids: List[int], m: int,
                              table: List[int], seq_id: int):
        """Run a prefix-hit export's unmatched suffix ``[m, plen)``
        through the compiled chunk family, synchronously (an export must
        return a complete payload — there is no decode loop to
        interleave with on a prefill replica).  Returns the last REAL
        prompt token's logits."""
        from paddlefleetx_tpu.models.gpt.generation import bucket_len

        chunk = self.prefill_chunk or bucket_len(
            len(prompt_ids) - m, self.bucket
        )
        tbl = self._padded_chunk_table(table)
        pending = prompt_ids[m:]
        pos = m
        last = None
        while pending:
            last, take = self._run_prefill_chunk(
                chunk, tbl, pos, pending,
                label="export suffix chunk", release_seq=seq_id,
            )
            pending = pending[take:]
            pos += take
        return last

    def _adopt_fn(self, PB: int):
        key = (PB,)
        fn = self._compiled_adopt.get(key)
        if fn is None:
            from paddlefleetx_tpu.models.gpt.generation import (
                scatter_kv_blocks,
            )

            names = ("k", "v", "k_scale", "v_scale")

            def traced(pools_t, idx, blocks_t):
                self.stats["traces"] += 1
                pools = scatter_kv_blocks(
                    self._pools_of(pools_t), idx, dict(zip(names, blocks_t))
                )
                return tuple(x for x in pools if x is not None)

            fn = self._jax.jit(traced, donate_argnums=(0,))
            self._compiled_adopt[key] = fn
            get_registry().counter("pfx_serving_traces_total").inc()
        return fn

    def adopt(self, meta: Dict[str, Any], arrays: Dict[str, Any],
              entry: Optional[_CBEntry] = None, row_idx: int = 0) -> int:
        """Decode-replica half of the handoff: validate the payload
        against this arena (LOUD on dtype/block-size/shape mismatch),
        allocate the row's FULL capacity (prompt + decode budget, like
        `admit`), scatter the exported blocks into its first PB blocks
        (donated dispatch — a failure resets the arena, the `admit`
        contract), and seed the row state so the continuous scheduler
        continues exactly where the prefill replica's math stopped —
        greedy output token-identical to a single-process `admit`."""
        self._classic_only("KV handoff (--role decode)")
        check_handoff_meta(
            meta, block=self.block, kv_dtype=self.kv_dtype,
            pool_sig=self._pool_sig(),
        )
        prompt_ids = [int(t) for t in meta["prompt_ids"]]
        plen = int(meta["prompt_len"])
        if plen != len(prompt_ids):
            raise ValueError(
                f"handoff prompt_len {plen} != {len(prompt_ids)} prompt ids"
            )
        P, PB, limit, max_new = self._clamp_budget(plen, int(meta["max_new"]))
        jnp = self._jnp
        self._t_results = None  # adoption is an admission for gap accounting
        vocab = int(self.mcfg.vocab_size)
        for name, want in (("logits", (vocab,)), ("counts", (vocab,))):
            got = tuple(np.shape(arrays.get(name)))
            if got != want:
                raise ValueError(
                    f"handoff {name} shape {got} != {want} (vocab {vocab})"
                )
        # the block-array SET is validated BEFORE the donated dispatch: a
        # payload missing k/v must fail this request alone, not trip the
        # in-trace check and reset the arena under every live row
        names = ("k", "v", "k_scale", "v_scale")
        need = set(names[: 4 if self.kv_dtype == "int8" else 2])
        if not need <= set(arrays):
            raise ValueError(
                f"handoff payload missing arrays "
                f"{sorted(need - set(arrays))} (has {sorted(arrays)})"
            )
        slot = next((i for i, r in enumerate(self.slots) if r is None), None)
        if slot is None:
            raise RuntimeError("no free slot in the running batch")
        self._seq_counter += 1
        seq_id = self._seq_counter
        table = self._cache_admit(
            seq_id, self.row_capacity_tokens(plen, max_new)
        )
        # NAMES order (k, v, scales) — _adopt_fn zips the same order
        blocks_t = tuple(jnp.asarray(arrays[n]) for n in names if n in need)
        trace = entry.future.trace if entry is not None else None
        t0 = time.monotonic()
        fn = self._adopt_fn(PB)
        pools_t = self._dispatch_donating(
            lambda: fn(
                self._pools_tuple(),
                jnp.asarray(table[:PB], jnp.int32),
                blocks_t,
            ),
            "handoff adopt", release_seq=seq_id,
        )
        self.pools = self._pools_of(pools_t)
        self._logits = self._logits.at[slot].set(
            jnp.asarray(arrays["logits"], jnp.float32)
        )
        self._counts = self._counts.at[slot].set(
            jnp.asarray(arrays["counts"], jnp.int32)
        )
        self._reject = self._reject.at[slot].set(-1)
        self.positions[slot] = plen
        self.gen_steps[slot] = 0
        self.max_news[slot] = max_new
        # same forced-EOS step as admit(): the coalesce path's bucketed
        # run end, so disaggregated output stays token-identical
        self.forced_steps[slot] = min(-(-max_new // 32) * 32, limit) - 1
        self.active[slot] = True
        if trace is not None:
            trace.span(
                "adopt", t0=t0, t1=time.monotonic(),
                prompt_len=plen, bucket=P, blocks=len(table), slot=slot,
            )
        self.slots[slot] = _Row(
            seq_id=seq_id, entry=entry, row_idx=row_idx, prompt_len=plen,
            max_new=max_new, table=table, prompt_ids=prompt_ids,
            trace=trace,
        )
        self.stats["adopts"] += 1
        if not self._warmup:
            get_registry().counter("pfx_handoff_adopts_total").inc()
        # deterministic decode-death drill (docs/fault_tolerance.md
        # adopt_crash): the Kth adoption hard-exits AFTER the row landed
        # in the arena — the transport sees the connection die
        # mid-exchange, driving the router's bounded re-prefill failover
        maybe_fire("adopt_crash", self.stats["adopts"])
        return slot

    # -- peer-to-peer prefix migration (drain/scale-down survival) -----
    def export_hot_prefixes(self, max_blocks: int = 0
                            ) -> Optional[Tuple[Dict[str, Any],
                                                Dict[str, np.ndarray]]]:
        """Snapshot the hottest published prefix blocks as ONE handoff
        payload ``(meta, arrays)`` for peer adoption on drain.  The
        top-``max_blocks`` most-recently-used FULL blocks are taken
        together with their ancestor chains (a child's KV is unmatchable
        without its parents), shortest path first, so the receiver can
        adopt in order and stop cleanly at any boundary.  Returns None
        when nothing is cached.  Called on the drain path AFTER the
        scheduler thread exited — the index walk is single-threaded."""
        if not self.prefix_enabled:
            return None
        pfx = self.cache.prefix
        nodes = [
            n for n in list(pfx._nodes) if len(n.tokens) == self.block
        ]
        if not nodes:
            return None
        nodes.sort(key=lambda n: n.last_used, reverse=True)
        picked = nodes[:max_blocks] if max_blocks > 0 else nodes
        chosen: set = set()
        for n in picked:
            while n is not None and n not in chosen:
                if len(n.tokens) == self.block:
                    chosen.add(n)
                n = n.parent
        order = sorted(chosen, key=lambda n: len(pfx.node_path(n)))
        from paddlefleetx_tpu.models.gpt.generation import gather_kv_blocks

        arrays = gather_kv_blocks(self.pools, [n.block_id for n in order])
        meta = {
            "kind": "prefixes",
            "prefixes": [list(pfx.node_path(n)) for n in order],
            "block": self.block,
            "kv_dtype": self.kv_dtype,
            "pool_sig": self._pool_sig(),
        }
        return meta, arrays

    def validate_prefix_payload(self, meta: Dict[str, Any],
                                arrays: Dict[str, Any]) -> int:
        """LOUD structural validation of a migration payload — run in
        full BEFORE anything touches the arena (the adopt rule: a torn
        or incompatible transfer is rejected whole, never half-adopted).
        Returns the block count."""
        check_handoff_meta(
            meta, block=self.block, kv_dtype=self.kv_dtype,
            pool_sig=self._pool_sig(),
        )
        prefixes = meta.get("prefixes")
        if not isinstance(prefixes, list) or not prefixes:
            raise ValueError("prefix payload carries no prefixes")
        for p in prefixes:
            if not isinstance(p, (list, tuple)) or not p \
                    or len(p) % self.block:
                raise ValueError(
                    "prefix path is not a token list of positive "
                    f"block-{self.block}-multiple length: {p!r:.60}"
                )
        names = ("k", "v", "k_scale", "v_scale")
        need = set(names[: 4 if self.kv_dtype == "int8" else 2])
        if not need <= set(arrays):
            raise ValueError(
                f"prefix payload missing arrays "
                f"{sorted(need - set(arrays))} (has {sorted(arrays)})"
            )
        nb = len(prefixes)
        for name in sorted(need):
            got = tuple(np.shape(arrays[name]))
            if len(got) != 5 or got[1] != nb:
                raise ValueError(
                    f"prefix payload array {name!r} shape {got} does "
                    f"not carry {nb} blocks"
                )
        return nb

    def adopt_prefixes(self, meta: Dict[str, Any],
                       arrays: Dict[str, Any]) -> int:
        """Migration-receiver half: adopt a draining peer's exported
        prefix blocks into this arena's radix index.  Entries land
        shortest-path-first so ancestor chains always precede children;
        pool pressure stops the adoption cleanly at a block boundary
        (what landed is a valid prefix, the rest is dropped — never
        half-adopted), and an already-cached path is skipped (an
        idempotent re-send only bumps LRU).  Returns adopted count."""
        nb = self.validate_prefix_payload(meta, arrays)
        if not self.prefix_enabled:
            return 0
        prefixes = meta["prefixes"]
        names = ("k", "v", "k_scale", "v_scale")
        need = set(names[: 4 if self.kv_dtype == "int8" else 2])
        order = sorted(range(nb), key=lambda i: len(prefixes[i]))
        jnp = self._jnp
        adopted = 0
        for i in order:
            path = [int(t) for t in prefixes[i]]
            if self.cache.prefix.has_path(path):
                continue
            if len(path) > self.block and not self.cache.prefix.has_path(
                    path[:-self.block]):
                continue  # its parent never landed (pressure): skip child
            try:
                fresh = self.cache.allocator.alloc(1)
            except BlockPoolExhausted:
                break  # prefix-closed stop: everything adopted so far holds
            blocks_t = tuple(
                jnp.asarray(np.ascontiguousarray(arrays[n][:, i:i + 1]))
                for n in names if n in need
            )
            fn = self._adopt_fn(1)
            try:
                pools_t = self._dispatch_donating(
                    lambda: fn(
                        self._pools_tuple(),
                        jnp.asarray(fresh, jnp.int32),
                        blocks_t,
                    ),
                    "prefix adopt",
                )
            except ArenaReset:
                self.cache.allocator.free(fresh)  # orphan: ours to return
                raise
            self.pools = self._pools_of(pools_t)
            self.cache.prefix.insert_block(path, fresh[0])
            adopted += 1
        if adopted:
            self.cache.prefix.evict_to_budget()
            self.stats["migrate_adopted"] += adopted
            if not self._warmup:
                get_registry().counter(
                    "pfx_migrate_adopted_total"
                ).inc(adopted)
        return adopted

    def table_width_bucket(self) -> int:
        widest = max(
            (len(r.table) for r in self.slots if r is not None), default=1
        )
        return min(_pow2_at_least(widest), _pow2_at_least(self.max_row_blocks))

    def _host_drafts(self) -> np.ndarray:
        """Self-draft every active row from its host-side prompt+output
        history: the n-gram lookup proposes k+1 tokens continuing the
        trailing n-gram's last earlier occurrence; proposal[0] predicts
        the not-yet-sampled pending token, proposals[1:] are the drafts
        the verify chunk carries.  Pure runtime data — never a compile
        key."""
        from paddlefleetx_tpu.ops.speculative import NGRAM_WINDOW

        k = self.spec.draft_k
        # the lookup never scans past NGRAM_WINDOW, so hand it only the
        # tail (+ needle/draft slack) — a 100k-token history must not
        # pay an O(history) copy per row per step on the decode hot path
        need = NGRAM_WINDOW + self.spec.ngram + k + 2
        out = np.zeros((self.capacity, k), np.int32)
        for i, r in enumerate(self.slots):
            if r is not None and self.active[i]:
                if len(r.tokens) >= need:
                    seq = r.tokens[-need:]
                else:
                    seq = r.prompt_ids[-(need - len(r.tokens)):] + r.tokens
                out[i] = ngram_propose_host(seq, k + 1, n=self.spec.ngram)[1:]
        return out

    def step(self) -> List[int]:
        """Run ONE decode step (speculative: one draft-verify iteration,
        committing 1..draft_k+1 tokens per row) for every active row;
        returns the slots that finished (their tokens are complete —
        release them with :meth:`release`).

        Synchronous mode (default): dispatch and commit in one call.
        Dispatch-ahead mode (``dispatch_ahead=True``): the NEXT step is
        dispatched before the in-flight step's sampled tokens are
        fetched — when possible it chains directly on the in-flight
        step's device-resident row state, so the readback barrier
        overlaps the chained step's compute and the host scheduling
        work between calls runs in the device's shadow.  The finished
        slots returned are those of the COMMITTED (previous) step.
        Callers that mutate row membership or host row state
        (admit/adopt/release/evict) between steps must :meth:`flush`
        first, but for an :meth:`admit` that :meth:`seats_behind_step`
        allows: the step after it takes the commit-first ordering."""
        pending = [
            i for i, r in enumerate(self.slots)
            if r is not None and not r.prefill_done
        ]
        # dispatch-ahead fast path: chain the next step on the in-flight
        # step's device-side outputs (positions/gen_steps/active are
        # async futures with the same avals as the host mirrors — and
        # NOT donated, so the commit below can still read them).  The
        # chained dispatch reaches the device queue before the host
        # fetches a single token.  Speculation needs the committed
        # tokens to draft from and a pending chunked prefill needs the
        # host tick, so both take the commit-first ordering below
        # instead (the readback then only waits for whatever compute
        # the prior dispatch has not finished yet).  So does the step
        # after an admission that was queued behind the step in flight
        # (seats_behind_step): that step's device-side positions /
        # gen_steps / active do not hold the new row, and its commit and
        # the dispatch from the host mirrors run while the device runs
        # the prefill.
        behind = self._inflight is not None and self._inflight["behind"]
        if (self.dispatch_ahead and self._inflight is not None
                and self.spec is None and not pending and not behind
                and self.active.any()):
            prev, self._inflight = self._inflight, None
            nxt = self._dispatch(
                prev["positions"], prev["gen_steps"], prev["active"],
                overlapped=True,
            )
            # stash BEFORE the commit barrier: a commit failure resets
            # the arena, and reset() must drop the chained dispatch too
            # (its pools chain on the poisoned step)
            self._inflight = nxt
            finished = self._commit(prev)
            # the chained step's dispatch-time active view IS the
            # committed step's output actives (merged on the host now);
            # rows the commit finished are excluded, so a later commit
            # of the chained step can never re-finish a released slot
            nxt["was_active"] = self.active.copy()
            return finished
        finished = self.flush()
        if behind:
            # the device has the prefill to run: the host seconds from
            # this commit to the dispatch below are no gap of its
            self._t_results = None
        # chunked-prefill interleave: at most ONE pending chunk per
        # iteration, oldest admission first — a long prompt streams in
        # across iterations while the decode batch below keeps stepping,
        # so no prefill ever head-of-line-blocks active rows
        if pending:
            self._tick_prefill(
                min(pending, key=lambda i: self.slots[i].seq_id)
            )
        if not self.active.any():
            return finished
        fl = self._dispatch(
            self.positions, self.gen_steps, self.active, overlapped=False,
        )
        fl["was_active"] = self.active.copy()
        self._inflight = fl
        if self.dispatch_ahead:
            return finished
        return finished + self.flush()

    @property
    def has_inflight(self) -> bool:
        """True while a dispatched step's results are not yet fetched
        (dispatch-ahead mode only; always False when synchronous)."""
        return self._inflight is not None

    @property
    def inflight_rows(self) -> int:
        """Rows live at the dispatch of the step in flight: 0 with none in
        flight, and for a chained step that outlived its rows (the commit
        before it finished them all), which the device is long done with."""
        fl = self._inflight
        return 0 if fl is None else int(fl["was_active"].sum())

    def _dispatch(self, positions, gen_steps, active, *,
                  overlapped: bool) -> Dict[str, Any]:
        """Dispatch one decode step and ADOPT its device-side outputs
        immediately: pools/logits/counts/reject are async futures, so a
        later dispatch (prefill chunk, COW copy, the next step) queues
        behind this one on device instead of ever touching the
        donation-invalidated inputs.  Returns the in-flight record
        whose window/ncommit/row-state handles :meth:`_commit` fetches;
        the caller fills ``was_active`` with its dispatch-time view."""
        M = self.table_width_bucket()
        tables = np.full((self.capacity, M), NULL_BLOCK, np.int32)
        for i, r in enumerate(self.slots):
            if r is not None:
                tables[i, : len(r.table)] = r.table
        if self.ring_pages:
            # a model with window layers: every row's ring beside its table
            rings = np.full((self.capacity, self.ring_pages), NULL_BLOCK, np.int32)
            for i, r in enumerate(self.slots):
                if r is not None:
                    rings[i] = r.ring
            tables = (tables, rings)
        self._key, sub = self._jax.random.split(self._key)
        k = self.spec.draft_k if self.spec else 0
        drafts = (
            self._host_drafts() if self.spec
            else np.zeros((self.capacity, 1), np.int32)
        )
        fn = self._step_fn(M)
        # host-gap accounting (pfx_sched_host_gap_seconds_total): host time
        # between consuming one step's results and handing the device
        # its next dispatch.  A chained dispatch lands while the
        # previous step is still in flight — the device never waits on
        # the host, so its gap is zero by construction.
        if self._t_results is not None and not overlapped:
            self.stats["host_gap_s"] += max(
                0.0, time.monotonic() - self._t_results
            )
        # host-fed row state and a chained dispatch's device-side handles
        # must type alike, or each width bucket keys TWO compiles — the
        # warmed host-fed one and a chained one first paid mid-traffic
        # (place_on_mesh: ONE transfer for the host mirrors, a no-op for
        # the handles)
        with ledger_span("pfx.sched.decode_dispatch", self.stats,
                         "t_device_decode", width_bucket=M,
                         chained=overlapped):
            (tables, positions, gen_steps, max_news, active, forced_steps,
             drafts) = place_on_mesh(
                (tables, positions, gen_steps, self.max_news, active,
                 self.forced_steps, drafts), self.mesh,
            )
            try:
                with self.mesh:
                    (window, ncommit, pools_t, logits, counts, positions_t,
                     gen_steps_t, active_t, reject, *moe) = fn(
                        self.server.params, self._pools_tuple(),
                        tables, self._logits, self._counts,
                        positions, gen_steps, max_news, active,
                        forced_steps, self._reject, drafts, sub,
                    )
            except BaseException as exc:
                dead = self.reset()
                raise ArenaReset(
                    f"decode step failed ({type(exc).__name__}: {exc}); "
                    "arena reset",
                    dead,
                ) from exc
        self.pools = self._pools_of(pools_t)
        self._logits, self._counts = logits, counts
        self._reject = reject
        return {
            "window": window, "ncommit": ncommit,
            "positions": positions_t, "gen_steps": gen_steps_t,
            "active": active_t, "rows": list(self.slots), "k": k,
            "was_active": None, "width_bucket": M,
            "moe": moe[0] if moe else None,
            # a donating dispatch was queued behind this step
            # (_dispatch_donating): the next step does not chain on it,
            # and the interval after its commit is an admission's
            "behind": False,
            # the expert-layer counts of prefills queued behind it
            "moe_behind": [],
        }

    def flush(self) -> List[int]:
        """Commit the in-flight dispatched step, if any (no-op when
        synchronous or nothing is in flight); returns the slots it
        finished.  This is the flush the dispatch-ahead contract
        requires before any row-membership or host-row-state mutation:
        the commit merge only protects rows that join or leave AFTER
        the dispatch it is committing."""
        if self._inflight is None:
            return []
        prev, self._inflight = self._inflight, None
        return self._commit(prev)

    def _commit(self, fl: Dict[str, Any]) -> List[int]:
        """Fetch one dispatched step's sampled window and fold it into
        host state — the ONLY host-device barrier on the decode path.
        The dispatched computation's errors materialize here: any
        failure resets the arena exactly like a synchronous step
        failure, and the ArenaReset carries every live row — INCLUDING
        rows admitted while the step was in flight, whose pools chained
        onto the poisoned dispatch."""
        try:
            # the span closes (and books a failed fetch) before the reset:
            # reset/requeue cost belongs to host_sched (the iterate
            # residual), not readback
            with ledger_span("pfx.sched.readback", self.stats,
                             "t_readback") as rb:
                maybe_fire("cb_commit_crash", int(self.stats["steps"]) + 1)
                window = np.array(fl["window"])
                ncommit = np.array(fl["ncommit"])
                new_active = np.array(fl["active"])
                positions = np.array(fl["positions"])
                gen_steps = np.array(fl["gen_steps"])
                self._count_moe(fl["moe"])
                self._moe_pending.extend(fl["moe_behind"])
        except BaseException as exc:
            dead = self.reset()
            raise ArenaReset(
                f"decode step failed ({type(exc).__name__}: {exc}); "
                "arena reset",
                dead,
            ) from exc
        self._t_results = rb.t1
        was_active = fl["was_active"]
        framed: List[int] = []  # scheduler-owned rows this commit frames
        # merge, never overwrite: slots that joined (admit/adopt) or
        # left (release/evict) after the dispatch were not part of it —
        # the step carried their stale state through, and their fresh
        # host values must win over its outputs
        self.positions[was_active] = positions[was_active]
        self.gen_steps[was_active] = gen_steps[was_active]
        self.active[was_active] = new_active[was_active]
        self.stats["steps"] += 1
        finished: List[int] = []
        n_act = int(was_active.sum())
        # work counters: what this step needed (live rows, their context)
        # against what the paged kernel computed on: the slots live at
        # dispatch, which are the rows its grid visits, each up to its
        # context in whole grid steps (the latent kernel's work list holds
        # just those steps, at the positions the step started from)
        self.stats["row_steps"] += n_act
        self.stats["slot_steps"] += self.capacity
        self.stats["kv_tokens"] += int(self.positions[was_active].sum())
        if self.ring_pages:
            self.stats["kv_window_tokens"] += int(np.minimum(
                self.positions[was_active], int(self.mcfg.sliding_window)).sum())
        if self.row_state and not self._warmup:
            self.stats["ssm_row_steps"] += n_act * int(self.mcfg.ssm_layers)
            self.stats["ssm_slot_steps"] += self.capacity * int(self.mcfg.ssm_layers)
        if self.mcfg.hyper_connections and not self._warmup:
            self.stats["hc_tokens"] += n_act
        if self.mcfg.latent_attention:
            self.stats["grid_tokens"] += int(mla_tokens_computed(
                (positions - ncommit)[was_active], self.block, fl["width_bucket"]).sum())
        else:
            self.stats["grid_tokens"] += int(paged_tokens_computed(
                (positions - ncommit)[was_active], fl["k"] + 1, self.block, fl["width_bucket"],
                self.mcfg.num_attention_heads // self.mcfg.kv_heads,
            ).sum())
        t_chunk = time.monotonic()
        for i, r in enumerate(fl["rows"]):
            if r is None or not was_active[i]:
                continue
            committed = int(ncommit[i])
            start = len(r.tokens)
            for tok in window[i, :committed].tolist():
                if tok != self.gen.eos_token_id:
                    r.tokens.append(int(tok))
            if r.entry is not None:
                # token ledger: commits into scheduler-owned rows are
                # ADMITTED tokens — every one must later reach exactly
                # one terminal disposition (delivered / evicted_lost /
                # preempt_refunded / shed_after_admit).  EOS never
                # appends, so it never enters the books.
                self.stats["ledger_admitted"] += len(r.tokens) - start
                if len(r.tokens) > start and not self._warmup:
                    framed.append(r.seq_id)
            if (len(r.tokens) > start and not self._warmup
                    and r.entry is not None and r.entry.stream is not None):
                # token streaming: push this step's commits as they
                # land.  A broken sink must never kill the batch — the
                # tokens are committed either way.
                with ledger_span("pfx.sched.stream_flush", self.stats,
                                 "t_stream_flush"):
                    try:
                        r.entry.emit_stream(
                            r.row_idx, start, r.tokens[start:]
                        )
                    except Exception as sink_exc:
                        logger.warning(
                            f"stream sink failed for seq {r.seq_id}: "
                            f"{type(sink_exc).__name__}: {sink_exc}"
                        )
            if r.trace is not None:
                # per-chunk decode timeline: one event per iteration the
                # row decoded in, carrying its commit + spec-accept
                # counts (counts only — never token values)
                r.trace.event(
                    "decode_chunk", t=t_chunk, slot=i,
                    committed=committed,
                    accepted=(committed - 1 if self.spec else 0),
                    position=int(self.positions[i]),
                )
            if not new_active[i]:
                finished.append(i)
        # the commit's stamp is where the readback span ended: the same
        # time.monotonic() the pfx.sched.readback span took
        self.gap_books.commit(rb.t1, framed)
        if fl["behind"] and not self._warmup:
            # the prefill queued behind this step runs from here on
            self.gap_books.note("admission")
        if self.spec and n_act and not self._warmup:
            proposed = fl["k"] * n_act
            accepted = int(ncommit[was_active].sum()) - n_act
            self.stats["spec_proposed"] += proposed
            self.stats["spec_accepted"] += accepted
            reg = get_registry()
            reg.counter("pfx_spec_proposed_total").inc(proposed)
            reg.counter("pfx_spec_accepted_total").inc(accepted)
        return finished

    def release(self, slot: int) -> None:
        """Return a finished/evicted row's blocks to the pool and clear
        its batch slot (loud on an empty slot — a double release means
        the caller's bookkeeping aliased two rows).  With the prefix
        cache on, the row's PROMPT-prefix blocks are published to the
        radix index first (the index takes its own references, so the
        blocks outlive the row under the LRU budget); a row still
        mid-chunked-prefill never publishes — its blocks are only
        partially written."""
        row = self.slots[slot]
        if row is None:
            raise ValueError(f"slot {slot} is already empty")
        if self.prefix_enabled and not self._warmup and row.prefill_done:
            self._publish_prefix(row.prompt_ids, row.table)
        self.cache.release(row.seq_id)
        self.slots[slot] = None
        self.active[slot] = False
        self.positions[slot] = 0
        self.gen_steps[slot] = 0
        self.max_news[slot] = 0
        self.forced_steps[slot] = 0

    def preempt_row(self, slot: int) -> List[int]:
        """Evict an ACTIVE row mid-decode for a priority preemption and
        return the tokens it had committed — the scheduler requeues the
        row as a re-prefill continuation, so the request is paused, not
        killed.  The KV-valid prefix (prompt plus the committed tokens
        whose KV has been written: ``positions - prompt_len`` of them;
        the LAST sampled token's KV does not exist yet) is published to
        the radix index first, so the continuation's prefill is a prefix
        hit riding the drilled token-identical reuse path — with the
        spill tier as the backstop when the live blocks get evicted
        before the resume lands.  Caller must ``flush()`` first
        (row-membership mutation, the dispatch-ahead contract)."""
        self._classic_only("preempt-resume")
        row = self.slots[slot]
        if row is None:
            raise ValueError(f"slot {slot} is empty")
        if not row.prefill_done:
            raise ValueError(
                f"slot {slot} is mid-chunked-prefill; only decode-active "
                "rows are preemptible"
            )
        committed = list(row.tokens)
        if self.prefix_enabled and not self._warmup:
            kv_valid = max(0, int(self.positions[slot]) - row.prompt_len)
            self._publish_prefix(
                row.prompt_ids + committed[:kv_valid], row.table
            )
        self.cache.release(row.seq_id)
        self.slots[slot] = None
        self.active[slot] = False
        self.positions[slot] = 0
        self.gen_steps[slot] = 0
        self.max_news[slot] = 0
        self.forced_steps[slot] = 0
        return committed

    def reset(self) -> List["_Row"]:
        """Rebuild the arena after a failed donating dispatch: the old
        pools may be donation-invalidated and must never be reused.
        Returns the rows that were live (the caller fails their
        requests)."""
        dead = [r for r in self.slots if r is not None]
        # any in-flight dispatched step chains on the poisoned pools:
        # drop its handles, its results must never be committed
        self._inflight = None
        self._t_results = None
        for r in dead:
            self.cache.release(r.seq_id)
        # the rebuilt pools hold NONE of the old blocks' KV: every cached
        # prefix is donation-invalidated and must never resurface as a
        # hit — drop the whole index (its block references with it) AND
        # the spill store in the same breath: a host copy of a dead
        # arena's block must never readmit (the ArenaReset atomicity
        # half of the spill contract; clear() frees directly, never
        # through _evict_node, so nothing re-spills here either)
        self.cache.prefix.clear()
        self.cache.spill.clear()
        self.slots = [None] * self.capacity
        self.active[:] = False
        self.positions[:] = 0
        self.gen_steps[:] = 0
        self.max_news[:] = 0
        self.forced_steps[:] = 0
        self._init_device_state()
        return dead

    def warmup_prefill(self, prompt_lens: Sequence[int]) -> Dict[str, float]:
        """Prefill-replica warmup: compile the prefill family per prompt
        bucket by running one export end-to-end (the blocks are freed on
        export, so nothing stays allocated).  Warmup exports are not
        traffic — the handoff counters stay clean.  With the prefix
        cache on, the COW-copy and EXPORT-width chunk families a traffic
        hit routes through compile here too (warmup exports skip the
        index, so they never exercise — or pollute — the hit path)."""
        from paddlefleetx_tpu.models.gpt.generation import bucket_len

        per: Dict[str, float] = {}
        self._warmup = True
        try:
            if self.prefix_enabled:
                self._warm_copy_family()
            for n in prompt_lens:
                t0 = time.time()
                try:
                    if self.prefix_enabled:
                        self._warm_chunk_family(
                            int(n),
                            capacity_tokens=bucket_len(int(n), self.bucket),
                        )
                    self.prefill_export([1] * int(n), self.gen.max_dec_len)
                except Exception as exc:
                    raise RuntimeError(
                        f"prefill warmup failed at bucket {n} (warmed so "
                        f"far: {sorted(per) or 'none'}): "
                        f"{type(exc).__name__}: {exc}"
                    ) from exc
                per[str(int(n))] = round(time.time() - t0, 2)
                logger.info(
                    f"prefill warmup: prompt bucket {n} compiled in "
                    f"{per[str(int(n))]:.1f}s"
                )
        finally:
            self._warmup = False
        return per

    def _warm_copy_family(self) -> None:
        """Compile the COW arena copy (one compile ever): a null-block
        self-copy is a safe no-op dispatch.  Without it, the first
        mid-block-divergence hit after boot would pay this compile
        inside a scheduler iteration."""
        fn = self._copy_fn()
        pools_t = self._dispatch_donating(
            lambda: fn(
                self._pools_tuple(),
                self._jnp.int32(NULL_BLOCK), self._jnp.int32(NULL_BLOCK),
            ),
            "COW copy warmup",
        )
        self.pools = self._pools_of(pools_t)

    def _warm_chunk_family(self, n: int,
                           capacity_tokens: Optional[int] = None) -> None:
        """Compile the chunk fns a traffic prefix hit at bucket ``n``
        routes its suffix through (only needed when ``prefill_chunk`` is
        off — a chunked config's normal warmup admission already rides
        the chunk path): the SHORT-suffix chunk (one bucket quantum —
        the hot case, a long cached prefix plus a short new suffix) and
        the full-bucket chunk, both at the table-width bucket a
        bucket-``n`` row allocates.  A null-table dispatch with
        ``n_valid=0`` compiles each without touching the arena.
        Suffix buckets between those two still compile on first use,
        and the width bucket follows the DEFAULT decode budget exactly
        like the warmed step family does (a request with a much smaller
        max_tokens keys a narrower width and compiles then) — the same
        partial-coverage contract as the prompt buckets.

        ``capacity_tokens`` overrides the row capacity the table width
        is derived from: EXPORT tables cover only the prompt bucket
        (the decode budget is the decode replica's to hold), so a
        prefill replica warms a narrower width than a decode-capacity
        row would."""
        from paddlefleetx_tpu.models.gpt.generation import bucket_len

        jnp = self._jnp
        blocks = blocks_for(
            capacity_tokens if capacity_tokens is not None
            else self.row_capacity_tokens(int(n), self.gen.max_dec_len),
            self.block,
        )
        M = min(_pow2_at_least(blocks), _pow2_at_least(self.max_row_blocks))
        chunks = ({self.prefill_chunk} if self.prefill_chunk
                  else {self.bucket, bucket_len(int(n), self.bucket)})
        for t in sorted(chunks):
            fn = self._chunk_fn(t, M)
            toks = np.full((1, t), self.gen.pad_token_id, np.int32)
            tbl = np.full((M,), NULL_BLOCK, np.int32)
            pools_t, _ = self._dispatch_donating(
                lambda: fn(
                    self.server.params, jnp.asarray(toks),
                    self._pools_tuple(), jnp.asarray(tbl),
                    jnp.int32(0), jnp.int32(0), jnp.int32(0),
                ),
                "chunk warmup",
            )
            self.pools = self._pools_of(pools_t)

    def warmup(self, prompt_lens: Sequence[int]) -> Dict[str, float]:
        """Compile (prefill, step) for each prompt bucket at the default
        decode budget — the continuous counterpart of
        `GenerationServer.warmup`; fails loudly naming the bucket.  With
        the prefix cache on, also compiles the chunk + COW-copy families
        a traffic hit will route through (suffix buckets smaller than
        the warmed list still compile on first use — the same
        partial-coverage contract as the prompt buckets themselves)."""
        per: Dict[str, float] = {}
        self._warmup = True  # warmup admits/steps are not traffic
        # warmup drives step()/release() with synchronous expectations
        # (step, then inspect/release the slot): force the synchronous
        # path for its duration regardless of the dispatch-ahead knob
        ahead, self.dispatch_ahead = self.dispatch_ahead, False
        try:
            if self.prefix_enabled:
                self._warm_copy_family()
            for n in prompt_lens:
                t0 = time.time()
                try:
                    if self.prefix_enabled and self.prefill_chunk == 0:
                        self._warm_chunk_family(int(n))
                    slot = self.admit(
                        [1] * int(n), max_new=self.gen.max_dec_len
                    )
                    # with chunked prefill on, admission returns
                    # mid-prefill: drive the remaining chunks so the
                    # whole chunk family compiles before traffic
                    guard = 0
                    while (self.slots[slot] is not None
                           and not self.slots[slot].prefill_done):
                        self.step()
                        guard += 1
                        if guard > 4096:
                            raise RuntimeError(
                                "warmup prefill never completed"
                            )
                    self.step()
                    if self.slots[slot] is not None:
                        self.release(slot)
                except Exception as exc:
                    raise RuntimeError(
                        f"continuous warmup failed at bucket {n} (warmed so "
                        f"far: {sorted(per) or 'none'}): "
                        f"{type(exc).__name__}: {exc}"
                    ) from exc
                per[str(int(n))] = round(time.time() - t0, 2)
                logger.info(
                    f"continuous warmup: prompt bucket {n} compiled in "
                    f"{per[str(int(n))]:.1f}s"
                )
        finally:
            self._warmup = False
            self.dispatch_ahead = ahead
        return per


class ContinuousScheduler:
    """Iteration-level scheduler with the RequestQueue admission surface.

    ``submit`` -> bounded waiting queue (QueueFull/QueueClosed exactly
    like RequestQueue); the scheduler thread loops one decode step per
    iteration: shed expired waiting entries, evict expired ACTIVE rows
    mid-decode (blocks freed immediately), admit from the queue head
    while slots + blocks allow (prefill-on-admit), then step the batch.
    """

    # where an admission's dispatch found the device: queued behind the
    # step in flight, after a flush of it in the same iteration, or with
    # nothing (that held live rows) in flight
    ADMIT_PATHS = ("behind_step", "after_flush", "idle")

    def __init__(self, engine: PagedDecodeEngine, *, max_depth: int = 64,
                 name: str = "serve-cb",
                 dispatch_ahead: bool = True,
                 tenant_config: Optional[TenantConfig] = None,
                 preempt_min_tokens: int = 8) -> None:
        if max_depth < 1:
            raise ValueError(f"max_depth must be >= 1, got {max_depth}")
        if preempt_min_tokens < 1:
            raise ValueError(
                f"preempt_min_tokens must be >= 1, got {preempt_min_tokens}"
            )
        self.engine = engine
        self.max_depth = int(max_depth)
        self.name = name
        # multi-tenant isolation (docs/serving.md "Multi-tenant
        # isolation"): the admission pull is a deficit round-robin
        # across tenant queues (weights from the config; FCFS within a
        # tenant — one tenant degenerates to exactly the old FCFS), and
        # a high-priority arrival that cannot fit may preempt the
        # lowest-priority active row once it has committed at least
        # preempt_min_tokens since its (last) admission — the
        # minimum-progress floor that makes preemption thrash-free.
        self.tenant_config = tenant_config or TenantConfig()
        self._fair = DeficitRoundRobin(self.tenant_config.weight)
        self._tenant_labels = TenantLabelCap(
            seed=self.tenant_config.known_tenants()
        )
        self.preempt_min_tokens = int(preempt_min_tokens)
        # cumulative per-tenant-label row counts (scheduler thread only;
        # the decision log diffs them per iteration and the same sites
        # bump the labeled registry counters, so replaying an
        # untruncated log reproduces pfx_tenant_admitted_total /
        # pfx_tenant_preemptions_total exactly)
        self._tenant_admitted: Dict[str, int] = {}
        self._tenant_preempted: Dict[str, int] = {}
        # dispatch-ahead decode (docs/decode_path.md); False is the
        # synchronous stepping the tests compare it with.  The scheduler
        # (not the engine ctor) sets it because direct engine drivers
        # need the synchronous default.
        self.dispatch_ahead = bool(dispatch_ahead)
        engine.dispatch_ahead = self.dispatch_ahead
        self._entries: List[_CBEntry] = []
        # peer prefix adoptions (POST /admin/adopt_prefixes) queued for
        # the scheduler thread: (meta, arrays, future) triples, drained
        # at iteration boundaries so donated dispatches stay
        # single-threaded with every other arena touch
        self._admin_tasks: List[tuple] = []
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._closed = False
        self._busy_since: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._req_counter = 0
        self._step_counter = 0
        # per-iteration decision log (docs/observability.md): one
        # structured row per scheduler iteration — admitted/evicted/shed
        # counts, block + width-bucket state, spec proposed/accepted
        # deltas.  Bounded (PFX_DECISION_LOG_CAP, default 4096) and
        # gated on tracing being enabled (PFX_TRACE_SAMPLE>0): replaying
        # an untruncated log reproduces pfx_prefill_admits_total /
        # pfx_request_evictions_total / pfx_spec_accepted_total exactly
        # (utils/tracing.replay_decision_log; agreement-tested).
        self.decision_log: deque = deque(
            maxlen=_env_int("PFX_DECISION_LOG_CAP", 4096)
        )
        self._iter_counter = 0
        # goodput ledgers (docs/observability.md "Goodput ledger").
        # Time: every scheduler-thread wall-second lands in exactly one
        # bucket — idle is stamped in _run's wait loop, the device/
        # readback/stream buckets are baseline-diffed off the engine's
        # per-phase accumulators inside _iterate, and host_sched is the
        # iterate residual, so the bucket sum closes against
        # _sched_wall_s BY CONSTRUCTION (drilled to <=1%).
        self._time_ledger: Dict[str, float] = {
            "device_decode": 0.0, "device_prefill": 0.0,
            "host_sched": 0.0, "readback": 0.0,
            "stream_flush": 0.0, "idle": 0.0,
        }
        self._sched_wall_s = 0.0
        # the slow-iteration watcher (telemetry.StallWatch): _iterate hands
        # it every iteration's stamps and bucket seconds; one that ran far
        # past its kind's median leaves a pfx.stall event
        self._stall = StallWatch(
            "sched.iterate",
            ("device_decode", "device_prefill", "readback", "stream_flush",
             "host_sched"),
            device_wait=("readback",),
        )
        self._stall_carry = 0  # prefill buckets the next readback waits for
        self._n_seated = 0  # units _iterate_inner picked this iteration
        self._t_device_free = 0.0  # _iterate_inner / _flush_engine stamp
        self._flushed = False  # _iterate_inner / _flush_engine, as above
        # Tokens: bank accounting over ADMITTED (committed) tokens.
        # admitted == delivered + evicted_lost + preempt_refunded +
        # shed_after_admit + (tokens still on live rows) holds EXACTLY
        # at every iteration boundary; preempt refunds the on-book
        # amount and a resume re-admits its carried prefix, so the
        # equation survives any preempt/resume interleaving.  Scheduler
        # thread writes only; _ledger_admit_base folds the engine's
        # commit-site counter per call site.
        self._tok_ledger: Dict[str, int] = {
            "admitted": 0, "delivered": 0, "evicted_lost": 0,
            "preempt_refunded": 0, "shed_after_admit": 0,
        }
        self._ledger_admit_base = 0
        # per-tenant-label occupancy integrals (billing-grade cost
        # attribution): decode-slot seconds and KV-block seconds,
        # accrued over each iteration's duration for every live row.
        # The scheduler never parks with live rows (_run's wait
        # predicate), so iterate durations cover all occupancy.
        self._tenant_occ: Dict[str, Dict[str, float]] = {}
        # engine-side debug view published by the scheduler thread at
        # the end of every iteration (read by debug_state() without
        # taking any lock the scheduler holds during decode).  With
        # tracing disabled AND no /debug client ever seen, the per-
        # iteration rebuild is skipped — the zero-observability-work
        # configuration pays nothing; the first debug_state() call
        # latches interest and views are fresh from the next iteration
        self._debug_requested = False
        self._debug_engine: Dict[str, Any] = self._engine_debug_view()
        # same pfx_queue_* registry names as RequestQueue (one scheduler
        # runs per process; /healthz's queue block works unchanged) plus
        # the continuous-only counters
        self.stats = StatsView(
            {
                "submitted": "pfx_queue_submitted_total",
                "completed": "pfx_queue_completed_total",
                "batches": "pfx_queue_batches_total",
                "coalesced_batches": "pfx_queue_coalesced_batches_total",
                "coalesced_requests": "pfx_queue_coalesced_requests_total",
                "shed_deadline": "pfx_queue_shed_deadline_total",
                "rejected_full": "pfx_queue_rejected_full_total",
                "rejected_closed": "pfx_queue_rejected_closed_total",
                "gen_errors": "pfx_queue_gen_errors_total",
                "evictions": "pfx_request_evictions_total",
                "prefill_admits": "pfx_prefill_admits_total",
                # the same admissions by where their dispatch found the
                # device (pfx_sched_admissions_total{path=}, collect())
                **{"admits_" + path: None for path in self.ADMIT_PATHS},
                # instance-local: the per-tenant labeled counter
                # (pfx_tenant_preemptions_total) is the exported form
                "preemptions": None,
            }
        )
        get_registry().register_collector(self)

    def collect(self):
        eng = self.engine
        occ = eng.active_rows() / max(1, eng.capacity)
        cstats = eng.cache.stats()
        out = [
            ("pfx_queue_depth", {}, float(self.depth())),
            ("pfx_queue_busy_seconds", {}, self.busy_seconds()),
            ("pfx_batch_occupancy", {}, occ),
            ("pfx_kv_blocks_used", {}, float(cstats["kv_blocks_used"])),
            ("pfx_kv_blocks_free", {}, float(cstats["kv_blocks_free"])),
            # free + reclaimable cached-prefix blocks: what an admission
            # can actually obtain — /healthz surfaces it and the decode
            # pool controller + router scoring read it (a nearly-full
            # arena must stop attracting adoptions it will bounce)
            ("pfx_kv_blocks_available", {},
             float(eng.cache.available_blocks())),
            # live arena payload bytes: used blocks x K+V bytes/block —
            # int8 halves the per-block bytes, the acceptance evidence.
            # kv_blocks_used counts PHYSICAL blocks (refcount-deduped),
            # so neither gauge can exceed the arena under any sharing
            ("pfx_kv_bytes", {},
             float(cstats["kv_blocks_used"]) * eng.kv_block_bytes()
             # a model with window layers: its held ring pages beside them
             + float(cstats.get("kv_ring_blocks_used", 0))
             * eng.ring_bytes_per_row() / max(1, eng.ring_pages)),
            # what one cached token takes over all layers: it comes from
            # the model (per-head K and V, or one latent), and the arena's
            # rows and --kv-blocks auto follow it
            ("pfx_kv_bytes_per_token", {}, float(eng.kv_bytes_per_token())),
            # what a row keeps beside its pages whatever its length (a
            # state-space layer's recurrent state and conv columns; 0 for
            # a block whose every layer caches tokens)
            ("pfx_state_bytes_per_row", {}, float(eng.state_bytes_per_row())),
            # what a row's rings of window-layer pages take whatever its
            # length (0 for a model without window layers)
            ("pfx_kv_ring_bytes_per_row", {}, float(eng.ring_bytes_per_row())),
            ("pfx_prefix_cached_blocks", {},
             float(cstats["prefix_cached_blocks"])),
            # host-RAM spill tier occupancy (0 when --prefix-spill-bytes
            # is off; the spills/readmits/discards counters live in the
            # engine's readmit/spill sites)
            ("pfx_prefix_spill_bytes", {},
             float(cstats["prefix_spill_bytes"])),
            ("pfx_prefix_spill_entries", {},
             float(cstats["prefix_spill_entries"])),
        ]
        if eng.ring_pages:
            # the two classes of pages of one arena, each by its own count
            for cls, key in (("full", "kv_blocks"), ("window", "kv_ring_blocks")):
                out.append(("pfx_kv_pages_held", {"class": cls}, float(cstats[key + "_used"])))
                out.append(("pfx_kv_pages_free", {"class": cls}, float(cstats[key + "_free"])))
        if eng.spec is not None:
            prop = float(eng.stats["spec_proposed"])
            out.append((
                "pfx_spec_accept_rate", {},
                float(eng.stats["spec_accepted"]) / prop if prop else 0.0,
            ))
        # goodput ledgers (docs/observability.md "Goodput ledger"):
        # the per-bucket time counters close against the wall counter
        # (<=1% drift) and the token dispositions close against
        # admitted exactly once in_flight drains to zero
        for b, v in sorted(self._time_ledger.items()):
            out.append((
                "pfx_sched_time_seconds_total", {"bucket": b}, round(v, 6),
            ))
        out.append((
            "pfx_sched_wall_seconds_total", {}, round(self._sched_wall_s, 6),
        ))
        # device-starved host seconds (host_gap_s): overlaps the
        # host_sched/readback buckets rather than joining the exhaustive
        # bucket family — it is the goodput_frac subtrahend
        # (goodput = 1 - host_gap / non-idle wall)
        out.append((
            "pfx_sched_host_gap_seconds_total", {},
            round(float(eng.stats["host_gap_s"]), 6),
        ))
        # the token gap's books: every gap between two frames of a row,
        # counted once under what its interval held (TokenGapBooks)
        books = eng.gap_books
        for held in books.HELD:
            out.append((
                "pfx_sched_token_gaps_total", {"held": held},
                float(books.gaps[held]),
            ))
            out.append((
                "pfx_sched_token_gap_seconds_total", {"held": held},
                round(books.seconds[held], 6),
            ))
        out.append((
            "pfx_sched_admit_host_seconds_total", {},
            round(books.admit_host_s, 6),
        ))
        out.append((
            "pfx_sched_gap_books_errors_total", {}, float(books.errors),
        ))
        for path in self.ADMIT_PATHS:
            out.append((
                "pfx_sched_admissions_total", {"path": path},
                float(self.stats["admits_" + path]),
            ))
        # work counted at the commit of every decode step (engine stats)
        for key, name in (
            ("steps", "pfx_sched_decode_steps_total"),
            ("row_steps", "pfx_sched_decode_row_steps_total"),
            ("slot_steps", "pfx_sched_decode_slot_steps_total"),
            ("kv_tokens", "pfx_sched_decode_kv_tokens_total"),
            ("grid_tokens", "pfx_sched_decode_grid_tokens_total"),
        ):
            out.append((name, {}, float(eng.stats[key])))
        if eng.ring_pages:
            out.append(("pfx_sched_decode_kv_window_tokens_total", {},
                        float(eng.stats["kv_window_tokens"])))
        if eng.mcfg.hyper_connections:
            out.append(("pfx_hc_tokens_total", {}, float(eng.stats["hc_tokens"])))
        if eng.row_state:
            for key, name in (
                ("ssm_row_steps", "pfx_ssm_row_steps_total"),
                ("ssm_slot_steps", "pfx_ssm_slot_steps_total"),
                ("ssm_prefill_tokens", "pfx_ssm_prefill_tokens_total"),
            ):
                out.append((name, {}, float(eng.stats[key])))
        if eng.mcfg.num_experts > 1:
            for key, name in (
                ("moe_pairs", "pfx_moe_serve_pairs_total"),
                ("moe_held_pairs", "pfx_moe_serve_held_pairs_total"),
                ("moe_held_max_pairs", "pfx_moe_serve_held_max_pairs_total"),
                ("moe_grouped_calls", "pfx_moe_serve_grouped_calls_total"),
            ):
                out.append((name, {}, float(eng.stats[key])))
        for d, v in sorted(self._tok_ledger.items()):
            out.append((
                "pfx_token_ledger_total", {"disposition": d}, float(v),
            ))
        out.append((
            "pfx_token_ledger_in_flight", {}, float(self._ledger_in_flight()),
        ))
        for lab, occ in sorted(self._tenant_occ.items()):
            out.append((
                "pfx_tenant_slot_seconds_total", {"tenant": lab},
                round(occ["slot_s"], 6),
            ))
            out.append((
                "pfx_tenant_kv_block_seconds_total", {"tenant": lab},
                round(occ["kv_block_s"], 6),
            ))
        per_tenant: Dict[str, int] = {}
        with self._lock:
            for e in self._entries:
                lab = self._tenant_labels.label(e.tenant)
                per_tenant[lab] = per_tenant.get(lab, 0) + 1
        for lab, n in sorted(per_tenant.items()):
            out.append(("pfx_tenant_queue_depth", {"tenant": lab}, float(n)))
        return out

    # -- admission (RequestQueue-compatible surface) --------------------
    def submit(self, prompts: Sequence[Any], max_new_tokens: int, *,
               coalesce_key=None, deadline_s: Optional[float] = None,
               stream=None, tenant: Optional[str] = None,
               priority: int = 0) -> RequestFuture:
        """``stream`` (optional): a ``stream(row_idx, start, tokens)``
        callable invoked on the scheduler thread as tokens commit —
        the token-streaming hook tools/serve.py's SSE path plugs in
        (see :class:`_CBEntry`).  ``tenant``/``priority`` place the
        entry in its weighted-fair tenant queue and priority class."""
        if not prompts:
            raise ValueError("prompts must be non-empty")
        for p in prompts:
            self.engine.validate_request(len(p), int(max_new_tokens))
        entry = _CBEntry(
            prompts=[list(p) for p in prompts],
            max_new=int(max_new_tokens),
            deadline=(time.monotonic() + float(deadline_s))
            if deadline_s is not None else None,
            future=RequestFuture(),
            enqueued_at=time.monotonic(),
            stream=stream,
            tenant=normalize_tenant(tenant),
            priority=int(priority),
        )
        entry.future.times["enqueued"] = entry.enqueued_at
        # deep-dive tracing (sampled; no-op at PFX_TRACE_SAMPLE=0):
        # attached BEFORE the entry becomes visible to the scheduler
        # thread, or a fast pickup could miss the prefill span
        attach_request_trace(
            entry.future, t0=entry.enqueued_at, scheduler=self.name,
            prompts=len(entry.prompts), max_new=entry.max_new,
        )
        try:
            with self._wake:
                if self._closed:
                    self.stats["rejected_closed"] += 1
                    raise QueueClosed(f"{self.name} queue is draining")
                if len(self._entries) >= self.max_depth:
                    self.stats["rejected_full"] += 1
                    raise QueueFull(
                        f"{self.name} queue full ({self.max_depth} waiting)"
                    )
                self._entries.append(entry)
                self.stats["submitted"] += 1
                self._wake.notify_all()
        except (QueueClosed, QueueFull):
            discard_request_trace(entry.future)  # never admitted
            raise
        return entry.future

    def submit_handoff(self, meta: Dict[str, Any], arrays: Dict[str, Any],
                       *, deadline_s: Optional[float] = None,
                       tenant: Optional[str] = None, priority: int = 0
                       ) -> RequestFuture:
        """Admit a disaggregated KV-handoff payload (one prefilled row
        from a prefill replica): same bounded-queue/deadline surface as
        :meth:`submit`, but the admission loop ADOPTS the exported blocks
        instead of prefilling.  Pre-admission validation is loud: an
        incompatible payload (dtype/block-size/pool-shape) or a
        could-never-fit budget raises ``ValueError`` before a queue slot
        is spent (HTTP 400 in tools/serve.py)."""
        check_handoff_meta(
            meta, block=self.engine.block, kv_dtype=self.engine.kv_dtype,
            pool_sig=self.engine._pool_sig(),
        )
        prompt = [int(t) for t in meta.get("prompt_ids", [])]
        max_new = int(meta.get("max_new", 0))
        self.engine.validate_request(len(prompt), max_new)
        entry = _CBEntry(
            prompts=[prompt],
            max_new=max_new,
            deadline=(time.monotonic() + float(deadline_s))
            if deadline_s is not None else None,
            future=RequestFuture(),
            enqueued_at=time.monotonic(),
            handoff=(meta, arrays),
            tenant=normalize_tenant(tenant),
            priority=int(priority),
        )
        entry.future.times["enqueued"] = entry.enqueued_at
        attach_request_trace(
            entry.future, t0=entry.enqueued_at, scheduler=self.name,
            prompts=1, max_new=entry.max_new,
        )
        try:
            with self._wake:
                if self._closed:
                    self.stats["rejected_closed"] += 1
                    raise QueueClosed(f"{self.name} queue is draining")
                if len(self._entries) >= self.max_depth:
                    self.stats["rejected_full"] += 1
                    raise QueueFull(
                        f"{self.name} queue full ({self.max_depth} waiting)"
                    )
                self._entries.append(entry)
                self.stats["submitted"] += 1
                self._wake.notify_all()
        except (QueueClosed, QueueFull):
            discard_request_trace(entry.future)  # never admitted
            raise
        return entry.future

    def submit_prefix_adoption(self, meta: Dict[str, Any],
                               arrays: Dict[str, Any]) -> RequestFuture:
        """Queue a draining peer's exported prefix payload for adoption
        on the scheduler thread (POST /admin/adopt_prefixes).  The FULL
        structural validation runs here, pre-queue — a torn or
        incompatible payload raises ``ValueError`` now (HTTP 400) and
        never reaches a donated dispatch (the adopt rule).  The future
        resolves with the adopted-block count once the scheduler folds
        the payload in at an iteration boundary."""
        self.engine.validate_prefix_payload(meta, arrays)
        fut = RequestFuture()
        with self._wake:
            if self._closed:
                raise QueueClosed(f"{self.name} queue is draining")
            self._admin_tasks.append((meta, arrays, fut))
            self._wake.notify_all()
        return fut

    def depth(self) -> int:
        with self._lock:
            return len(self._entries)

    def busy_seconds(self) -> float:
        with self._lock:
            if self._busy_since is None:
                return 0.0
            return time.monotonic() - self._busy_since

    # -- goodput ledgers ------------------------------------------------
    def _fold_admitted(self) -> None:
        """Fold the engine's commit-site admitted-token counter into the
        scheduler ledger.  Called right after any step/flush that can
        commit tokens and BEFORE the rows are resolved or failed, so
        delivered/lost never outruns admitted within an iteration."""
        cur = int(self.engine.stats["ledger_admitted"])
        if cur != self._ledger_admit_base:
            self._tok_ledger["admitted"] += cur - self._ledger_admit_base
            self._ledger_admit_base = cur

    def _row_on_books(self, row: "_Row") -> int:
        """Tokens currently on the books for one live slot row: commits
        since its (last) admission plus the resume prefix it re-admitted
        (row_prefill carries it for rows seated via a resume)."""
        if row.entry is None:
            return 0
        return len(row.tokens) + len(
            row.entry.row_prefill.get(row.row_idx, ())
        )

    def _ledger_in_flight(self) -> int:
        """Admitted tokens without a terminal disposition yet: the sum
        over live scheduler-owned rows of their on-book tokens."""
        return sum(
            self._row_on_books(r)
            for r in self.engine.slots if r is not None
        )

    def time_ledger(self) -> Dict[str, Any]:
        """Snapshot of the scheduler-thread time ledger (bench/report
        accessor): per-bucket seconds plus the wall total they close
        against."""
        return {
            "buckets": dict(self._time_ledger),
            "wall_s": self._sched_wall_s,
        }

    def token_ledger(self) -> Dict[str, int]:
        """Snapshot of the token ledger plus the live in-flight count —
        ``admitted == delivered + evicted_lost + preempt_refunded +
        shed_after_admit + in_flight`` holds exactly at iteration
        boundaries (and with ``in_flight == 0`` at quiescence)."""
        out = dict(self._tok_ledger)
        out["in_flight"] = self._ledger_in_flight()
        return out

    def try_remove(self, future: RequestFuture) -> bool:
        """Shed a WAITING entry (no row admitted yet).  An entry already
        in the running batch resolves via mid-decode eviction at its
        deadline instead."""
        with self._wake:
            for e in self._entries:
                if e.future is future and e.next_row == 0:
                    self._entries.remove(e)
                    self.stats["shed_deadline"] += 1
                    if e.future.trace is not None:
                        e.future.trace.event("shed", reason="handler_timeout")
                    e.future.set_exception(
                        DeadlineExceeded("deadline exceeded while queued")
                    )
                    return True
        return False

    # -- live introspection (GET /debug/state) --------------------------
    def _engine_debug_view(self) -> Dict[str, Any]:
        """The engine-side half of debug_state(), built ONLY on the
        scheduler thread (or before it starts): per-row positions and
        budgets, arena occupancy/fragmentation, width bucket, compile-
        key family counts.  Carries lengths/counts, never token ids."""
        eng = self.engine
        rows = []
        for i, r in enumerate(eng.slots):
            if r is None:
                continue
            rows.append({
                "slot": i,
                "seq_id": r.seq_id,
                "prompt_len": r.prompt_len,
                "max_new": r.max_new,
                "position": int(eng.positions[i]),
                "gen_step": int(eng.gen_steps[i]),
                "tokens_out": len(r.tokens),
                "blocks": len(r.table),
                "active": bool(eng.active[i]),
                "prefix_hit_tokens": r.prefix_hit,
                "prefill_pending": len(r.pending),
            })
        view: Dict[str, Any] = {
            # which scheduler iteration this view reflects: staleness is
            # visible to the reader, never silent
            "as_of_iter": self._iter_counter,
            "batch": {
                "capacity": eng.capacity,
                "active_rows": eng.active_rows(),
                "occupancy": round(
                    eng.active_rows() / max(1, eng.capacity), 4
                ),
                "width_bucket": eng.table_width_bucket(),
                "rows": rows,
            },
            "arena": eng.cache.stats(),
            "overlap": {
                "dispatch_ahead": bool(eng.dispatch_ahead),
                "inflight": eng.has_inflight,
                "host_gap_s": round(float(eng.stats["host_gap_s"]), 6),
            },
            "compiled": {
                "prefill_families": len(eng._compiled_prefill),
                "step_families": len(eng._compiled_step),
                "chunk_families": len(eng._compiled_chunk),
                "traces": int(eng.stats["traces"]),
            },
            # goodput ledgers, snapshotted in the SAME build as the row
            # list above: tokens.admitted == delivered + evicted_lost +
            # preempt_refunded + shed_after_admit + tokens_in_flight
            # holds EXACTLY within this view
            "goodput": {
                "time_s": {
                    k: round(v, 6) for k, v in self._time_ledger.items()
                },
                "wall_s": round(self._sched_wall_s, 6),
                "tokens": dict(self._tok_ledger),
                "tokens_in_flight": self._ledger_in_flight(),
                "tenant_occupancy": {
                    lab: {
                        "slot_s": round(occ["slot_s"], 6),
                        "kv_block_s": round(occ["kv_block_s"], 6),
                    }
                    for lab, occ in sorted(self._tenant_occ.items())
                },
            },
        }
        if eng.prefix_enabled or eng.prefill_chunk:
            pfx = eng.cache.prefix
            view["prefix_cache"] = {
                "enabled": eng.prefix_enabled,
                "budget_blocks": pfx.budget,
                "cached_blocks": pfx.cached_blocks(),
                "hits": int(pfx.stats["hits"]),
                "misses": int(pfx.stats["misses"]),
                "hit_tokens": int(pfx.stats["hit_tokens"]),
                "evictions": int(pfx.stats["evictions"]),
                "prefill_chunk": eng.prefill_chunk,
                "prefill_chunks": int(eng.stats["prefill_chunks"]),
                "prefill_tokens": int(eng.stats["prefill_tokens"]),
                "spill_budget_bytes": eng.cache.spill.budget,
                "spill_bytes": eng.cache.spill.bytes_used(),
                "spill_entries": len(eng.cache.spill),
                "spills": int(eng.cache.spill.stats["spills"]),
                "readmits": int(eng.cache.spill.stats["readmits"]),
                "spill_discards": int(eng.cache.spill.stats["discards"]),
                "migrate_adopted": int(eng.stats["migrate_adopted"]),
            }
        if eng.spec is not None:
            prop = int(eng.stats["spec_proposed"])
            acc = int(eng.stats["spec_accepted"])
            view["spec"] = {
                "draft_k": eng.spec.draft_k,
                "proposed": prop,
                "accepted": acc,
                "accept_rate": round(acc / prop, 4) if prop else 0.0,
            }
        return view

    def _publish_debug(self) -> None:
        # one atomic reference assignment: readers get either the old
        # or the new fully-built view, never a torn one
        self._debug_engine = self._engine_debug_view()

    def debug_state(self) -> Dict[str, Any]:
        """Read-only snapshot for ``GET /debug/state``: the waiting
        queue (under this scheduler's lock, briefly) plus the engine
        view.  While the scheduler is mid-iteration the view is the one
        PUBLISHED at the last iteration end (the HTTP thread never
        touches live engine state, so a decode step is never blocked or
        torn); while the scheduler is provably parked (``_busy_since``
        is None under this lock, and it cannot enter ``_iterate``
        without re-acquiring it) the view is rebuilt LIVE here — an
        idle, quiesced server always reports current arena/row state
        even with tracing disabled.  ``as_of_iter`` marks which
        iteration the view reflects."""
        self._debug_requested = True
        now = time.monotonic()
        with self._lock:
            waiting = [
                {
                    "age_s": round(now - e.enqueued_at, 4),
                    "prompts": len(e.prompts),
                    "admitted_rows": e.next_row,
                    "max_new": e.max_new,
                    "deadline_in_s": (
                        round(e.deadline - now, 4)
                        if e.deadline is not None else None
                    ),
                    "tenant": e.tenant,
                    "priority": e.priority,
                    "requeued_rows": len(e.requeue_rows),
                }
                for e in self._entries
            ]
            tenant_admitted = dict(self._tenant_admitted)
            tenant_preempted = dict(self._tenant_preempted)
            closed = self._closed
            busy = (
                now - self._busy_since if self._busy_since is not None else 0.0
            )
            decisions = list(self.decision_log)  # appended under this lock
            if self._busy_since is None:
                # scheduler parked: engine state is stable, refresh the
                # view (O(capacity) dict build — microseconds; the next
                # iteration can't start until we release this lock)
                self._publish_debug()
        # aggregate per LABEL (the top-k fold) so the keys line up with
        # the admitted/preempted counters; raw names stay on the
        # per-entry waiting rows above
        tenants: Dict[str, Dict[str, Any]] = {}
        for w in waiting:
            lab = self._tenant_labels.label(w["tenant"])
            t = tenants.setdefault(lab, {"waiting": 0, "admitted_rows": 0})
            t["waiting"] += 1
        for lab, n in tenant_admitted.items():
            tenants.setdefault(lab, {"waiting": 0})["admitted_rows"] = n
        for lab, n in tenant_preempted.items():
            tenants.setdefault(lab, {"waiting": 0})["preempted_rows"] = n
        return {
            "scheduler": "continuous",
            "depth": len(waiting),
            "waiting": waiting,
            "tenants": tenants,
            "preempt_min_tokens": self.preempt_min_tokens,
            "busy_s": round(busy, 4),
            "closed": closed,
            "iterations": self._iter_counter,
            "decisions": decisions,
            # slow iterations: count, excess seconds, the last 8 events
            "stalls": self._stall.summary(),
            **self._debug_engine,
        }

    # -- lifecycle ------------------------------------------------------
    def start(self) -> "ContinuousScheduler":
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name=f"{self.name}-scheduler", daemon=True
            )
            self._thread.start()
        return self

    def close(self) -> None:
        with self._wake:
            self._closed = True
            self._wake.notify_all()

    def join(self, timeout: Optional[float] = None) -> bool:
        if self._thread is None:
            return True
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> bool:
        self.close()
        if not drain:
            with self._wake:
                while self._entries:
                    e = self._entries.pop(0)
                    e.future.set_exception(
                        QueueClosed(f"{self.name} queue shut down")
                    )
                self._wake.notify_all()
        return self.join(timeout)

    def warmup(self, prompt_lens: Sequence[int]) -> Dict[str, float]:
        per = self.engine.warmup(prompt_lens)
        self._publish_debug()  # /debug/state sees the warmed compile keys
        return per

    # -- scheduler loop -------------------------------------------------
    def _has_live_rows(self) -> bool:
        return any(r is not None for r in self.engine.slots)

    def _park(self) -> bool:
        """Wait for work; False once the queue is closed and drained.
        Time-ledger idle: the parked wait between iterations.  _iterate
        accounts its own duration, so idle + the iterate folds cover this
        thread's whole wall clock."""
        idle = ledger_span("pfx.sched.idle", self._time_ledger, "idle")
        waited = False
        try:
            with idle, self._wake:
                while (not self._entries and not self._admin_tasks
                       and not self._has_live_rows()):
                    if self._closed:
                        return False  # drained
                    self._wake.wait()
                    waited = True
                self._busy_since = time.monotonic()
            return True
        finally:
            self._sched_wall_s += idle.seconds
            if waited:
                # the next iteration's CPU clocks start after the wait
                self._stall.stamp()

    def _run(self) -> None:
        while self._park():
            try:
                self._iterate()
            finally:
                with self._lock:
                    self._busy_since = None

    def _shed_locked(self, entry: _CBEntry) -> None:
        self.stats["shed_deadline"] += 1
        waited = time.monotonic() - entry.enqueued_at
        logger.warning(
            f"{self.name}: shed expired request after {waited:.2f}s queued"
        )
        if entry.future.trace is not None:
            entry.future.trace.event("shed", reason="expired_in_queue")
        entry.future.set_exception(
            DeadlineExceeded(f"deadline exceeded after {waited:.2f}s queued")
        )

    def _evict_entry(self, entry: _CBEntry, reason: str) -> None:
        """Mid-decode eviction: free every admitted row of the entry and
        resolve its future.  Blocks return to the pool IMMEDIATELY — the
        next admission can use them this same iteration.  Token ledger:
        the rows' on-book tokens get their terminal disposition here —
        ``shed_after_admit`` when the entry expired only PARTIALLY
        admitted (reason ``expired_partial``), ``evicted_lost`` for a
        fully-admitted entry evicted mid-decode."""
        eng = self.engine
        disposition = (
            "shed_after_admit" if reason == "expired_partial"
            else "evicted_lost"
        )
        n = 0
        for i, r in enumerate(eng.slots):
            if r is not None and r.entry is entry:
                self._tok_ledger[disposition] += self._row_on_books(r)
                eng.release(i)
                n += 1
        self.stats["evictions"] += n
        self.stats["shed_deadline"] += 1
        waited = time.monotonic() - entry.enqueued_at
        if entry.future.trace is not None:
            entry.future.trace.event("evicted", rows=n, reason=reason)
        logger.warning(
            f"{self.name}: evicted {n} mid-decode row(s) of an expired "
            f"request after {waited:.2f}s ({reason})"
        )
        if not entry.future.done():
            entry.future.set_exception(
                DeadlineExceeded(
                    f"deadline exceeded after {waited:.2f}s ({reason})"
                )
            )

    def _fail_rows(self, rows, exc: BaseException) -> None:
        # token ledger: the rows died with their on-book tokens (the
        # arena reset already released them) — every one is evicted_lost.
        # Fold first: commits that landed before the crash must be
        # admitted before they can be lost.
        self._fold_admitted()
        for r in rows:
            if r.entry is not None:
                self._tok_ledger["evicted_lost"] += (
                    len(r.tokens)
                    + len(r.entry.row_prefill.get(r.row_idx, ()))
                )
        failed = {r.entry for r in rows if r.entry is not None}
        for e in failed:
            if not e.future.done():
                e.future.set_exception(exc)

    def _decision_baselines(self) -> Dict[str, Any]:
        """The counters a decision-log row is diffed against, read before
        the iteration; :meth:`_iterate` takes them only while the trace
        buffer is on."""
        eng = self.engine
        pfx = eng.cache.prefix.stats
        spill = eng.cache.spill.stats
        return {
            "admit": int(self.stats["prefill_admits"]),
            "shed": int(self.stats["shed_deadline"]),
            "evict": int(self.stats["evictions"]),
            "spec_p": int(eng.stats["spec_proposed"]),
            "spec_a": int(eng.stats["spec_accepted"]),
            "prefix_h": int(pfx["hits"]),
            "prefix_t": int(pfx["hit_tokens"]),
            "prefix_e": int(pfx["evictions"]),
            "chunks": int(eng.stats["prefill_chunks"]),
            "spill_s": int(spill["spills"]),
            "spill_r": int(spill["readmits"]),
            "spill_d": int(spill["discards"]),
            "mig_a": int(eng.stats["migrate_adopted"]),
            "blocks_free": eng.cache.allocator.free_count(),
            "tadmit": dict(self._tenant_admitted),
            "tpre": dict(self._tenant_preempted),
            # the token columns are per-iteration deltas of the same
            # dict the registry and /debug/state export
            "tok": dict(self._tok_ledger),
        }

    def _decision_row(self, b: Dict[str, Any], n_finished: int) -> Dict[str, Any]:
        """This iteration's row of the decision log, diffed against the
        baselines ``b`` it began with."""
        eng = self.engine
        pfx = eng.cache.prefix.stats
        spill = eng.cache.spill.stats
        tok, tok0 = self._tok_ledger, b["tok"]
        row = {
            "iter": self._iter_counter,
            "t": round(time.monotonic(), 6),
            # baseline-diffed (like evicted/shed), NOT the inner
            # return value: an exception escaping after some
            # admits succeeded must still land them in this row
            # or the replay contract breaks with no event lost
            "admitted": int(self.stats["prefill_admits"]) - b["admit"],
            "evicted": int(self.stats["evictions"]) - b["evict"],
            "shed": int(self.stats["shed_deadline"]) - b["shed"],
            # informational only (not a replayed counter): 0 when
            # the step raised before resolving finishes
            "finished": n_finished,
            "active": eng.active_rows(),
            "width_bucket": eng.table_width_bucket(),
            "blocks_free": eng.cache.allocator.free_count(),
            "blocks_delta":
                eng.cache.allocator.free_count() - b["blocks_free"],
            "spec_proposed": int(eng.stats["spec_proposed"]) - b["spec_p"],
            "spec_accepted": int(eng.stats["spec_accepted"]) - b["spec_a"],
            # prefix-reuse + chunked-prefill accounting: hits
            # join the exact-replay contract (replay reproduces
            # pfx_prefix_hits_total like the admit/evict trio)
            "prefix_hits": int(pfx["hits"]) - b["prefix_h"],
            "prefix_hit_tokens": int(pfx["hit_tokens"]) - b["prefix_t"],
            "prefix_evictions": int(pfx["evictions"]) - b["prefix_e"],
            "chunks": int(eng.stats["prefill_chunks"]) - b["chunks"],
            # spill-tier + migration deltas: every site moves
            # the store stats and registry counters together,
            # so the replay fold reproduces pfx_prefix_spills/
            # readmits/spill_discards and pfx_migrate_adopted
            # exactly (the PR 8/12 contract extended)
            "spills": int(spill["spills"]) - b["spill_s"],
            "readmits": int(spill["readmits"]) - b["spill_r"],
            "spill_discards": int(spill["discards"]) - b["spill_d"],
            "migrate_adopted": int(eng.stats["migrate_adopted"]) - b["mig_a"],
            # token-ledger columns (baseline-diffed like the
            # trio): folding an untruncated log reproduces the
            # pfx_token_ledger_total dispositions exactly
            "tok_admitted": tok["admitted"] - tok0["admitted"],
            "tok_delivered": tok["delivered"] - tok0["delivered"],
            "tok_evicted_lost": tok["evicted_lost"] - tok0["evicted_lost"],
            "tok_preempt_refunded":
                tok["preempt_refunded"] - tok0["preempt_refunded"],
            "tok_shed_after_admit":
                tok["shed_after_admit"] - tok0["shed_after_admit"],
        }
        # multi-tenant columns (same baseline-diff discipline):
        # per-tenant-label admitted/preempted row counts — the
        # replay fold reproduces pfx_tenant_admitted_total and
        # pfx_tenant_preemptions_total exactly from these
        tenants_row = {
            lab: n - b["tadmit"].get(lab, 0)
            for lab, n in self._tenant_admitted.items()
            if n - b["tadmit"].get(lab, 0)
        }
        preempted_row = {
            lab: n - b["tpre"].get(lab, 0)
            for lab, n in self._tenant_preempted.items()
            if n - b["tpre"].get(lab, 0)
        }
        row["preempted"] = sum(preempted_row.values())
        if tenants_row:
            row["tenants"] = tenants_row
        if preempted_row:
            row["preempted_tenants"] = preempted_row
        return row

    def _watch_stall(self, wall, dd: float, dp: float, rb: float, sf: float,
                     hs: float, n_finished: int) -> None:
        """Hand the iteration that just ended to the slow-iteration
        watcher (docs/observability.md "Slow iterations"), each against
        its own kind: ``decode``; ``admit:<tokens>``, one that dispatched
        prefills, by the sum of their buckets rounded up to a power of two
        (``+<tokens>`` where it also waited for the one before's);
        ``after_admit:<tokens>``, the one after it, whose readback waits
        for them where the scheduler dispatches ahead.  So a 2,048-token
        prefill is held against other prefills of 1,025 to 2,048 tokens
        and not against a decode step, and a mix of many prompt buckets
        still gives each kind enough observations to judge by."""
        eng = self.engine
        carried, self._stall_carry = self._stall_carry, 0
        if dp > 0.0:
            own = eng.prefill_bucket_sum and _pow2_at_least(eng.prefill_bucket_sum)
            eng.prefill_bucket_sum = 0
            if self.dispatch_ahead:
                self._stall_carry = own
            kind = f"admit:{own}+{carried}" if carried else f"admit:{own}"
        elif carried:
            kind = f"after_admit:{carried}"
        else:
            kind = "decode"
        ev = self._stall.observe(kind, wall.t0, wall.t1, (dd, dp, rb, sf, hs))
        if ev is not None:
            self._stall.publish(
                ev, iter=self._iter_counter, active=eng.active_rows(),
                admitted=self._n_seated, finished=n_finished,
                width_bucket=eng.table_width_bucket(),
                waiting=len(self._entries),
            )

    def _iterate(self) -> None:
        eng = self.engine
        # per-iteration decision accounting (the decision log's row):
        # pre-iteration counter baselines diffed at the end, so every
        # SCHEDULER-side admit/evict/shed — including helper-raised
        # ones — lands in exactly one row.  (A handler-thread
        # try_remove shed can land between iterations: shed rows are
        # scheduler-side only, and shed is deliberately NOT part of the
        # exact-replay trio.)  Taken only while the trace buffer is on:
        # nothing else reads them
        base = self._decision_baselines() if get_trace_buffer().enabled else None
        # goodput-ledger baselines: the iterate's wall duration is fully
        # attributed — engine per-phase deltas plus a host_sched
        # residual
        tdd0 = float(eng.stats["t_device_decode"])
        tdp0 = float(eng.stats["t_device_prefill"])
        trb0 = float(eng.stats["t_readback"])
        tsf0 = float(eng.stats["t_stream_flush"])
        n_finished = 0
        # the iterate's wall span; the engine's dispatch / readback /
        # flush spans nest inside it and its self time is host_sched
        wall = ledger_span(
            "pfx.sched.iterate", iter=self._iter_counter + 1,
            active=eng.active_rows(), width_bucket=eng.table_width_bucket(),
        )
        try:
            with wall:
                n_finished = self._iterate_inner()
        finally:
            self._fold_admitted()
            dur = wall.seconds
            dd = float(eng.stats["t_device_decode"]) - tdd0
            dp = float(eng.stats["t_device_prefill"]) - tdp0
            rb = float(eng.stats["t_readback"]) - trb0
            sf = float(eng.stats["t_stream_flush"]) - tsf0
            led = self._time_ledger
            led["device_decode"] += dd
            led["device_prefill"] += dp
            led["readback"] += rb
            led["stream_flush"] += sf
            hs = max(0.0, dur - (dd + dp + rb + sf))
            led["host_sched"] += hs
            self._sched_wall_s += dur
            # per-tenant occupancy integrals: every live row held its
            # decode slot and KV blocks for this whole iteration
            for r in eng.slots:
                if r is not None and r.entry is not None:
                    lab = self._tenant_labels.label(r.entry.tenant)
                    occ = self._tenant_occ.setdefault(
                        lab, {"slot_s": 0.0, "kv_block_s": 0.0}
                    )
                    occ["slot_s"] += dur
                    occ["kv_block_s"] += len(r.table) * dur
            self._iter_counter += 1
            self._watch_stall(wall, dd, dp, rb, sf, hs, n_finished)
            if base is not None:
                row = self._decision_row(base, n_finished)
                with self._lock:
                    self.decision_log.append(row)
            if get_trace_buffer().enabled or self._debug_requested:
                self._publish_debug()

    def _iterate_inner(self):
        eng = self.engine
        now = time.monotonic()
        n_finished = 0
        # since when the device has nothing queued, for the admission
        # path's host seconds: the iteration's start, or the end of the
        # flush that committed the step in flight (_flush_engine)
        self._t_device_free = now
        self._flushed = False  # a step with live rows was committed early

        # peer prefix adoptions (drain-migration receiver): folded in
        # BEFORE this iteration's admissions, so a migrated
        # prefix is hittable by the very next admit.  Each payload was
        # fully validated at submit time; adoption failures fail only
        # their own future — except an ArenaReset, which fails every
        # live row exactly like a prefill dispatch death
        with self._wake:
            tasks, self._admin_tasks = self._admin_tasks, []
        for meta, arrays, fut in tasks:
            try:
                fut.set_result(eng.adopt_prefixes(meta, arrays))
            except ArenaReset as exc:
                self.stats["gen_errors"] += 1
                self._fail_rows(exc.dead_rows, exc)
                if not fut.done():
                    fut.set_exception(exc)
                logger.warning(f"{self.name}: {exc}")
            except Exception as exc:  # noqa: BLE001 — fail this payload
                if not fut.done():    # alone, keep serving
                    fut.set_exception(exc)
                logger.warning(
                    f"{self.name}: prefix adoption failed: "
                    f"{type(exc).__name__}: {exc}"
                )

        admitted: List[tuple] = []
        expired_partial: List[_CBEntry] = []
        with self._wake:
            # shed expired WAITING entries before spending anything; an
            # expired PARTIALLY-admitted entry leaves the queue too (its
            # remaining rows must never start) and is evicted below
            keep: List[_CBEntry] = []
            for e in self._entries:
                if e.deadline is not None and now > e.deadline:
                    if e.next_row == 0:
                        self._shed_locked(e)
                    else:
                        expired_partial.append(e)
                else:
                    keep.append(e)
            self._entries = keep

        # evict expired ACTIVE rows BEFORE picking admissions (mid-decode
        # shed): their slots and blocks return to the pool for this same
        # iteration's admissions
        expired = set(expired_partial)
        for r in eng.slots:
            if r is not None and r.entry is not None:
                e = r.entry
                if e.deadline is not None and now > e.deadline:
                    expired.add(e)
        if expired:
            # row membership is about to change: commit the in-flight
            # dispatched step first (dispatch-ahead), so evicted rows'
            # final state is folded in before their blocks return
            n_finished += self._flush_engine()
        partial = set(expired_partial)
        for e in expired:
            if e.future.done():
                continue  # the in-flight step completed it first
            # reason doubles as the ledger disposition: a PARTIALLY
            # admitted entry's on-book tokens are shed_after_admit, a
            # fully-admitted one's are evicted_lost
            self._evict_entry(
                e, "expired_partial" if e in partial else "mid-decode"
            )

        # pick against the view as it stands, the step in flight not
        # committed: rows that step finishes hold their slots and blocks
        # until its commit, so a free slot and enough free blocks now are
        # still free after it, and a unit that fits is seated with the
        # step still running (its prefill queues on the device behind
        # it).  The commit buys something only when this view cannot seat
        # the head: then flush and pick again, in this same iteration
        reserved_blocks, blocked = self._pick_units(admitted, 0)
        if blocked is not None and eng.has_inflight:
            n_finished += self._flush_engine()
            reserved_blocks, blocked = self._pick_units(
                admitted, reserved_blocks
            )

        # priority preemption (outside the lock: engine work).  A
        # blocked arrival with strictly higher priority than the
        # lowest-priority active row may seat itself by preempting that
        # row; preempt_storm:K forces one preemption per fire with no
        # arrival needed (the deterministic drill hook).  Victims must
        # be past the protected minimum-progress floor
        # (preempt_min_tokens committed since their last admission), so
        # a priority storm cannot livelock the batch — and a preempted
        # row is requeued as a re-prefill continuation, never killed.
        storm = maybe_fire("preempt_storm", self._iter_counter + 1)
        want = None
        if blocked is not None and not blocked[0].future.done():
            want = blocked
        if want is not None or storm:
            flushed = False
            fits = False
            for _ in range(eng.capacity + 1):
                if want is not None:
                    head, row_idx, p, mx, resumed, need = want
                    free_s = eng.free_slots() - len(admitted)
                    free_b = (eng.cache.allocator.free_count()
                              - reserved_blocks)
                    if need > free_b:
                        free_b += eng.cache.prefix.reclaimable_blocks()
                    if free_s >= 1 and need <= free_b:
                        fits = True
                        break
                victim = self._pick_victim(
                    below_priority=(
                        want[0].priority if want is not None else None
                    ),
                    # before the flush a row's committed count lags the
                    # in-flight step by one token — give the pre-flush
                    # probe that slack, so the flush (which costs the
                    # dispatch-ahead overlap) only runs when a victim
                    # is at least plausibly eligible
                    progress_slack=0 if flushed else 1,
                )
                if victim is None:
                    break
                if not flushed:
                    # row membership is about to change: commit the
                    # in-flight dispatched step first (the engine's
                    # dispatch-ahead flush contract)
                    n_finished += self._flush_engine()
                    # the flush may have finished the victim — re-pick
                    # against the committed state, strictly
                    flushed = True
                    continue
                self._preempt_slot(victim)
                if want is None:
                    break  # storm fire: exactly one forced preemption
            if want is not None and fits:
                # seat the preemptor NOW: it earned the freed capacity
                # (no second fair-pick — priority cuts across fairness
                # by design, and its tenant's deficit is still charged)
                head, row_idx, p, mx, resumed, need = want
                with self._wake:
                    if not head.future.done():
                        reserved_blocks += need
                        self._fair.charge(head.tenant)
                        t_pick = time.monotonic()
                        head.future.times.setdefault("picked", t_pick)
                        if (head.future.trace is not None
                                and head.next_row == 0 and not resumed):
                            head.future.trace.span(
                                "queue_wait", t0=head.enqueued_at,
                                t1=t_pick,
                            )
                        admitted.append((head, row_idx, p, mx, resumed))
                        if resumed:
                            head.requeue_rows.pop(0)
                        else:
                            head.next_row += 1
                        if (head.next_row >= len(head.prompts)
                                and not head.requeue_rows
                                and head in self._entries):
                            self._entries.remove(head)

        # prefill-on-admit (outside the lock: device work).  With a step
        # in flight the prefill is dispatched BEHIND it where the engine
        # allows (seats_behind_step: the monolithic prefill, no
        # speculation) and the unit is a fresh prompt; an adoption, a
        # resumed row and every other admission commit the step first
        t_admitted: Optional[float] = None  # the last one the device waited for
        for entry, row_idx, prompt, mx, resumed in admitted:
            adopting = entry.handoff is not None and not resumed
            behind = (eng.has_inflight and not adopting and not resumed
                      and eng.seats_behind_step(prompt))
            if not behind:
                n_finished += self._flush_engine()
            if entry.future.done():
                continue  # an earlier row of this entry already failed
            self._req_counter += 1
            path = ("behind_step" if behind
                    else "after_flush" if self._flushed else "idle")
            try:
                maybe_fire("gen_crash", self._req_counter)
                if adopting:
                    # disaggregated: adopt the prefill replica's exported
                    # blocks instead of running paged_prefill.  Counted in
                    # prefill_admits too — it IS a row admission, and the
                    # decision-log replay contract stays exact.  (A
                    # RESUMED handoff row re-prefills below instead: its
                    # arrays were consumed at the original adoption.)
                    meta, arrays = entry.handoff
                    eng.adopt(meta, arrays, entry=entry, row_idx=row_idx)
                else:
                    eng.admit(prompt, mx, entry=entry, row_idx=row_idx)
                if not behind:
                    t_admitted = time.monotonic()
                self.stats["admits_" + path] += 1
                if resumed:
                    # token ledger: a resume re-admits the prefix its
                    # preemption refunded — the tokens are back on the
                    # books, and finished_tokens will deliver them
                    self._tok_ledger["admitted"] += len(
                        entry.row_prefill.get(row_idx, ())
                    )
                self.stats["prefill_admits"] += 1
                lab = self._tenant_labels.label(entry.tenant)
                self._tenant_admitted[lab] = (
                    self._tenant_admitted.get(lab, 0) + 1
                )
                get_registry().counter(
                    "pfx_tenant_admitted_total", tenant=lab
                ).inc()
            except ArenaReset as exc:
                # the donating prefill dispatch failed: every live row
                # died with the arena — fail them all, keep serving on
                # the fresh pools
                self.stats["gen_errors"] += 1
                self._fail_rows(exc.dead_rows, exc)
                if not entry.future.done():
                    entry.future.set_exception(exc)
                logger.warning(f"{self.name}: {exc}")
            except (BlockPoolExhausted, RuntimeError, ValueError) as exc:
                # host-side failure BEFORE any dispatch (capacity raced
                # between the locked check and here, or an injected
                # crash): arena intact, fail only this entry
                self.stats["gen_errors"] += 1
                # a sibling's prefill may be queued behind the step this
                # admission was to queue behind too: commit that step
                # before the rows leave (the flush contract)
                n_finished += self._flush_engine()
                for i, r in enumerate(eng.slots):
                    if r is not None and r.entry is entry:
                        # sibling rows admitted earlier die with their
                        # on-book tokens: evicted_lost
                        self._tok_ledger["evicted_lost"] += (
                            self._row_on_books(r)
                        )
                        eng.release(i)
                if not entry.future.done():
                    entry.future.set_exception(exc)
                logger.warning(
                    f"{self.name}: admission failed: "
                    f"{type(exc).__name__}: {exc}"
                )

        self._n_seated = len(admitted)
        if t_admitted is not None:
            eng.gap_books.admit_host(t_admitted - self._t_device_free)
        if not self._has_live_rows():
            return n_finished
        return n_finished + self._step_batch()

    def _pick_units(self, admitted: List[tuple],
                    reserved_blocks: int) -> Tuple[int, Optional[tuple]]:
        """Pick the units to seat against the engine's view as it stands
        (free slots and blocks less what ``admitted`` already reserved)
        and append them to ``admitted``; nothing is allocated here.
        Returns the blocks reserved so far and the head of the line if it
        did not fit."""
        eng = self.engine
        blocked: Optional[tuple] = None
        with self._wake:
            # weighted-fair admission: a deficit round-robin across
            # tenant queues replaces the old global-FCFS head pull.
            # Each pick serves the chosen tenant's OLDEST admissible
            # unit (a preempted row waiting to resume before any fresh
            # row) and charges one deficit — FCFS within a tenant, one
            # tenant degenerates to exactly the old FCFS order.
            # Nothing is allocated until _iterate_inner's prefill loop, so
            # the pull accounts for its OWN picks — a burst larger than free
            # capacity stays queued instead of hard-failing at admit()
            free_slots = eng.free_slots() - len(admitted)
            free_blocks = eng.cache.allocator.free_count() - reserved_blocks
            # cached-prefix blocks only the index references evict on
            # demand inside admit — add them to the budget LAZILY (the
            # reclaimable scan is O(cached nodes); an iteration whose
            # free pool already covers its admissions never pays it)
            reclaim_counted = False
            # already-failed entries (e.g. an earlier row died in an
            # ArenaReset) must neither reserve capacity nor spend a
            # tenant's turn
            self._entries = [e for e in self._entries if not e.future.done()]
            while self._entries:
                backlog: Dict[str, int] = {}
                for e in self._entries:
                    backlog[e.tenant] = backlog.get(e.tenant, 0) + 1
                pick = self._fair.pick(backlog)
                head = next(e for e in self._entries if e.tenant == pick)
                row_idx, p, mx, resumed = self._next_unit(head)
                need = blocks_for(
                    eng.row_capacity_tokens(len(p), mx), eng.block
                )
                if need > free_blocks and not reclaim_counted:
                    free_blocks += eng.cache.prefix.reclaimable_blocks()
                    reclaim_counted = True
                if free_slots < 1 or need > free_blocks:
                    # head-of-line blocked (same backpressure as the old
                    # FCFS pull) — remembered as the priority-preemption
                    # candidate below
                    blocked = (head, row_idx, p, mx, resumed, need)
                    break
                free_slots -= 1
                free_blocks -= need
                reserved_blocks += need
                self._fair.charge(head.tenant)
                t_pick = time.monotonic()
                head.future.times.setdefault("picked", t_pick)
                if (head.future.trace is not None and head.next_row == 0
                        and not resumed):
                    head.future.trace.span(
                        "queue_wait", t0=head.enqueued_at, t1=t_pick,
                    )
                admitted.append((head, row_idx, p, mx, resumed))
                if resumed:
                    head.requeue_rows.pop(0)
                else:
                    head.next_row += 1
                if (head.next_row >= len(head.prompts)
                        and not head.requeue_rows):
                    self._entries.remove(head)
        return reserved_blocks, blocked

    def _step_batch(self) -> int:
        """One iteration-level decode step: dispatch (and, synchronous
        or commit-first, fetch) via engine.step(), then resolve the rows
        it finished.  Under dispatch-ahead the finished rows are the
        PREVIOUS step's — commit order, which is exactly the order the
        decision log accounts them in."""
        if not self._has_live_rows():
            return 0
        self._step_counter += 1
        maybe_fire("cb_step_hang", self._step_counter)
        try:
            finished = self.engine.step()
        except ArenaReset as exc:
            self.stats["gen_errors"] += 1
            self._fail_rows(exc.dead_rows, exc)
            logger.warning(f"{self.name}: {exc}")
            return 0
        self._fold_admitted()  # before _finish_rows can deliver them
        self.stats["batches"] += 1
        return self._finish_rows(finished)

    def _flush_engine(self) -> int:
        """Commit the engine's in-flight dispatched step (no-op when
        synchronous or idle) and resolve the rows it finished.  Must
        run before anything that mutates row membership — eviction and
        admission — per the engine's dispatch-ahead flush contract."""
        if not self.engine.has_inflight:
            return 0
        if self.engine.inflight_rows:
            self._flushed = True
        try:
            finished = self.engine.flush()
        except ArenaReset as exc:
            self.stats["gen_errors"] += 1
            self._fail_rows(exc.dead_rows, exc)
            logger.warning(f"{self.name}: {exc}")
            return 0
        # the step in flight was committed early: the next one is
        # dispatched after this iteration's host work, not under it (the
        # books read "flush" unless an admission follows), and from here
        # the device has nothing queued (the admission path's host clock)
        self.engine.gap_books.note("flush")
        self._t_device_free = time.monotonic()
        self._fold_admitted()  # before _finish_rows can deliver them
        return self._finish_rows(finished)

    def _next_unit(self, head: "_CBEntry") -> tuple:
        """The entry's next admissible unit.  A preempted row waiting to
        resume goes before any fresh row: its prompt is the original
        prompt plus every committed token (the last sampled token needs
        no KV yet, so the whole committed prefix re-prefills — mostly as
        a radix-index prefix hit), and its budget is what remains.
        Returns ``(row_idx, prompt, max_new, resumed)``."""
        if head.requeue_rows:
            row_idx = head.requeue_rows[0]
            committed = head.row_prefill.get(row_idx, [])
            return (
                row_idx,
                head.prompts[row_idx] + committed,
                head.max_new - len(committed),
                True,
            )
        return head.next_row, head.prompts[head.next_row], head.max_new, False

    def _pick_victim(self, below_priority: Optional[int],
                     progress_slack: int = 0) -> Optional[int]:
        """The slot of the lowest-priority active row eligible for
        preemption, or None.  Eligible means: decode-active with prefill
        done, its entry still live, past the protected minimum-progress
        floor (``preempt_min_tokens`` committed since its last
        admission — the anti-livelock guard: a resumed victim must
        re-earn eligibility before it can be preempted again), and —
        unless ``below_priority`` is None (the preempt_storm drill) —
        strictly below the preemptor's priority.  Deterministic
        tie-break: lowest slot index."""
        eng = self.engine
        best: Optional[int] = None
        if not eng.mcfg.classic_block:
            return None  # engine.preempt_row refuses this block by name
        for i, r in enumerate(eng.slots):
            if r is None or r.entry is None or r.entry.future.done():
                continue
            if not r.prefill_done or not bool(eng.active[i]):
                continue
            if len(r.tokens) + progress_slack < self.preempt_min_tokens:
                continue
            if below_priority is not None and r.entry.priority >= below_priority:
                continue
            if best is None or (
                (r.entry.priority, i)
                < (eng.slots[best].entry.priority, best)
            ):
                best = i
        return best

    def _preempt_slot(self, slot: int) -> None:
        """Evict one active row mid-decode and requeue it as a
        re-prefill continuation: the engine publishes its KV-valid
        prefix to the radix index and frees the slot, the committed
        tokens are folded into the entry's resume state, and the entry
        re-enters the queue at the FRONT (it already waited its turn).
        The caller must have flushed the engine first."""
        eng = self.engine
        row = eng.slots[slot]
        entry = row.entry
        committed = eng.preempt_row(slot)
        prev = entry.row_prefill.get(row.row_idx)
        entry.row_prefill[row.row_idx] = (
            prev + committed if prev else committed
        )
        entry.requeue_rows.append(row.row_idx)
        # token ledger: the row's WHOLE on-book amount (any earlier
        # resume prefix + this stint's commits) leaves the books as a
        # refund; the resume re-admits it, so books stay closed across
        # any preempt/resume chain
        self._tok_ledger["preempt_refunded"] += len(
            entry.row_prefill[row.row_idx]
        )
        self.stats["preemptions"] += 1
        lab = self._tenant_labels.label(entry.tenant)
        self._tenant_preempted[lab] = self._tenant_preempted.get(lab, 0) + 1
        get_registry().counter(
            "pfx_tenant_preemptions_total", tenant=lab
        ).inc()
        if row.trace is not None:
            row.trace.event(
                "preempted",
                slot=slot,
                committed=len(committed),
                total_committed=len(entry.row_prefill[row.row_idx]),
            )
        logger.info(
            f"{self.name}: preempted slot {slot} (tenant {entry.tenant}, "
            f"priority {entry.priority}) after {len(committed)} committed "
            "token(s); requeued as a re-prefill continuation"
        )
        with self._wake:
            if entry not in self._entries:
                self._entries.insert(0, entry)
            self._wake.notify_all()

    def _finish_rows(self, finished: List[int]) -> int:
        eng = self.engine
        reg = get_registry()
        for slot in finished:
            row = eng.slots[slot]
            entry = row.entry
            eng.release(slot)
            if entry is None:
                continue
            entry.results[row.row_idx] = entry.finished_tokens(
                row.row_idx, row.tokens
            )
            # token ledger: the full output (resume prefix + this
            # stint's commits) reached the results array — delivered
            self._tok_ledger["delivered"] += len(
                entry.results[row.row_idx]
            )
            entry.done_rows += 1
            if entry.done_rows == len(entry.prompts):
                entry.future.set_result(list(entry.results))
                self.stats["completed"] += 1
                reg.counter("pfx_serving_requests_total").inc()
                reg.counter("pfx_serving_tokens_out_total").inc(
                    sum(len(t) for t in entry.results)
                )
        return len(finished)
