"""Generation serving: persistent model + bucketed compiled decode.

The reference deploys generation through its static-graph predictor
(core/engine/inference_engine.py:104 `InferenceEngine.predict` :252, one
process per mp rank over NCCL).  TPU-native serving is simpler: ONE process
per host, params sharded over the serving mesh by the same logical rules as
training, and a jitted decode per (prompt-bucket, max_dec_len) pair — the
bucket padding (`pad_prompts`) keeps the number of compiled artifacts small
and stable under real traffic.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from paddlefleetx_tpu.models.gpt.generation import (
    GenerationConfig,
    bucket_len,
    generate,
    init_cache,
    init_serving_params,
    pad_prompts,
    serving_params,
)
from paddlefleetx_tpu.ops.decode_attention import kv_cache_dtype
from paddlefleetx_tpu.ops.speculative import spec_config_from
from paddlefleetx_tpu.parallel.sharding import place_on_mesh
from paddlefleetx_tpu.utils.checkpoint import load_pretrained_params
from paddlefleetx_tpu.utils.log import logger
from paddlefleetx_tpu.utils.resilience import maybe_fire
from paddlefleetx_tpu.utils.telemetry import StatsView, get_registry


def plan_decode(padded_len: int, max_toks: int, *, context: int):
    """THE decode-length clamp for an explicit client ``max_tokens``:
    (trim, run) where ``trim`` is the per-request output cap (context
    room respected, floored at 1) and ``run`` is the 32-bucketed decode
    length that keys the compile.  Single-sourced on purpose —
    ``generate_ids`` clamps with it and the serve-layer coalesce key
    (tools/serve.py ``plan_request``) predicts with it, so "equal keys
    pad identically whether served together or apart" can never drift.
    Raises ValueError when the padded prompt leaves no decode room."""
    limit = int(context) - int(padded_len)
    if limit < 1:
        raise ValueError(
            f"prompt bucket {padded_len} leaves no decode room in "
            f"context {context}"
        )
    trim = max(1, min(int(max_toks), limit))
    run = min(-(-trim // 32) * 32, limit)
    return trim, run


class GenerationServer:
    """Holds params on the mesh and serves tokenized generation requests.

    ``generate_ids`` is the transport-independent core; ``generate_text``
    adds tokenizer round-tripping when one is configured.
    """

    def __init__(self, cfg, mesh, module, params=None, tokenizer=None):
        from paddlefleetx_tpu.models.gpt.model import ShardingCtx
        from paddlefleetx_tpu.parallel.seed import get_seed_tracker
        from paddlefleetx_tpu.parallel.sharding import (
            make_rules,
            tree_logical_to_sharding,
        )

        self.cfg = cfg
        self.mesh = mesh
        self.module = module
        self.tokenizer = tokenizer

        gen_cfg = cfg.get("Generation", {})
        self.bucket = int(gen_cfg.get("pad_to_multiple", 64))
        self.gen = GenerationConfig(
            max_dec_len=int(gen_cfg.get("max_dec_len", 64)),
            min_dec_len=int(gen_cfg.get("min_dec_len", 1)),
            decode_strategy=gen_cfg.get("decode_strategy", "sampling"),
            temperature=float(gen_cfg.get("temperature", 1.0)),
            top_k=int(gen_cfg.get("top_k", 0)),
            top_p=float(gen_cfg.get("top_p", 1.0)),
            repetition_penalty=float(gen_cfg.get("repetition_penalty", 1.0)),
            eos_token_id=int(gen_cfg.get("eos_token_id", 50256)),
            pad_token_id=int(gen_cfg.get("pad_token_id", 0)),
            forced_bos_token_id=int(gen_cfg.get("forced_bos_token_id", -1)),
            forced_eos_token_id=int(gen_cfg.get("forced_eos_token_id", -1)),
        )
        # Generation.speculative: {draft_k, drafter, ngram, kv_dtype} —
        # draft_k > 0 routes the contiguous decode through the
        # speculative while-loop (greedy stays token-identical); kv_dtype
        # int8 quantizes the donated cache pool (--kv-dtype overrides the key)
        spec_section = dict(gen_cfg.get("speculative", {}) or {})
        self.spec = spec_config_from(spec_section)
        self.kv_dtype = kv_cache_dtype(
            str(spec_section.get("kv_dtype", "") or "")
        )

        rules = make_rules(mesh=mesh)
        self.ctx = ShardingCtx(mesh, rules) if mesh.size > 1 else None
        # with no tree given the server loads (Engine.save_load.ckpt_dir) or
        # makes its own, and so holds the only reference to it: a tree a
        # caller passes stays alive in the caller, whole, through the cast
        if params is None:
            params = load_pretrained_params(cfg)
        shardings = None
        if self.ctx is not None:
            shardings = tree_logical_to_sharding(module.logical_axes(), mesh, rules)
        if params is None and hasattr(module.config, "classic_block"):
            # random weights are made one leaf at a time, already in the
            # dtype they are held in: the float32 tree never exists
            params = init_serving_params(
                module.config, get_seed_tracker().params_key(), shardings)
            shardings = None
        elif params is None:
            params = module.init_params(get_seed_tracker().params_key())
        if shardings is not None:
            params = jax.device_put(params, shardings)
        # the tree is HELD in the dtype the step computes in (float32 on
        # disk, cfg.dtype here, LayerNorm leaves float32): a decode step
        # is its own dispatch, and a float32 tree would be converted whole
        # inside every one (docs/serving.md "What the server holds").
        # After the placement, so each device casts its own shard; moved
        # out of this frame, so each float32 leaf is freed as its cast
        # exists and no moment holds two whole trees.
        owned = [params]
        del params
        self.params = serving_params(owned.pop(), module.config)
        held: Dict[str, int] = {}
        for leaf in jax.tree.leaves(self.params):
            held[str(leaf.dtype)] = held.get(str(leaf.dtype), 0) + leaf.nbytes
        for dtype_name, nbytes in held.items():
            get_registry().gauge("pfx_serving_params_bytes", dtype=dtype_name).set(nbytes)
        self._key = jax.random.key(int(cfg.get("Global", {}).get("seed", 0)))
        # one jitted decode per (bucket_b, bucket_len, GenerationConfig):
        # mixed-traffic serving hits a small, log-bounded set of compiled
        # artifacts (pad_prompts length buckets x power-of-two batch
        # buckets) and NEVER retraces a key it has seen — stats["traces"]
        # counts trace-time entries so a retrace regression is testable
        self._compiled: Dict = {}
        # live cache pairs recycled between same-bucket requests via
        # donation (see generate_ids).  LRU-BOUNDED: unlike the compiled-fn
        # memo (host-side artifacts), each pooled entry pins a full
        # [layers,b,heads,max_len,dim] k/v pair in device memory, and the
        # key space multiplies across batch x prompt x dec-len buckets —
        # unbounded mixed traffic on a real model would exhaust HBM.  An
        # evicted bucket just re-allocates a zeros pair on its next hit.
        from collections import OrderedDict

        self._cache_pool: "OrderedDict" = OrderedDict()
        self._cache_pool_size = int(gen_cfg.get("cache_pool_size", 4))
        # last_latency_s: wall-clock of the most recent generate_ids call —
        # /healthz surfaces it so operators see a slow/regressed decode
        # without scraping logs (tools/serve.py)
        # gen_errors / last_error: structured generation-failure stats —
        # /healthz spreads server.stats, so an operator sees a failing
        # decode (and its class) without scraping logs
        # StatsView: same dict interface as before, but the numeric keys
        # are exported onto the process-wide telemetry registry so
        # /metrics and /healthz render one locked snapshot (non-exported
        # keys — last_error, warmup_s — stay instance-local)
        self.stats = StatsView(
            {
                "requests": "pfx_serving_requests_total",
                "tokens_out": "pfx_serving_tokens_out_total",
                "time_s": "pfx_serving_gen_seconds_total",
                "traces": "pfx_serving_traces_total",
                "gen_errors": "pfx_serving_gen_errors_total",
                "last_latency_s": "pfx_serving_last_latency_seconds",
                "spec_proposed": "pfx_spec_proposed_total",
                "spec_accepted": "pfx_spec_accepted_total",
            },
            init={"time_s": 0.0, "last_latency_s": 0.0, "last_error": ""},
        )

    def _decode_fn(self, gen: GenerationConfig, batch: int, bucket_len: int):
        key = (gen, batch, bucket_len)
        fn = self._compiled.get(key)
        if fn is None:
            beam = gen.decode_strategy == "beam_search"
            spec = None if beam else self.spec

            def traced(p, x, lens, k, cache):
                # trace-time side effect: runs once per compile, never at
                # execution — the retrace-count contract's probe
                self.stats["traces"] += 1
                # (tokens, final cache[, (proposed, accepted)]) on the
                # sampling/greedy path; bare tokens for beam (no
                # donation there)
                return generate(
                    p, x, self.module.config, gen, key=k, ctx=self.ctx,
                    prompt_lens=lens, cache=cache, return_cache=not beam,
                    spec=spec, return_spec_stats=spec is not None,
                )

            # the KV cache is DONATED and RETURNED: donation aliases the
            # input pair to the returned final cache, so the per-step
            # dynamic_update_slice writes the [layers,b,heads,max_len,dim]
            # buffers in place; generate_ids feeds the returned cache of
            # one request straight back into the next same-bucket request
            # (stale tail slots are never visited by the blocked kernel)
            fn = jax.jit(traced, donate_argnums=(4,))
            self._compiled[key] = fn
        return fn

    # ------------------------------------------------------------------
    def generate_ids(
        self, prompts: Sequence[Sequence[int]], max_dec_len: Optional[int] = None
    ) -> List[List[int]]:
        """Generate continuations for a batch of token-id prompts."""
        import dataclasses

        if not prompts or any(len(p) == 0 for p in prompts):
            raise ValueError("prompts must be a non-empty list of non-empty id lists")
        if not getattr(self.module.config, "classic_block", True):
            raise ValueError(
                "the coalesce scheduler (generate_ids, the contiguous cache) knows the "
                "GPT-2 block only; serve this block with --scheduler continuous")
        from paddlefleetx_tpu.parallel.mesh import data_parallel_world

        gen = self.gen
        # the batch dim is sharded over (data, fsdp): pad the request batch
        # to a dp-world multiple (replicas of the last prompt) so any mesh
        # serves any request size; batched traffic rides the data axis
        n_req = len(prompts)
        dpw = data_parallel_world(self.mesh)
        # bucket the batch dim like the decode length: pad to the next power
        # of two (then up to a dp-world multiple) so varied client batch
        # sizes reuse a small log-bounded set of compiled artifacts instead
        # of keying a fresh multi-second XLA compile per distinct size
        target = 1
        while target < n_req:
            target *= 2
        target = -(-target // dpw) * dpw
        batch = list(prompts)
        while len(batch) < target:
            batch.append(batch[-1])
        prompt, prompt_lens = pad_prompts(batch, gen.pad_token_id, multiple=self.bucket)

        # clamp + bucket the decode length: an uncapped client value would
        # key an unbounded number of jit compiles (and a huge one would try
        # to allocate a decode buffer that long); the cap is whatever room
        # the model context leaves after the padded prompt bucket
        limit = int(self.module.config.max_position_embeddings) - prompt.shape[1]
        if limit < 1:
            raise ValueError(
                f"prompt bucket {prompt.shape[1]} leaves no decode room in "
                f"context {self.module.config.max_position_embeddings}"
            )
        if max_dec_len is None:
            # configured default: honor it exactly (one compile), just clamp
            trim = min(gen.max_dec_len, limit)
            run_len = trim
        else:
            # shared clamp: the serve-layer coalesce key predicts this
            trim, run_len = plan_decode(
                int(prompt.shape[1]), max_dec_len,
                context=int(self.module.config.max_position_embeddings),
            )
        if run_len != gen.max_dec_len:
            gen = dataclasses.replace(gen, max_dec_len=run_len)
        self._key, k = jax.random.split(self._key)
        t0 = time.time()
        beam = gen.decode_strategy == "beam_search"
        bucket_key = (gen, int(prompt.shape[0]), int(prompt.shape[1]))
        req_idx = int(self.stats["requests"]) + 1
        with self.mesh:
            # donated cache per request: first hit of a bucket allocates a
            # zeros pair, every later request re-donates the FINAL cache
            # the previous same-bucket request returned (the jit aliases
            # input to output, so steady-state serving does zero cache
            # copies and zero cache allocations; stale tail slots are
            # never visited by the blocked decode kernel).  Beam search
            # reorders the cache by parent each step and allocates
            # internally instead.
            cache = None
            if not beam:
                cache = self._cache_pool.pop(bucket_key, None)
                if cache is None:
                    # speculation needs draft_k slack slots for the
                    # verify chunk's rejected tail; kv_dtype int8
                    # allocates the quantized pair + scale planes
                    slack = self.spec.draft_k if self.spec else 0
                    # on the mesh like the decode fn's returned cache, so
                    # the first and every later same-bucket request share
                    # ONE compile (parallel/sharding.place_on_mesh)
                    cache = place_on_mesh(init_cache(
                        self.module.config, prompt.shape[0],
                        prompt.shape[1] + gen.max_dec_len + slack,
                        kv_dtype=self.kv_dtype,
                    ), self.mesh)
            try:
                # serving fault sites (tests/test_serve_drills.py): both
                # fire after the cache pop so an injected failure lands on
                # the same path as a real mid-decode one
                maybe_fire("gen_crash", req_idx)
                maybe_fire("gen_hang", req_idx)
                out = self._decode_fn(gen, prompt.shape[0], prompt.shape[1])(
                    self.params,
                    jax.numpy.asarray(prompt),
                    jax.numpy.asarray(prompt_lens),
                    k,
                    cache,
                )
            except BaseException as exc:
                # the popped pair was already fed to a donating jit call
                # (or is about to be abandoned): it may be
                # donation-invalidated, so DROP it — never return a
                # possibly-deleted buffer to the pool, and never leave
                # the bucket pointing at one.  The next same-bucket
                # request re-allocates a fresh zeros pair.
                self.stats["gen_errors"] += 1
                self.stats["last_error"] = f"{type(exc).__name__}: {exc}"
                raise
            spec_stats = None
            if not beam:
                if self.spec is not None:
                    out, final_cache, spec_stats = out
                else:
                    out, final_cache = out
                self._cache_pool[bucket_key] = final_cache
                self._cache_pool.move_to_end(bucket_key)
                while len(self._cache_pool) > self._cache_pool_size:
                    self._cache_pool.popitem(last=False)  # evict LRU pair
        out = np.asarray(out)[:n_req]
        dt = time.time() - t0
        outs: List[List[int]] = []
        for row in out:
            ids = row.tolist()[:trim]
            if gen.eos_token_id in ids:
                ids = ids[: ids.index(gen.eos_token_id)]
            outs.append(ids)
        self.stats["requests"] += 1
        self.stats["tokens_out"] += sum(len(o) for o in outs)
        self.stats["time_s"] += dt
        self.stats["last_latency_s"] = round(dt, 4)
        if spec_stats is not None:
            self.stats["spec_proposed"] += int(spec_stats[0])
            self.stats["spec_accepted"] += int(spec_stats[1])
            prop = float(self.stats["spec_proposed"])
            get_registry().gauge("pfx_spec_accept_rate").set(
                float(self.stats["spec_accepted"]) / prop if prop else 0.0
            )
        return outs

    def generate_text(self, prompts: Sequence[str], max_dec_len: Optional[int] = None):
        if self.tokenizer is None:
            raise ValueError("no tokenizer configured (Generation.tokenizer_dir)")
        ids = [self.tokenizer.encode(p) for p in prompts]
        outs = self.generate_ids(ids, max_dec_len=max_dec_len)
        return [self.tokenizer.decode(o) for o in outs]

    def warmup(
        self,
        prompt_lens: "Sequence[int] | int" = (8,),
        batch_sizes: Sequence[int] = (1,),
    ) -> Dict[str, float]:
        """Compile the decode for a list of prompt-length buckets
        (`--warmup-buckets` in tools/serve.py), optionally crossed with
        batch-size buckets (`--warmup-batches` — the coalescing scheduler
        makes power-of-two batch buckets a hot compile key too); returns
        and records per-bucket compile seconds in ``stats["warmup_s"]``.

        Fails LOUDLY: every bucket is validated up front (positive,
        leaves decode room in the context) and a failing bucket raises
        naming what did and did not warm — a silently half-warmed server
        would pay a surprise multi-second compile on its first live
        request.
        """
        if isinstance(prompt_lens, int):  # old warmup(prompt_len=8) shape
            prompt_lens = (prompt_lens,)
        lens = [int(n) for n in prompt_lens]
        batches = [int(b) for b in batch_sizes]
        if not lens or not batches:
            raise ValueError("warmup needs >= 1 prompt-length and batch bucket")
        ctx = int(self.module.config.max_position_embeddings)
        for n in lens:
            padded = bucket_len(n, self.bucket)
            if n < 1 or padded >= ctx:
                raise ValueError(
                    f"warmup bucket {n} invalid: padded prompt {padded} "
                    f"leaves no decode room in context {ctx}"
                )
        for b in batches:
            if b < 1:
                raise ValueError(f"warmup batch size {b} must be >= 1")
        per: Dict[str, float] = {}
        for n in lens:
            for b in batches:
                key = f"{n}" if b == 1 else f"{n}x{b}"
                t0 = time.time()
                try:
                    # int max_dec_len: land on the 32-bucketed compile key
                    # live traffic hits (a client always sends/clamps to
                    # an explicit max_tokens in tools/serve.py)
                    self.generate_ids(
                        [[1] * n] * b, max_dec_len=self.gen.max_dec_len
                    )
                except Exception as exc:
                    raise RuntimeError(
                        f"warmup failed at bucket {key} (warmed so far: "
                        f"{sorted(per) or 'none'}): "
                        f"{type(exc).__name__}: {exc}"
                    ) from exc
                per[key] = round(time.time() - t0, 2)
                logger.info(
                    f"serving warmup: prompt bucket {n} batch {b} "
                    f"(pad multiple {self.bucket}) compiled in {per[key]:.1f}s"
                )
        self.stats["warmup_s"] = dict(per)
        get_registry().counter("pfx_serving_warmup_seconds_total").inc(
            sum(per.values())
        )
        return per
