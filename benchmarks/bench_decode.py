"""Decode/serving throughput bench: KV-cache generation on GPT-345M.

The training side has deep throughput evidence (headline, sweep, 1.3B,
ViT); this measures the INFERENCE side of the stack at realistic shapes:

  decode cases   batch {8, 32} x prompt 128 x dec_len 256, greedy AND
                 top-p sampling (the `ops/sampling.py` top-k-prefilter
                 nucleus sampler that replaces the reference's CUDA
                 topp_sampling kernel, ppfleetx/ops/topp_sampling.cu:377);
                 `*_legacy` variants re-trace with PFX_DECODE_ATTN=dense +
                 PFX_DECODE_SCAN=1 (pre-overhaul attend-over-the-whole-
                 cache scan) so every window emits an A/B row pair
  serving case   `core.serving.GenerationServer` bucketed-batch traffic
                 (mixed request sizes riding the power-of-two batch
                 buckets), i.e. the deploy path the reference serves via
                 its static-graph predictor (single_model.py:1190-1320)

Comparison point: the reference ships the fused sampler and a measured
generation path but publishes NO machine-readable decode tokens/s, so
every row reports absolute new-tokens/s/chip with vs_baseline null —
evidence artifacts, not ratios.

Contract: same parent/child split as bench.py — the parent never imports
jax, stays SIGTERM-responsive, and emits an honest value:0.0 row for any
case the child did not finish.  Rows append to
benchmarks/results_decode.jsonl.

  python benchmarks/bench_decode.py [--cases b8_greedy,b8_topp,...]
      [--prompt 128] [--dec 256] [--iters 3]
"""

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# PFX_DECODE_RESULTS: contract tests / smoke runs point this at a tmp
# file so CPU rows don't accumulate in the tracked evidence artifact
OUT_PATH = os.environ.get(
    "PFX_DECODE_RESULTS", os.path.join(ROOT, "benchmarks", "results_decode.jsonl")
)

# BENCH_DEC_DTYPE: bf16 is the honest chip bench dtype (near-tie argmax
# flips between schedulers are counted in greedy_divergent_rows, not
# hidden); the CPU contract smoke forces float32, where greedy
# continuous-vs-coalesce divergence must be exactly ZERO
DTYPE = os.environ.get("BENCH_DEC_DTYPE", "bfloat16")

# case -> (batch, decode_strategy, legacy).  top_p 0.9 matches the
# reference's default nucleus setting (projects/gpt/docs generation
# configs).  ``*_legacy`` cases re-run the same shape with
# PFX_DECODE_ATTN=dense + PFX_DECODE_SCAN=1 (the attend-over-the-whole-
# cache scan path from before the decode overhaul), so every window
# produces an A/B row pair without code changes.
CASES = {
    "b8_greedy": (8, "greedy_search", False),
    "b8_greedy_legacy": (8, "greedy_search", True),
    "b8_topp": (8, "sampling", False),
    "b8_topp_legacy": (8, "sampling", True),
    "b32_greedy": (32, "greedy_search", False),
    "b32_greedy_legacy": (32, "greedy_search", True),
    "b32_topp": (32, "sampling", False),
    "b32_topp_legacy": (32, "sampling", True),
    # speculative + quantized A/B rows: each case runs its OWN baseline
    # on the same prompts and reports both sides in one row (value =
    # the feature side; baseline_tokens_per_s alongside).  The spec case
    # uses a REPETITIVE prompt — the self-draft lookup's best case, the
    # regime the acceptance contract pins (accept_rate >= 0.5).
    "b8_greedy_spec4": (8, "greedy_search", False),
    "b8_greedy_kvint8": (8, "greedy_search", False),
    "serving": (None, None, False),  # GenerationServer bucketed-batch traffic
    # staggered-arrival A/B: the SAME fixed-seed Poisson-ish request
    # trace through the continuous-batching scheduler vs the PR 3
    # coalescer — emits TWO rows (continuous + coalesce) reporting
    # delivered tokens/s and p99 TTFT, the head-of-line-blocking evidence
    "staggered": (None, None, False),
    # prefix-heavy staggered A/B: N requests sharing one long system
    # prefix replayed against the continuous scheduler with the
    # shared-prefix KV cache ON vs OFF — emits TWO rows (cached +
    # nocache) reporting TTFT percentiles, prefill tokens COMPUTED, and
    # the hit rate (docs/serving.md "Prefix cache")
    "prefix": (None, None, False),
    # dispatch-ahead A/B: the SAME greedy batch through two continuous
    # schedulers differing only in ``dispatch_ahead`` — emits TWO rows
    # (ahead + sync) reporting delivered tokens/s and ``host_gap_ms``,
    # the per-device-step host gap the overlap exists to hide
    # (docs/decode_path.md "Dispatch-ahead decode")
    "overlap": (None, None, False),
    # host-RAM spill tier A/B: the SAME prefix-heavy staggered trace
    # against a prefix budget too small to keep both prefix families
    # resident, with the spill tier ON vs OFF — emits TWO rows (on +
    # off) reporting readmits, prefill tokens COMPUTED (strictly fewer
    # with spill ON when anything readmitted), and honest greedy
    # divergence (docs/serving.md "KV lifecycle")
    "spill": (None, None, False),
    # two-tenant isolation A/B: a flood tenant bursts at t=0 while a
    # trickle tenant arrives staggered into the backlog, replayed
    # through a slot-starved continuous scheduler with weighted-fair
    # DRR ON vs single-class FCFS — emits TWO rows (fair + fcfs)
    # reporting per-tenant TTFT percentiles, the isolation evidence
    # (docs/serving.md "Multi-tenant isolation")
    "tenant": (None, None, False),
}

# env spellings of the two decode paths (read at trace time).  BOTH are
# pinned explicitly around each case — a baseline row must measure the
# overhauled path even if the caller's shell has PFX_DECODE_ATTN=dense
# left over from an A/B session, or the evidence artifact silently
# mislabels (the exact failure the loud-knob convention exists to stop).
_LEGACY_ENV = {"PFX_DECODE_ATTN": "dense", "PFX_DECODE_SCAN": "1"}
_OVERHAUL_ENV = {"PFX_DECODE_ATTN": "blocked", "PFX_DECODE_SCAN": "0"}


def _emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT_PATH, "a") as f:
        f.write(line + "\n")


def _metrics_for(name: str) -> list:
    """Metric names a case emits (staggered emits its A/B pair)."""
    if name == "serving":
        return ["gpt345m_serving_bucketed"]
    if name == "staggered":
        return ["gpt345m_decode_staggered_continuous",
                "gpt345m_decode_staggered_coalesce"]
    if name == "prefix":
        return ["gpt345m_decode_prefix_cached",
                "gpt345m_decode_prefix_nocache"]
    if name == "overlap":
        return ["gpt345m_decode_overlap_ahead",
                "gpt345m_decode_overlap_sync"]
    if name == "spill":
        return ["gpt345m_decode_spill_on",
                "gpt345m_decode_spill_off"]
    if name == "tenant":
        return ["gpt345m_decode_tenant_fair",
                "gpt345m_decode_tenant_fcfs"]
    return [f"gpt345m_decode_{name}"]


def _metric(name: str) -> str:
    return _metrics_for(name)[0]


def _parse_cases(cases_arg: str) -> list:
    out = []
    for name in cases_arg.split(","):
        name = name.strip()
        if name not in CASES:
            print(f"unknown case {name!r}; have {sorted(CASES)}", file=sys.stderr)
            continue
        out.append(name)
    return out


def _mfu_fields(cfg, per_chip_tokens_per_s: float) -> dict:
    """Hardware-normalized fields for a decode/serving row: the repo-wide
    analytic estimator on its forward-only basis (2·N per token — decode
    runs no backward) against the per-device-kind peak
    (docs/observability.md).  Same estimator as bench.py and the engine's
    step records, so BENCH_*.json trajectories compare on one definition."""
    from paddlefleetx_tpu.utils import telemetry

    flops_tok = telemetry.model_flops_per_token(cfg, backward=False)
    peak = telemetry.peak_flops()
    out = {"tokens_per_sec": round(per_chip_tokens_per_s, 1)}
    if flops_tok and peak:
        out["mfu"] = round(per_chip_tokens_per_s * flops_tok / peak, 6)
    return out


def _gpt_cfg(args):
    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    return GPTConfig(
        vocab_size=50304, hidden_size=args.hidden, num_layers=args.layers,
        num_attention_heads=16,
        max_position_embeddings=args.prompt + args.dec,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=DTYPE,
    )


def run_decode_case(name: str, args, params_cache: dict) -> dict:
    import jax

    from paddlefleetx_tpu.models.gpt import model as gpt
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig, generate

    batch, strategy, legacy = CASES[name]
    cfg = _gpt_cfg(args)
    gen = GenerationConfig(
        decode_strategy=strategy, max_dec_len=args.dec,
        top_p=0.9 if strategy == "sampling" else 1.0,
        temperature=1.0,
    )
    if "params" not in params_cache:
        params_cache["params"] = gpt.init(cfg, jax.random.key(0))
    params = params_cache["params"]
    prompts = jax.random.randint(
        jax.random.key(1), (batch, args.prompt), 0, cfg.vocab_size
    )
    key = jax.random.key(2)

    from bench import host_fence, knob_env

    with knob_env(_LEGACY_ENV if legacy else _OVERHAUL_ENV):
        fn = jax.jit(lambda p, ids, k: generate(p, ids, cfg, gen, key=k))
        # one-element host fetch per iteration (bench.host_fence)
        host_fence(fn(params, prompts, key))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(args.iters):
            host_fence(fn(params, prompts, key))
        dt = (time.perf_counter() - t0) / args.iters

    return {
        "metric": _metric(name), "value": round(batch * args.dec / dt, 1),
        "unit": "new tokens/s/chip", "vs_baseline": None,
        "batch": batch, "prompt_len": args.prompt, "dec_len": args.dec,
        "strategy": strategy,
        "decode_path": "legacy(dense+scan)" if legacy else "overhauled",
        "per_token_ms": round(dt / args.dec * 1e3, 3),
        **_mfu_fields(cfg, batch * args.dec / dt),
        "platform": jax.default_backend(),
    }


def _delivered(rows, eos_token_id: int) -> int:
    """Delivered tokens (cut at EOS) — both A/B sides of a greedy pair
    deliver the same count when token-identical, and the honest count
    when not."""
    total = 0
    for row in rows.tolist():
        if eos_token_id in row:
            row = row[: row.index(eos_token_id)]
        total += len(row)
    return total


def run_spec_case(name: str, args, params_cache: dict) -> dict:
    """Speculative-vs-baseline A/B on the SAME repetitive prompts: one
    row whose ``value`` is the speculative tokens/s, carrying the
    baseline rate, the measured acceptance rate, and the count of rows
    whose greedy output diverged (must be 0 — greedy speculation is
    token-identical by construction; bf16 near-ties are counted, not
    hidden)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.models.gpt import model as gpt
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig, generate
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    batch, strategy, _ = CASES[name]
    k = int(name.rsplit("spec", 1)[1])
    # a floor on the decode window: acceptance is a STEADY-STATE metric —
    # the first iteration's drafts derive from the prompt before the
    # model's own output loop establishes, so a handful of decode steps
    # under-reports the rate every longer window sustains (the row
    # reports the dec_len it actually ran)
    dec = max(int(args.dec), 24)
    import dataclasses

    cfg = dataclasses.replace(
        _gpt_cfg(args), max_position_embeddings=args.prompt + dec
    )
    gen = GenerationConfig(decode_strategy=strategy, max_dec_len=dec)
    # the extended context keys its own position table: params are cached
    # per context length (the plain cases keep sharing theirs)
    pkey = ("params", cfg.max_position_embeddings)
    if pkey not in params_cache:
        params_cache[pkey] = gpt.init(cfg, jax.random.key(0))
    params = params_cache[pkey]
    # repetitive prompt: a short token cycle fills the window, so the
    # n-gram lookup's needle always has an earlier occurrence
    cycle = np.array([11, 23, 7, 41], np.int32)
    prompt_row = np.tile(cycle, -(-args.prompt // len(cycle)))[: args.prompt]
    prompts = jnp.asarray(np.tile(prompt_row, (batch, 1)))
    key = jax.random.key(2)
    spec = SpecConfig(draft_k=k)

    from bench import host_fence, knob_env

    with knob_env(_OVERHAUL_ENV):
        base_fn = jax.jit(lambda p, ids, kk: generate(p, ids, cfg, gen, key=kk))
        spec_fn = jax.jit(lambda p, ids, kk: generate(
            p, ids, cfg, gen, key=kk, spec=spec, return_spec_stats=True))
        base_out = base_fn(params, prompts, key)
        host_fence(base_out)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(args.iters):
            host_fence(base_fn(params, prompts, key))
        dt_base = (time.perf_counter() - t0) / args.iters
        spec_out, (prop, acc) = spec_fn(params, prompts, key)
        host_fence(spec_out)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            host_fence(spec_fn(params, prompts, key)[0])
        dt_spec = (time.perf_counter() - t0) / args.iters

    base_rows = np.asarray(base_out)
    spec_rows = np.asarray(spec_out)
    divergent = int((base_rows != spec_rows).any(axis=1).sum())
    delivered = _delivered(spec_rows, gen.eos_token_id)
    prop, acc = int(prop), int(acc)
    toks = delivered / dt_spec
    return {
        "metric": _metric(name), "value": round(toks, 1),
        "unit": "new tokens/s/chip (speculative)", "vs_baseline": None,
        "batch": batch, "prompt_len": args.prompt, "dec_len": dec,
        "strategy": strategy, "decode_path": "overhauled",
        "draft_k": k, "drafter": "ngram",
        "baseline_tokens_per_s": round(delivered / dt_base, 1),
        "speedup": round(dt_base / dt_spec, 3),
        "accept_rate": round(acc / prop, 4) if prop else 0.0,
        "spec_proposed": prop, "spec_accepted": acc,
        "greedy_divergent_rows": divergent,
        **_mfu_fields(cfg, toks),
        "platform": jax.default_backend(),
    }


def run_kvint8_case(name: str, args, params_cache: dict) -> dict:
    """int8-KV-vs-native A/B on the same prompts: ``value`` is the int8
    tokens/s (the HBM-bytes win is chip evidence — CPU rows pay the
    dequant multiplies without the bandwidth relief), with the native
    rate and honest divergence count alongside."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.models.gpt import model as gpt
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig, generate

    batch, strategy, _ = CASES[name]
    cfg = _gpt_cfg(args)
    gen = GenerationConfig(decode_strategy=strategy, max_dec_len=args.dec)
    if "params" not in params_cache:
        params_cache["params"] = gpt.init(cfg, jax.random.key(0))
    params = params_cache["params"]
    prompts = jax.random.randint(
        jax.random.key(1), (batch, args.prompt), 0, cfg.vocab_size
    )
    key = jax.random.key(2)

    from bench import host_fence, knob_env

    outs, rates = {}, {}
    for kv in ("bf16", "int8"):
        with knob_env({**_OVERHAUL_ENV, "PFX_KV_DTYPE": kv}):
            fn = jax.jit(lambda p, ids, kk: generate(p, ids, cfg, gen, key=kk))
            out = fn(params, prompts, key)
            host_fence(out)  # compile + warm
            t0 = time.perf_counter()
            for _ in range(args.iters):
                host_fence(fn(params, prompts, key))
            dt = (time.perf_counter() - t0) / args.iters
            outs[kv] = np.asarray(out)
            rates[kv] = _delivered(outs[kv], gen.eos_token_id) / dt

    divergent = int((outs["bf16"] != outs["int8"]).any(axis=1).sum())
    return {
        "metric": _metric(name), "value": round(rates["int8"], 1),
        "unit": "new tokens/s/chip (int8 KV cache)", "vs_baseline": None,
        "batch": batch, "prompt_len": args.prompt, "dec_len": args.dec,
        "strategy": strategy, "decode_path": "overhauled",
        "kv_dtype": "int8",
        "baseline_tokens_per_s": round(rates["bf16"], 1),
        "divergent_rows": divergent,
        **_mfu_fields(cfg, rates["int8"]),
        "platform": jax.default_backend(),
    }


def run_serving_case(args) -> dict:
    """Bucketed-batch serving throughput: mixed request sizes through
    GenerationServer, measuring delivered new-tokens/s including the
    bucket-padding + host round-trip overhead the raw decode rows skip."""
    import jax
    import numpy as np

    server = _serving_server(args)  # sampling(top_p=0.9), the shared cfg

    rng = np.random.default_rng(0)
    # mixed client batch sizes -> power-of-two buckets 8 and 32; two
    # distinct request shapes exercise the bucket cache, repeats reuse it
    sizes = [8, 32, 8, 32]
    reqs = [
        [rng.integers(1, 50304, args.prompt).tolist() for _ in range(n)]
        for n in sizes
    ]
    from bench import knob_env

    with knob_env(_OVERHAUL_ENV):  # row is labeled "overhauled": pin it
        for req in reqs[:2]:  # compile both buckets outside the timed window
            server.generate_ids(req)
        t0 = time.perf_counter()
        delivered = 0
        for req in reqs:
            outs = server.generate_ids(req)
            delivered += sum(len(o) for o in outs)
        dt = time.perf_counter() - t0
    # the decode loop is bounded at batch*dec_len new tokens per request
    # (the while_loop can exit earlier once every row emits EOS, but with
    # random weights EOS is a ~1/vocab draw, so the bound is what runs);
    # report computed tokens/s as the throughput value and delivered
    # tokens/s alongside; normalized per chip like bench_extra (the dp
    # mesh spreads the batch)
    n_dev = jax.device_count()
    computed = sum(sizes) * args.dec
    return {
        "metric": _metric("serving"), "value": round(computed / dt / n_dev, 1),
        "unit": "new tokens/s/chip (bucketed serving)", "vs_baseline": None,
        "request_sizes": sizes, "prompt_len": args.prompt, "dec_len": args.dec,
        "delivered_tokens_per_s": round(delivered / dt / n_dev, 1),
        "strategy": "sampling(top_p=0.9)",
        "decode_path": "overhauled",
        "jit_traces": server.stats.get("traces"),
        **_mfu_fields(server.module.config, computed / dt / n_dev),
        "platform": jax.default_backend(),
    }


def _serving_server(args, *, greedy: bool = False):
    """One tiny-or-real GenerationServer for the serving/staggered cases."""
    import jax

    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    raw = {
        "Global": {"global_batch_size": 8, "seed": 7},
        "Engine": {"mix_precision": {"enable": False},
                   "save_load": {"save_steps": 0}},
        "Model": {
            "module": "GPTModule",
            "vocab_size": 50304, "hidden_size": args.hidden,
            "num_layers": args.layers, "num_attention_heads": 16,
            "max_position_embeddings": args.prompt + args.dec,
            "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
            "dtype": DTYPE,
        },
        "Distributed": {},
        "Optimizer": {"name": "FusedAdamW",
                      "lr": {"name": "Constant", "learning_rate": 1e-4}},
        "Generation": {
            "max_dec_len": args.dec,
            "decode_strategy": "greedy_search" if greedy else "sampling",
            "top_p": 0.9, "pad_to_multiple": args.prompt,
            "eos_token_id": 50256, "pad_token_id": 0,
        },
    }
    cfg = process_configs(AttrDict.from_nested(raw),
                          num_devices=jax.device_count())
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    return GenerationServer(cfg, mesh, module)


def _staggered_trace(n: int, mean_gap_s: float):
    """Fixed-seed Poisson-ish arrival offsets (exponential inter-arrival
    gaps, cumulative) — deterministic across runs, no wall-clock
    randomness, per the bench-contract rules."""
    import numpy as np

    rng = np.random.default_rng(42)
    gaps = rng.exponential(mean_gap_s, size=n)
    gaps[0] = 0.0
    return np.cumsum(gaps)


def _drive_staggered(submit, offsets, prompts, max_new, tenants=None):
    """Replay one arrival trace against a scheduler ``submit`` callable;
    returns (per-request TTFT seconds, per-request output rows, wall
    seconds).  TTFT here is submit->resolved: the serving definition for
    a non-streaming decode (tools/serve.py span semantics).  ``tenants``
    (optional, per-request labels) is forwarded as the ``tenant=``
    keyword — the multi-tenant case's fair side; ``None`` keeps the
    single-class submit shape every other case uses."""
    import threading

    n = len(prompts)
    ttft = [None] * n
    outs = [None] * n
    errs = [None] * n
    t0 = time.perf_counter()

    def worker(i):
        time.sleep(max(0.0, offsets[i] - (time.perf_counter() - t0)))
        t_sub = time.perf_counter()
        try:
            if tenants is None:
                fut = submit([prompts[i]], max_new)
            else:
                fut = submit([prompts[i]], max_new, tenant=tenants[i])
            rows = fut.result(timeout=600)
            ttft[i] = time.perf_counter() - t_sub
            outs[i] = rows[0]
        except Exception as e:  # noqa: BLE001 — recorded, parent stays honest
            errs[i] = e

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    bad = [e for e in errs if e is not None]
    if bad:
        raise RuntimeError(f"{len(bad)}/{n} staggered requests failed: {bad[0]}")
    return ttft, outs, wall


def run_staggered_case(args) -> list:
    """Continuous-vs-coalesce under the SAME staggered arrival trace.

    N single-prompt greedy requests arrive at fixed-seed Poisson-ish
    offsets scaled to ~25% of a single warm decode: most arrivals land
    while an earlier decode is mid-flight — exactly the head-of-line
    case iteration-level scheduling exists for.  The coalescer can only
    batch requests that are WAITING together, so late arrivals eat whole
    decode windows; the continuous scheduler admits them at the next
    step boundary.  Both paths deliver token-identical greedy output
    (asserted: the A/B is fair or the row is invalid)."""
    import jax
    import numpy as np

    from paddlefleetx_tpu.core.continuous_batching import (
        ContinuousScheduler,
        PagedDecodeEngine,
    )
    from paddlefleetx_tpu.core.request_queue import RequestQueue

    from bench import knob_env

    n_req = int(os.environ.get("BENCH_STAGGER_N", 6))
    gap_frac = float(os.environ.get("BENCH_STAGGER_GAP", 0.5))
    server = _serving_server(args, greedy=True)
    rng = np.random.default_rng(1)
    prompts = [
        rng.integers(1, 50304, args.prompt).tolist() for _ in range(n_req)
    ]

    rows = []
    with knob_env(_OVERHAUL_ENV):
        # calibrate: one warm single-request decode bounds the gap scale
        server.generate_ids([prompts[0]], max_dec_len=args.dec)
        t0 = time.perf_counter()
        ref = [server.generate_ids([p], max_dec_len=args.dec)[0]
               for p in prompts]
        t_one = (time.perf_counter() - t0) / n_req
        offsets = _staggered_trace(n_req, mean_gap_s=gap_frac * t_one)

        # -- continuous: iteration-level admission --------------------
        engine = PagedDecodeEngine(server, max_batch=max(8, n_req))
        sched = ContinuousScheduler(engine, max_depth=2 * n_req)
        sched.warmup([args.prompt])
        sched.start()
        ttft_cb, outs_cb, wall_cb = _drive_staggered(
            sched.submit, offsets, prompts, args.dec
        )
        sched.shutdown(timeout=60)
        # fairness: both paths must DELIVER the same token counts or the
        # tokens/s A/B is invalid.  Exact token identity is the f32 test
        # contract (tests/test_continuous_batching.py); the bench model
        # runs bf16 where random-init logits carry near-ties that flip
        # argmax between float-equivalent summation orders — count the
        # divergent rows honestly instead of failing the row
        if [len(o) for o in outs_cb] != [len(r) for r in ref]:
            raise RuntimeError(
                "continuous staggered DELIVERED COUNTS diverged from the "
                "sequential reference — the tokens/s A/B would be unfair"
            )
        divergent = sum(1 for a, b in zip(outs_cb, ref) if a != b)
        toks_cb = sum(len(o) for o in outs_cb)

        # -- coalesce: the PR 3 queue over the same server -------------
        # warm every power-of-two batch bucket a coalesced burst can land
        # on (exactly what tools/serve.py does at boot) so the A/B
        # measures scheduling, not a mid-traffic compile
        b = 1
        while b <= 8:
            server.generate_ids([prompts[0]] * b, max_dec_len=args.dec)
            b *= 2
        queue = RequestQueue(
            lambda ps, mx: server.generate_ids(ps, max_dec_len=mx),
            max_depth=2 * n_req, max_coalesce=8,
        )
        queue.start()
        ttft_co, outs_co, wall_co = _drive_staggered(
            lambda ps, mx: queue.submit(
                ps, mx, coalesce_key=(args.prompt, args.dec)
            ),
            offsets, prompts, args.dec,
        )
        queue.shutdown(timeout=60)
        toks_co = sum(len(o) for o in outs_co)

    n_dev = jax.device_count()

    def row(metric, ttft, toks, wall, extra):
        r = {
            "metric": metric, "value": round(toks / wall / n_dev, 1),
            "unit": "delivered new tokens/s/chip (staggered arrivals)",
            "vs_baseline": None,
            "arrivals": n_req, "prompt_len": args.prompt,
            "dec_len": args.dec,
            "mean_gap_s": round(float(gap_frac * t_one), 4),
            "single_decode_s": round(float(t_one), 4),
            "p50_ttft_s": round(float(np.quantile(ttft, 0.5)), 4),
            "p99_ttft_s": round(float(np.quantile(ttft, 0.99)), 4),
            "strategy": "greedy_search",
            "decode_path": "overhauled",
            **_mfu_fields(server.module.config, toks / wall / n_dev),
            "platform": jax.default_backend(),
        }
        r.update(extra)
        return r

    rows.append(row(
        "gpt345m_decode_staggered_continuous", ttft_cb, toks_cb, wall_cb,
        {"scheduler": "continuous", "jit_traces": engine.stats["traces"],
         "steps": engine.stats["steps"],
         "greedy_divergent_rows": divergent},
    ))
    rows.append(row(
        "gpt345m_decode_staggered_coalesce", ttft_co, toks_co, wall_co,
        {"scheduler": "coalesce"},
    ))
    return rows


def run_tenant_case(args) -> list:
    """Weighted-fair DRR vs single-class FCFS under the SAME two-tenant
    arrival trace (docs/serving.md "Multi-tenant isolation").

    A flood tenant bursts every request at t=0 into a deliberately
    slot-starved continuous engine (max_batch=2: the backlog is the
    point); a trickle tenant's requests land staggered INSIDE that
    backlog window.  The fair side labels submissions and weights the
    trickle tenant 8:1, so DRR hands it the next free slot ahead of the
    flood's queue; the FCFS side replays the identical trace through
    the same scheduler with every request in one class, so the trickle
    waits behind the whole burst.  Per-tenant TTFT percentiles are the
    row payload — the contract pins fair trickle-p99 <= fcfs
    trickle-p99 and exact greedy token identity at the f32 smoke dtype
    (both sides decode the same rows on the same engine; bf16 chip rows
    count near-tie argmax flips honestly instead)."""
    import jax
    import numpy as np

    from paddlefleetx_tpu.core.continuous_batching import (
        ContinuousScheduler,
        PagedDecodeEngine,
    )
    from paddlefleetx_tpu.core.tenancy import TenantConfig

    from bench import knob_env

    n_flood = int(os.environ.get("BENCH_TENANT_FLOOD", 6))
    n_trickle = int(os.environ.get("BENCH_TENANT_TRICKLE", 3))
    n_req = n_flood + n_trickle
    server = _serving_server(args, greedy=True)
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(1, 50304, args.prompt).tolist() for _ in range(n_req)
    ]
    tenants = ["flood"] * n_flood + ["trickle"] * n_trickle

    with knob_env(_OVERHAUL_ENV):
        # calibrate: one warm single-request decode bounds the gap scale
        server.generate_ids([prompts[0]], max_dec_len=args.dec)
        t0 = time.perf_counter()
        ref = [server.generate_ids([p], max_dec_len=args.dec)[0]
               for p in prompts]
        t_one = (time.perf_counter() - t0) / n_req
        # flood burst at t=0; trickle arrivals start a quarter-decode in
        # and stagger from there — all inside the ~(n_flood/2)*t_one
        # backlog the burst creates on a 2-slot engine
        gap = 0.5 * t_one
        trickle_off = 0.25 * t_one + _staggered_trace(n_trickle, gap)
        offsets = np.concatenate([np.zeros(n_flood), trickle_off])

        def side(tenant_cfg, labels):
            engine = PagedDecodeEngine(server, max_batch=2)
            sched = ContinuousScheduler(
                engine, max_depth=2 * n_req, tenant_config=tenant_cfg
            )
            sched.warmup([args.prompt])
            sched.start()
            ttft, outs, wall = _drive_staggered(
                sched.submit, offsets, prompts, args.dec, tenants=labels
            )
            sched.shutdown(timeout=120)
            if [len(o) for o in outs] != [len(r) for r in ref]:
                raise RuntimeError(
                    "tenant-case DELIVERED COUNTS diverged from the "
                    "sequential reference — the TTFT A/B would be unfair"
                )
            divergent = sum(1 for a, b in zip(outs, ref) if a != b)
            return ttft, sum(len(o) for o in outs), wall, divergent

        fair_cfg = TenantConfig.from_obj(
            {"tenants": {"flood": {"weight": 1}, "trickle": {"weight": 8}}},
            where="bench tenant case",
        )
        fair = side(fair_cfg, tenants)
        # FCFS control: same trace, same engine shape, one class — a
        # single tenant queue degenerates to exactly the old FCFS pull
        fcfs = side(None, None)

    n_dev = jax.device_count()

    def row(metric, scheduler, res, extra):
        ttft, toks, wall, divergent = res
        flood_t = ttft[:n_flood]
        trickle_t = ttft[n_flood:]
        r = {
            "metric": metric, "value": round(toks / wall / n_dev, 1),
            "unit": "delivered new tokens/s/chip (two-tenant trace)",
            "vs_baseline": None,
            "arrivals": n_req, "flood_arrivals": n_flood,
            "trickle_arrivals": n_trickle,
            "prompt_len": args.prompt, "dec_len": args.dec,
            "mean_gap_s": round(float(gap), 4),
            "single_decode_s": round(float(t_one), 4),
            "scheduler": scheduler,
            "p50_ttft_s": round(float(np.quantile(ttft, 0.5)), 4),
            "p99_ttft_s": round(float(np.quantile(ttft, 0.99)), 4),
            "flood_p50_ttft_s": round(float(np.quantile(flood_t, 0.5)), 4),
            "flood_p99_ttft_s": round(float(np.quantile(flood_t, 0.99)), 4),
            "trickle_p50_ttft_s": round(float(np.quantile(trickle_t, 0.5)), 4),
            "trickle_p99_ttft_s": round(float(np.quantile(trickle_t, 0.99)), 4),
            "greedy_divergent_rows": divergent,
            "strategy": "greedy_search",
            "decode_path": "overhauled",
            **_mfu_fields(server.module.config, toks / wall / n_dev),
            "platform": jax.default_backend(),
        }
        r.update(extra)
        return r

    return [
        row("gpt345m_decode_tenant_fair", "fair-drr", fair,
            {"weights": {"flood": 1, "trickle": 8}}),
        row("gpt345m_decode_tenant_fcfs", "fcfs", fcfs, {}),
    ]


def run_prefix_case(args) -> list:
    """Shared-prefix cache ON vs OFF under the SAME prefix-heavy
    staggered trace.

    N greedy requests share one long system prefix (75% of the prompt,
    distinct tails) and arrive at fixed-seed staggered offsets.  Both
    sides run the continuous scheduler on identical engines except
    ``prefix_cache_blocks``; a PRIMER request carrying the bare prefix
    runs before each timed window (cache-off too — same warm-up work)
    so the cached side models the steady state where the system prefix
    is resident.  The cached row reports the hit rate and the prompt
    tokens actually COMPUTED — strictly fewer than cache-off whenever
    anything hit — plus TTFT percentiles; output token-identity across
    the two sides is counted honestly (divergent_rows must be 0 at the
    f32 contract dtype)."""
    import jax
    import numpy as np

    from paddlefleetx_tpu.core.continuous_batching import (
        ContinuousScheduler,
        PagedDecodeEngine,
    )

    from bench import knob_env

    n_req = int(os.environ.get("BENCH_PREFIX_N", 6))
    gap_frac = float(os.environ.get("BENCH_STAGGER_GAP", 0.5))
    server = _serving_server(args, greedy=True)
    rng = np.random.default_rng(3)
    shared_len = max((args.prompt * 3 // 4), 2)
    shared = rng.integers(1, 50304, shared_len).tolist()
    prompts = [
        shared + rng.integers(1, 50304, args.prompt - shared_len).tolist()
        if args.prompt > shared_len else list(shared)
        for _ in range(n_req)
    ]

    with knob_env(_OVERHAUL_ENV):
        # calibrate the arrival gaps off one warm single decode
        server.generate_ids([prompts[0]], max_dec_len=args.dec)
        t0 = time.perf_counter()
        server.generate_ids([prompts[0]], max_dec_len=args.dec)
        t_one = time.perf_counter() - t0
        offsets = _staggered_trace(n_req, mean_gap_s=gap_frac * t_one)

        sides = {}
        for label, budget in (("nocache", 0), ("cached", 4096)):
            engine = PagedDecodeEngine(
                server, max_batch=max(8, n_req),
                prefix_cache_blocks=budget,
            )
            sched = ContinuousScheduler(engine, max_depth=2 * n_req)
            sched.warmup([args.prompt])
            sched.start()
            # primers, both OUTSIDE the timed window and identical on
            # both sides: the bare system prefix (on the cached side
            # this publishes its blocks) and one full prompt (on the
            # cached side its suffix compiles the chunk family, so the
            # timed window measures scheduling — not a first-hit
            # mid-traffic compile)
            sched.submit([shared], args.dec).result(timeout=600)
            sched.submit([prompts[0]], args.dec).result(timeout=600)
            # baselines AFTER the primers: the row reports the timed
            # window only (cumulative stats would count the second
            # primer's hit and push hit_rate past 1.0)
            tok0 = int(engine.stats["prefill_tokens"])
            pfx = engine.cache.prefix.stats
            h0, ht0 = int(pfx["hits"]), int(pfx["hit_tokens"])
            ttft, outs, wall = _drive_staggered(
                sched.submit, offsets, prompts, args.dec
            )
            sched.shutdown(timeout=60)
            sides[label] = {
                "ttft": ttft, "outs": outs, "wall": wall,
                "prefill_tokens": int(engine.stats["prefill_tokens"]) - tok0,
                "hits": int(pfx["hits"]) - h0,
                "hit_tokens": int(pfx["hit_tokens"]) - ht0,
                "traces": int(engine.stats["traces"]),
            }

    a, b = sides["cached"], sides["nocache"]
    if [len(o) for o in a["outs"]] != [len(o) for o in b["outs"]]:
        raise RuntimeError(
            "prefix-cache DELIVERED COUNTS diverged from cache-off — the "
            "TTFT/prefill A/B would be unfair"
        )
    divergent = sum(1 for x, y in zip(a["outs"], b["outs"]) if x != y)
    n_dev = jax.device_count()
    rows = []
    for label, side in (("cached", a), ("nocache", b)):
        toks = sum(len(o) for o in side["outs"])
        rows.append({
            "metric": f"gpt345m_decode_prefix_{label}",
            "value": round(toks / side["wall"] / n_dev, 1),
            "unit": "delivered new tokens/s/chip (prefix-heavy staggered)",
            "vs_baseline": None,
            "arrivals": n_req, "prompt_len": args.prompt,
            "dec_len": args.dec,
            "shared_prefix_len": shared_len,
            "mean_gap_s": round(float(gap_frac * t_one), 4),
            "single_decode_s": round(float(t_one), 4),
            "p50_ttft_s": round(float(np.quantile(side["ttft"], 0.5)), 4),
            "p99_ttft_s": round(float(np.quantile(side["ttft"], 0.99)), 4),
            "prefill_tokens": side["prefill_tokens"],
            "prefix_hits": side["hits"],
            "prefix_hit_tokens": side["hit_tokens"],
            "hit_rate": round(side["hits"] / n_req, 4),
            "greedy_divergent_rows": divergent,
            "jit_traces": side["traces"],
            "strategy": "greedy_search",
            "decode_path": "overhauled",
            "scheduler": "continuous",
            **_mfu_fields(server.module.config, toks / side["wall"] / n_dev),
            "platform": jax.default_backend(),
        })
    return rows


def run_overlap_case(args) -> list:
    """Dispatch-ahead ON vs OFF under the SAME greedy batch.

    One batched submission of N prompts through two continuous
    schedulers on identical engines, differing only in
    ``dispatch_ahead``.  Each side reports delivered tokens/s plus
    ``host_gap_ms`` — mean host time per device step spent with NO step
    in flight (the engine's ``host_gap_s``/``steps`` accounting).  The
    synchronous side pays the full commit-processing + scheduler-scan
    gap on EVERY step; the overlapped side only pays it on admission
    boundaries (chained dispatches land while the previous step is
    still in flight, gap zero by construction), so its ``host_gap_ms``
    must come out strictly lower — the contract test pins that.
    Greedy output token-identity across the sides is counted
    (``greedy_divergent_rows`` must be 0 at the f32 contract dtype)."""
    import jax
    import numpy as np

    from paddlefleetx_tpu.core.continuous_batching import (
        ContinuousScheduler,
        PagedDecodeEngine,
    )

    from bench import knob_env

    n_req = int(os.environ.get("BENCH_OVERLAP_N", 8))
    server = _serving_server(args, greedy=True)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 50304, args.prompt).tolist()
               for _ in range(n_req)]

    with knob_env(_OVERHAUL_ENV):
        sides = {}
        for label, ahead in (("sync", False), ("ahead", True)):
            engine = PagedDecodeEngine(server, max_batch=n_req)
            sched = ContinuousScheduler(engine, max_depth=2 * n_req,
                                        dispatch_ahead=ahead)
            sched.warmup([args.prompt])
            sched.start()
            # primer OUTSIDE the timed window: compiles the decode
            # chunk family so the window measures stepping, not traces
            sched.submit([prompts[0]], args.dec).result(timeout=600)
            g0 = float(engine.stats["host_gap_s"])
            n0 = int(engine.stats["gap_steps"])
            s0 = int(engine.stats["steps"])
            tl0 = sched.time_ledger()
            t0 = time.perf_counter()
            outs = sched.submit(prompts, args.dec).result(timeout=600)
            wall = time.perf_counter() - t0
            # goodput off the scheduler's own time ledger, deltas over
            # the timed window only (the primer/warmup laps are out).
            # The numerator is DEVICE-COVERED wall: non-idle scheduler
            # time minus host_gap_s (host time the device sat starved
            # waiting for its next dispatch).  Attributed-bucket sums
            # (device_decode+readback) cannot discriminate the overlap
            # win — both sides book the device wait under readback —
            # but the gap is zero by construction when chained
            # dispatches land in flight, so covered/non-idle is the
            # honest "was the device fed" fraction.
            tl1 = sched.time_ledger()
            led = {k: tl1["buckets"][k] - tl0["buckets"][k]
                   for k in tl1["buckets"]}
            led_wall = max(tl1["wall_s"] - tl0["wall_s"], 1e-9)
            gap = float(engine.stats["host_gap_s"]) - g0
            non_idle = max(led_wall - led["idle"], 1e-9)
            covered = max(non_idle - gap, 0.0)
            sides[label] = {
                "outs": outs, "wall": wall,
                "host_gap_s": gap,
                "gap_steps": int(engine.stats["gap_steps"]) - n0,
                "steps": max(1, int(engine.stats["steps"]) - s0),
                "traces": int(engine.stats["traces"]),
                "goodput_frac": covered / non_idle,
                "device_util": covered / led_wall,
            }
            sched.shutdown(timeout=60)

    a, b = sides["ahead"], sides["sync"]
    divergent = sum(1 for x, y in zip(a["outs"], b["outs"]) if x != y)
    n_dev = jax.device_count()
    rows = []
    for label, side in (("ahead", a), ("sync", b)):
        toks = sum(len(o) for o in side["outs"])
        rows.append({
            "metric": f"gpt345m_decode_overlap_{label}",
            "value": round(toks / side["wall"] / n_dev, 1),
            "unit": "delivered new tokens/s/chip (dispatch-ahead A/B)",
            "vs_baseline": None,
            "dispatch_ahead": label == "ahead",
            "host_gap_ms": round(
                side["host_gap_s"] * 1000.0 / side["steps"], 4),
            # goodput ledger view of the same window: device-covered
            # fraction of non-idle scheduler wall (goodput_frac) and of
            # TOTAL wall (device_util) — dispatch-ahead must win the
            # former strictly (contract-pinned; its host gap is zero by
            # construction while sync pays it every step)
            "goodput_frac": round(side["goodput_frac"], 4),
            "device_util": round(side["device_util"], 4),
            "gap_steps": side["gap_steps"],
            "device_steps": side["steps"],
            "batch": n_req, "prompt_len": args.prompt,
            "dec_len": args.dec,
            "greedy_divergent_rows": divergent,
            "jit_traces": side["traces"],
            "strategy": "greedy_search",
            "decode_path": "overhauled",
            "scheduler": "continuous",
            **_mfu_fields(server.module.config,
                          toks / side["wall"] / n_dev),
            "platform": jax.default_backend(),
        })
    return rows


def run_spill_case(args) -> list:
    """Host-RAM spill tier ON vs OFF under the SAME prefix-heavy
    staggered trace with an arena prefix budget too small for the
    traffic.

    Two prefix families (A and B, one full KV block each) alternate at
    fixed-seed staggered offsets against a prefix budget of ONE block:
    every publication of one family evicts the other, so with the spill
    tier OFF each arrival recomputes its full prompt, while with the
    tier ON (``prefix_spill_bytes``) the evicted prefix demotes to host
    RAM and the next arrival of its family READMITS it instead.  Both
    sides run identical engines except ``prefix_spill_bytes`` and the
    same primers (bare prefixes publish the blocks, one full prompt per
    family compiles the post-hit suffix family outside the timed
    window).  The ON row reports readmits and the prompt tokens
    actually COMPUTED — strictly fewer than OFF whenever anything
    readmitted — and greedy output token-identity across the sides is
    counted honestly (``greedy_divergent_rows`` must be 0 at the f32
    contract dtype: a readmitted block is the bit-exact KV that was
    evicted)."""
    import jax
    import numpy as np

    from paddlefleetx_tpu.core.continuous_batching import (
        ContinuousScheduler,
        PagedDecodeEngine,
    )

    from bench import knob_env

    n_req = int(os.environ.get("BENCH_SPILL_N", 6))
    gap_frac = float(os.environ.get("BENCH_STAGGER_GAP", 0.5))
    block = 8  # small block so a tiny --prompt still carries full blocks
    server = _serving_server(args, greedy=True)
    rng = np.random.default_rng(13)
    shared_len = block
    tail_len = max(args.prompt - shared_len, block)
    fams = ("A", "B")
    pref = {f: rng.integers(1, 50304, shared_len).tolist() for f in fams}
    # primer tails are DISTINCT from the timed prompts: the timed
    # window must exercise prefix readmission, not whole-prompt reuse
    primer = {f: pref[f] + rng.integers(1, 50304, tail_len).tolist()
              for f in fams}
    prompts = [
        pref[fams[i % 2]] + rng.integers(1, 50304, tail_len).tolist()
        for i in range(n_req)
    ]

    with knob_env(_OVERHAUL_ENV):
        # calibrate the arrival gaps off one warm single decode
        server.generate_ids([prompts[0]], max_dec_len=args.dec)
        t0 = time.perf_counter()
        server.generate_ids([prompts[0]], max_dec_len=args.dec)
        t_one = time.perf_counter() - t0
        offsets = _staggered_trace(n_req, mean_gap_s=gap_frac * t_one)

        sides = {}
        for label, spill_bytes in (("off", 0), ("on", 64 << 20)):
            engine = PagedDecodeEngine(
                server, max_batch=max(8, n_req), block=block,
                # ONE block of prefix budget: publishing either family
                # evicts the other — the churn the spill tier survives
                prefix_cache_blocks=1,
                prefix_spill_bytes=spill_bytes,
            )
            sched = ContinuousScheduler(engine, max_depth=2 * n_req)
            sched.warmup([shared_len + tail_len])
            sched.start()
            # primers, identical on both sides and OUTSIDE the timed
            # window: bare prefixes publish each family's block; full
            # prompts compile the post-hit suffix prefill family.  After
            # family B's primers, family A's block is evicted — spilled
            # on the ON side, gone on the OFF side
            for f in fams:
                sched.submit([list(pref[f])], args.dec).result(timeout=600)
                sched.submit([list(primer[f])], args.dec).result(timeout=600)
            tok0 = int(engine.stats["prefill_tokens"])
            pfx = engine.cache.prefix.stats
            h0, ht0 = int(pfx["hits"]), int(pfx["hit_tokens"])
            sp = engine.cache.spill.stats
            sp0, rd0, dc0 = (int(sp["spills"]), int(sp["readmits"]),
                             int(sp["discards"]))
            ttft, outs, wall = _drive_staggered(
                sched.submit, offsets, prompts, args.dec
            )
            sched.shutdown(timeout=60)
            sides[label] = {
                "ttft": ttft, "outs": outs, "wall": wall,
                "prefill_tokens": int(engine.stats["prefill_tokens"]) - tok0,
                "hits": int(pfx["hits"]) - h0,
                "hit_tokens": int(pfx["hit_tokens"]) - ht0,
                "spills": int(sp["spills"]) - sp0,
                "readmits": int(sp["readmits"]) - rd0,
                "spill_discards": int(sp["discards"]) - dc0,
                "traces": int(engine.stats["traces"]),
            }

    a, b = sides["on"], sides["off"]
    if [len(o) for o in a["outs"]] != [len(o) for o in b["outs"]]:
        raise RuntimeError(
            "spill-tier DELIVERED COUNTS diverged from spill-off — the "
            "prefill/readmit A/B would be unfair"
        )
    divergent = sum(1 for x, y in zip(a["outs"], b["outs"]) if x != y)
    n_dev = jax.device_count()
    rows = []
    for label, side, budget in (("on", a, 64 << 20), ("off", b, 0)):
        toks = sum(len(o) for o in side["outs"])
        rows.append({
            "metric": f"gpt345m_decode_spill_{label}",
            "value": round(toks / side["wall"] / n_dev, 1),
            "unit": "delivered new tokens/s/chip "
                    "(prefix-heavy staggered, spill A/B)",
            "vs_baseline": None,
            "arrivals": n_req, "prompt_len": shared_len + tail_len,
            "dec_len": args.dec,
            "shared_prefix_len": shared_len,
            "kv_block": block,
            "prefix_budget_blocks": 1,
            "spill_budget_bytes": budget,
            "mean_gap_s": round(float(gap_frac * t_one), 4),
            "p50_ttft_s": round(float(np.quantile(side["ttft"], 0.5)), 4),
            "p99_ttft_s": round(float(np.quantile(side["ttft"], 0.99)), 4),
            "prefill_tokens": side["prefill_tokens"],
            "prefix_hits": side["hits"],
            "prefix_hit_tokens": side["hit_tokens"],
            "spills": side["spills"],
            "readmits": side["readmits"],
            "spill_discards": side["spill_discards"],
            "readmit_hit_rate": round(side["readmits"] / n_req, 4),
            "greedy_divergent_rows": divergent,
            "jit_traces": side["traces"],
            "strategy": "greedy_search",
            "decode_path": "overhauled",
            "scheduler": "continuous",
            **_mfu_fields(server.module.config, toks / side["wall"] / n_dev),
            "platform": jax.default_backend(),
        })
    return rows


def _parent(argv) -> int:
    from bench import run_child_with_honest_fallback

    ap = _argparser()
    args = ap.parse_args(argv)
    cases = _parse_cases(args.cases)
    if not cases:
        print(f"no valid cases in {args.cases!r}; have {sorted(CASES)}",
              file=sys.stderr)
        return 2

    def emit_missing(seen, reason):
        for name in cases:
            for metric in _metrics_for(name):
                if metric not in seen:
                    _emit({"metric": metric, "value": 0.0,
                           "unit": f"new tokens/s/chip ({reason})",
                           "vs_baseline": None})

    return run_child_with_honest_fallback(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--cases", ",".join(cases), "--prompt", str(args.prompt),
         "--dec", str(args.dec), "--iters", str(args.iters),
         "--hidden", str(args.hidden), "--layers", str(args.layers)],
        float(os.environ.get("BENCH_DECODE_DEADLINE_S", 1200)),
        emit_missing,
    )


def _child(argv) -> None:
    args = _argparser().parse_args(argv)

    from paddlefleetx_tpu.utils.device import apply_platform_env

    apply_platform_env()
    cases = _parse_cases(args.cases)

    params_cache: dict = {}
    for name in cases:
        try:
            if name == "serving":
                rows = [run_serving_case(args)]
            elif name == "staggered":
                rows = run_staggered_case(args)
            elif name == "prefix":
                rows = run_prefix_case(args)
            elif name == "overlap":
                rows = run_overlap_case(args)
            elif name == "spill":
                rows = run_spill_case(args)
            elif name == "tenant":
                rows = run_tenant_case(args)
            elif "_spec" in name:
                rows = [run_spec_case(name, args, params_cache)]
            elif name.endswith("_kvint8"):
                rows = [run_kvint8_case(name, args, params_cache)]
            else:
                rows = [run_decode_case(name, args, params_cache)]
        except Exception as e:  # noqa: BLE001 — an OOM on b32 must not
            # abort the remaining cases
            traceback.print_exc(file=sys.stderr)
            rows = [{"metric": metric, "value": 0.0,
                     "unit": f"new tokens/s/chip ({type(e).__name__})",
                     "vs_baseline": None}
                    for metric in _metrics_for(name)]
        for row in rows:
            _emit(row)


def _argparser():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--cases",
        default="b8_greedy,b8_greedy_legacy,b8_topp,b8_topp_legacy,"
                "b32_greedy,b32_greedy_legacy,b32_topp,b32_topp_legacy,"
                "b8_greedy_spec4,b8_greedy_kvint8,serving,staggered,prefix,"
                "overlap,spill,tenant",
    )
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--dec", type=int, default=256)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--hidden", type=int, default=int(os.environ.get("BENCH_DEC_HIDDEN", 1024)))
    ap.add_argument("--layers", type=int, default=int(os.environ.get("BENCH_DEC_LAYERS", 24)))
    return ap


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--child" in argv:
        argv.remove("--child")
        _child(argv)
        return
    sys.exit(_parent(argv))


if __name__ == "__main__":
    main()
