"""Benchmark: GPT-345M pretrain throughput (tokens/s) on the local device(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline: reference PaddleFleetX GPT-345M single-card pretrain ~16,260
tokens/s on 1x V100-32G (BASELINE.md / projects/gpt/docs/single_card.md:40-49).

The benchmark itself runs in a CHILD process; the parent is pure Python (no
jax import), so it never holds the chip the child needs, stays responsive
to SIGTERM, and ALWAYS emits the one JSON line — the child's real number,
or an honest value:0.0 — before exiting.

No fallback: the child resolves the platform like every entry point
(utils/device.apply_platform_env — ``tpu`` unless a CPU pin is set), so
without a chip and without a pin it fails and the row is the honest zero.
A pinned CPU run (``PFX_PLATFORM=cpu`` + the ``BENCH_*`` shrink knobs) is
a contract smoke, labeled ``"platform": "cpu"``.
"""

import contextlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_TOKENS_PER_S = 16260.0
METRIC = "gpt345m_pretrain_throughput_per_chip"

# long-context ring-attention row (shard_map-port PR): seq >= 4096 through
# parallel/ring_attention.py with the zigzag causal layout on a sep-axis
# ring over every local device.  No published reference number exists (the
# reference has no context-parallel path at all — SURVEY §5.7, max trained
# context 1024), so the row reports an absolute rate with vs_baseline null.
RING_METRIC = "ring_attention_seq4096_throughput_per_chip"


def model_flops_per_token(hidden: int, layers: int, vocab: int, seq: int) -> float:
    """Model FLOPs per token, fwd + 2x bwd, WITH the seq-dependent
    attention-score term (causal attention counted at half the score
    matrix) — kept for benchmarks/bench_extra.py's detailed view.  The
    headline row's ``mfu``/``tokens_per_sec`` fields instead come from
    the repo-wide analytic 6·N estimator
    (paddlefleetx_tpu.utils.telemetry.model_flops_per_token), the same
    one the engine's step records and bench_decode.py use, so every
    BENCH_*.json trajectory is normalized by ONE definition."""
    h, L, v = int(hidden), int(layers), int(vocab)
    ffn = 4 * h
    per = L * (2 * h * 3 * h + 2 * seq * h + 2 * h * h + 4 * h * ffn) + 2 * h * v
    return per * 3.0


def host_fence(out):
    """Wait for ALL device work behind ``out`` by fetching ONE element.

    A device->host copy is a fence that cannot return early: the
    one-element slice depends on the full output buffer, so the 2-4 byte
    transfer completes only after the whole computation.  Shared by
    bench_decode.py and kernel_bench.py so there is exactly one fence
    implementation; ``chip_smoke.py`` prints the step time under this
    fence and under ``block_until_ready`` side by side."""
    import jax
    import numpy as np

    leaf = jax.tree_util.tree_leaves(out)[0]
    return np.asarray(leaf.ravel()[:1])


@contextlib.contextmanager
def knob_env(knobs):
    """Context manager: set trace-time env knobs (PFX_FLASH_*/PFX_DECODE_*)
    for a bench section, clearing jax's trace caches on BOTH edges, and
    restore the prior values (pop if previously unset) on exit — even on
    error.  The single audited copy of the save/mutate/restore hygiene
    (ADVICE r5: a sweep that leaves its last combo exported poisons any
    in-process caller that traces afterwards); child-process only, like
    host_fence — the parent never imports jax (jax is imported lazily in
    the generator body, which only runs when a child enters the cm)."""
    import jax

    saved = {k: os.environ.get(k) for k in knobs}
    try:
        os.environ.update({k: str(v) for k, v in knobs.items()})
        jax.clear_caches()  # env knobs are read at trace time
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        jax.clear_caches()


def _honest_row(reason: str) -> dict:
    return {
        "metric": METRIC,
        "value": 0.0,
        "unit": f"tokens/s/chip ({reason})",
        "vs_baseline": 0.0,
    }


def _honest_ring_row(reason: str) -> dict:
    # vs_baseline null: no published reference number for long-context CP
    return {
        "metric": RING_METRIC,
        "value": 0.0,
        "unit": f"tokens/s/chip ({reason})",
        "vs_baseline": None,
    }


# ----------------------------------------------------------------------
# Parent harness: spawn the child benchmark, relay its JSON lines, and
# guarantee the expected metric rows come out even on SIGTERM / deadline.
# Shared by bench.py and benchmarks/bench_extra.py (which imports it).
def run_child_with_honest_fallback(child_argv, deadline_s, emit_missing) -> int:
    """Run `child_argv`, relaying its stdout.  `emit_missing(seen, reason)`
    is called with the set of metric names the child DID print whenever the
    run ends abnormally (signal, deadline, bad exit, no output) and must
    print honest zero rows for everything still missing.  The parent
    never imports jax, so it never holds the chip and stays responsive to
    the driver's SIGTERM."""
    seen: set = set()

    child = subprocess.Popen(child_argv, stdout=subprocess.PIPE, text=True)

    def _reader():
        # relay the child's stdout as it streams; remember metric rows
        for line in child.stdout:
            line = line.rstrip("\n")
            if not line:
                continue
            try:
                row = json.loads(line)
                if isinstance(row, dict) and "metric" in row:
                    seen.add(row["metric"])
            except ValueError:
                pass
            print(line, flush=True)

    t = threading.Thread(target=_reader, daemon=True)
    t.start()

    def _quiesce():
        # emission is about to start: a late follow-up signal (driver
        # kill-then-escalate) must not re-enter the handler and print
        # duplicate fallback rows
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        signal.signal(signal.SIGINT, signal.SIG_IGN)

    def _bail(reason: str) -> int:
        _quiesce()
        try:
            child.kill()
        except OSError:
            pass
        # drain the pipe BEFORE deciding what's missing: the child may have
        # printed its real row in the same instant — emitting a fallback on
        # top would break the one-line-per-metric contract
        t.join(timeout=10)
        emit_missing(seen, reason)
        return 0

    def _on_term(signum, frame):
        # the driver's clock ran out: emit the honest line(s) NOW and exit 0
        # so the capture parses (a propagated kill would record rc!=0,
        # parsed:null — round 3's failure mode)
        _bail(f"killed by signal {signum} before completion")
        os._exit(0)

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    start = time.time()
    while True:
        rc = child.poll()
        if rc is not None:
            _quiesce()
            t.join(timeout=10)
            emit_missing(seen, f"child exited rc={rc} with no JSON")
            return 0
        if time.time() - start > deadline_s:
            return _bail(f"self-deadline {deadline_s:.0f}s exceeded")
        time.sleep(0.5)


def _parent() -> int:
    def emit_missing(seen, reason):
        if METRIC not in seen:
            print(json.dumps(_honest_row(reason)), flush=True)

    rc = run_child_with_honest_fallback(
        [sys.executable, os.path.abspath(__file__), "--child"],
        float(os.environ.get("BENCH_DEADLINE_S", 600)),
        emit_missing,
    )

    if os.environ.get("BENCH_RING", "1") != "1":
        return rc

    def emit_missing_ring(seen, reason):
        if RING_METRIC not in seen:
            print(json.dumps(_honest_ring_row(reason)), flush=True)

    rc_ring = run_child_with_honest_fallback(
        [sys.executable, os.path.abspath(__file__), "--child-ring"],
        float(os.environ.get("BENCH_RING_DEADLINE_S", 600)),
        emit_missing_ring,
    )
    return rc or rc_ring


# ----------------------------------------------------------------------
def _child() -> None:
    # tpu unless a CPU pin is set: without a chip this fails at the first
    # backend touch and the parent prints the honest zero row
    from paddlefleetx_tpu.utils.device import apply_platform_env

    apply_platform_env()

    import jax
    import numpy as np

    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    n_dev = jax.device_count()
    batch = int(os.environ.get("BENCH_BATCH", 16)) * n_dev
    seq = int(os.environ.get("BENCH_SEQ", 1024))
    steps = int(os.environ.get("BENCH_STEPS", 10))

    cfg = AttrDict.from_nested(
        {
            "Global": {
                "global_batch_size": batch,
                "micro_batch_size": batch // n_dev,
                "seed": 1024,
                # hardware RNG for dropout masks: ~15% step-time win over
                # threefry on TPU, no effect on loss statistics
                "prng_impl": os.environ.get("BENCH_PRNG", "rbg"),
            },
            "Engine": {
                "max_steps": steps,
                "eval_freq": 0,
                "logging_freq": 10**9,
                "mix_precision": {"enable": True, "dtype": "bfloat16"},
                "save_load": {"save_steps": 0},
            },
            "Model": {
                "module": "GPTModule",
                # BENCH_* shrink knobs are for CI smoke of the bench
                # contract only; the real case is the reference 345M shape
                "vocab_size": int(os.environ.get("BENCH_VOCAB", 50304)),
                "hidden_size": int(os.environ.get("BENCH_HIDDEN", 1024)),
                "num_layers": int(os.environ.get("BENCH_LAYERS", 24)),
                "num_attention_heads": int(os.environ.get("BENCH_HEADS", 16)),
                "max_position_embeddings": seq,
                "hidden_dropout_prob": float(os.environ.get("BENCH_DROPOUT", 0.1)),
                "attention_probs_dropout_prob": float(os.environ.get("BENCH_DROPOUT", 0.1)),
                "attn_impl": os.environ.get("BENCH_ATTN", "flash"),
                # 16GB v5e HBM can't hold the full activation set (37G), but
                # blanket full-layer remat wastes a whole extra forward;
                # "selective" saves the measured-best named set (qkv +
                # attn_out + attn_lse) and recomputes the cheap rest
                "use_recompute": os.environ.get("BENCH_RECOMPUTE", "1") == "1",
                "recompute_granularity": os.environ.get("BENCH_REMAT", "selective"),
                "use_fused_ln": os.environ.get("BENCH_FUSED_LN", "1") == "1",
                # streams the vocab through the CE so the fp32 logits buffer
                # never materializes (ops/chunked_ce.py) — try with bigger
                # BENCH_BATCH once enabled
                "use_chunked_ce": os.environ.get("BENCH_CHUNKED_CE", "0") == "1",
                "scan_unroll": int(os.environ.get("BENCH_SCAN_UNROLL", 1)),
                # measured on-chip 2026-07-31 via the end-to-end headline
                # A/B (the trustworthy loss-host-fetch timing): 34,940
                # tok/s with fused/512 vs 33,757 with the old split/256 —
                # +3.5%.  Fall back to the auto block ladder when 512
                # does not divide the (override) seq, so shrink-knob CI
                # smokes and odd seqs keep flash support.
                "flash_block": int(os.environ.get(
                    "BENCH_FLASH_BLOCK", 512 if seq % 512 == 0 else 0)),
                "flash_bwd": os.environ.get("BENCH_FLASH_BWD", "fused"),
            },
            "Distributed": {},
            "Optimizer": {
                "name": "FusedAdamW",
                "weight_decay": 0.01,
                "beta1": 0.9,
                "beta2": 0.95,
                "lr": {"name": "Constant", "learning_rate": 1e-4},
                "grad_clip": {"name": "ClipGradByGlobalNorm", "clip_norm": 1.0},
            },
        }
    )
    cfg = process_configs(cfg, num_devices=n_dev)
    mesh = init_dist_env(cfg)
    module = build_module(cfg)

    rng = np.random.default_rng(0)
    vocab = int(cfg.Model.vocab_size)
    host_batch = {
        "tokens": rng.integers(0, vocab, (batch, seq)).astype(np.int64),
        "labels": rng.integers(0, vocab, (batch, seq)).astype(np.int64),
        "loss_mask": np.ones((batch, seq), np.float32),
        "position_ids": np.tile(np.arange(seq), (batch, 1)),
    }

    with mesh:
        engine = Engine(cfg, module, mesh)
        dev_batch = engine._put_batch(host_batch)
        # warmup (compile)
        for _ in range(3):
            engine.state, m = engine.train_step(engine.state, dev_batch)
        float(m["loss"])  # host fetch: drains the warmup chain (see below)
        t0 = time.time()
        for _ in range(steps):
            engine.state, m = engine.train_step(engine.state, dev_batch)
        # a device->host fetch of the final loss fences the whole
        # donated-state chain (see host_fence)
        final_loss = float(m["loss"])
        dt = time.time() - t0

    if not np.isfinite(final_loss):
        # same honest-failure contract as the unreachable-backend path:
        # always ONE parseable JSON line, never a traceback
        print(json.dumps(_honest_row(f"non-finite bench loss {final_loss}")), flush=True)
        return

    tokens_per_s = batch * seq * steps / dt

    # hardware normalization via the repo-wide estimator (6·N per token)
    # and per-device-kind peak table — BENCH_PEAK_TFLOPS / PFX_PEAK_FLOPS
    # override, in that order (docs/observability.md)
    from paddlefleetx_tpu.utils import telemetry

    mc = cfg.Model
    flops_tok = telemetry.model_flops_per_token(
        vocab_size=mc.vocab_size, hidden_size=mc.hidden_size,
        num_layers=mc.num_layers,
    )
    # an unknown device_kind has no peak and therefore no MFU — never an
    # assumed one
    env_peak = os.environ.get("BENCH_PEAK_TFLOPS")
    peak = float(env_peak) * 1e12 if env_peak else telemetry.peak_flops()
    mfu = tokens_per_s / n_dev * flops_tok / peak if peak else None

    print(
        json.dumps(
            {
                "metric": METRIC,
                "value": round(tokens_per_s / n_dev, 1),
                "unit": "tokens/s/chip",
                "vs_baseline": round(tokens_per_s / n_dev / BASELINE_TOKENS_PER_S, 3),
                "tokens_per_sec": round(tokens_per_s, 1),
                # 6 digits: CPU smoke shapes under forced multi-device
                # hosts land near 1e-5 and must not round to a dishonest 0
                "mfu": None if mfu is None else round(mfu, 6),
                # CPU smoke rows must never read as chip evidence
                "platform": jax.default_backend(),
            }
        ),
        flush=True,
    )


def _child_ring() -> None:
    """Long-context ring-attention case: fwd+bwd of
    parallel/ring_attention.py at BENCH_RING_SEQ (>= 4096) rows, zigzag
    causal layout, K/V rotating a sep-axis ring over every local device.

    Multi-device gated: a ring of one is dense attention, not the
    collective path — a 1-device backend emits an honest platform-labeled
    zero row naming the gate instead of a dishonest dense number.  A
    pinned CPU smoke gets a virtual 4-device host (the flag must land
    before jax initializes) and shrinks heads/dim through the
    ``BENCH_RING_*`` knobs itself — never the sequence."""
    from paddlefleetx_tpu.utils.device import apply_platform_env

    if apply_platform_env() == "cpu":
        # a pinned cpu smoke has one real device: the ring needs a sep
        # axis, so force virtual host devices BEFORE the first in-process
        # backend init (no-op when the caller already did)
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
    from paddlefleetx_tpu.parallel.ring_attention import (
        ring_attention,
        zigzag_permutation,
    )

    n_dev = jax.device_count()
    if n_dev < 2:
        print(
            json.dumps(
                {
                    **_honest_ring_row("needs >= 2 devices for the sep ring"),
                    "platform": jax.default_backend(),
                }
            ),
            flush=True,
        )
        return

    seq = int(os.environ.get("BENCH_RING_SEQ", 4096))
    heads = int(os.environ.get("BENCH_RING_HEADS", 16))
    dim = int(os.environ.get("BENCH_RING_DIM", 64))
    batch = int(os.environ.get("BENCH_RING_BATCH", 1))
    steps = int(os.environ.get("BENCH_RING_STEPS", 4))
    chunk = int(os.environ.get("BENCH_RING_CHUNK", 1024))
    # ring = every local device on the sep axis; zigzag needs 2*ring | seq
    ring = n_dev
    while ring > 1 and seq % (2 * ring):
        ring //= 2
    if ring < 2:
        print(
            json.dumps(
                {
                    **_honest_ring_row(
                        f"no ring >= 2 divides seq {seq} on {n_dev} devices"
                    ),
                    "platform": jax.default_backend(),
                }
            ),
            flush=True,
        )
        return
    dtype = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16

    mesh = build_mesh(MeshConfig(sep_degree=ring), jax.devices()[:ring])
    key = jax.random.key(0)
    q = jax.random.normal(key, (batch, seq, heads, dim), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), q.shape, dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), q.shape, dtype)
    perm = zigzag_permutation(seq, ring)
    qz, kz, vz = q[:, perm], k[:, perm], v[:, perm]

    def loss(q, k, v):
        out = ring_attention(
            q, k, v, mesh, causal=True, chunk_k=chunk, positions=perm
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    step = jax.jit(jax.grad(loss, (0, 1, 2)))
    with mesh:
        host_fence(step(qz, kz, vz))  # compile + warmup
        t0 = time.time()
        for _ in range(steps):
            grads = step(qz, kz, vz)
        host_fence(grads)
        dt = time.time() - t0

    tokens_per_s = batch * seq * steps / dt
    print(
        json.dumps(
            {
                "metric": RING_METRIC,
                "value": round(tokens_per_s / ring, 1),
                "unit": (
                    "tokens/s/chip (cpu smoke)"
                    if jax.default_backend() == "cpu"
                    else "tokens/s/chip"
                ),
                "vs_baseline": None,
                "platform": jax.default_backend(),
                "seq": seq,
                "ring": ring,
                "heads": heads,
                "note": (
                    "fwd+bwd ring attention (zigzag causal layout), "
                    "K/V rotating the sep ring; no published reference "
                    "number (the reference has no context-parallel path)"
                ),
            }
        ),
        flush=True,
    )


def main():
    if "--child-ring" in sys.argv:
        _child_ring()
        return
    if "--child" in sys.argv:
        _child()
        return
    sys.exit(_parent())


if __name__ == "__main__":
    main()
