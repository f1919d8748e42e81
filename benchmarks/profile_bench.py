"""Capture a jax.profiler trace at the bench.py shape and print the top
time sinks (the MFU-push workflow: VERDICT r2 item 3).

Runs the same GPT-345M config as bench.py (same env knobs), traces a
window of steady-state steps, then emits the ProfilerHook summary views
(summary_ops.txt ranked by self time + hlo_stats.json + memory summary)
into --log_dir and prints the top table to stdout.

  python benchmarks/profile_bench.py [--log_dir ./profiler_log] [--steps 8]
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--log_dir", default="./profiler_log")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)

    from paddlefleetx_tpu.utils.device import apply_platform_env

    apply_platform_env()

    import jax
    import numpy as np

    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs
    from paddlefleetx_tpu.utils.profiler import ProfilerHook


    n_dev = jax.device_count()
    batch = int(os.environ.get("BENCH_BATCH", 16)) * n_dev
    seq = int(os.environ.get("BENCH_SEQ", 1024))

    cfg = AttrDict.from_nested(
        {
            "Global": {
                "global_batch_size": batch,
                "micro_batch_size": batch // n_dev,
                "seed": 1024,
                "prng_impl": os.environ.get("BENCH_PRNG", "rbg"),
            },
            "Engine": {
                "max_steps": args.steps + 4,
                "eval_freq": 0,
                "logging_freq": 10**9,
                "mix_precision": {"enable": True, "dtype": "bfloat16"},
                "save_load": {"save_steps": 0},
            },
            "Model": {
                "module": "GPTModule",
                "vocab_size": 50304,
                "hidden_size": int(os.environ.get("BENCH_HIDDEN", 1024)),
                "num_layers": int(os.environ.get("BENCH_LAYERS", 24)),
                "num_attention_heads": 16,
                "max_position_embeddings": seq,
                "hidden_dropout_prob": float(os.environ.get("BENCH_DROPOUT", 0.1)),
                "attention_probs_dropout_prob": float(os.environ.get("BENCH_DROPOUT", 0.1)),
                "attn_impl": os.environ.get("BENCH_ATTN", "flash"),
                "use_recompute": os.environ.get("BENCH_RECOMPUTE", "1") == "1",
                "recompute_granularity": os.environ.get("BENCH_REMAT", "selective"),
                "use_fused_ln": os.environ.get("BENCH_FUSED_LN", "1") == "1",
                "use_chunked_ce": os.environ.get("BENCH_CHUNKED_CE", "0") == "1",
                "scan_unroll": int(os.environ.get("BENCH_SCAN_UNROLL", 1)),
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
    host_batch = {
        "tokens": rng.integers(0, 50304, (batch, seq)).astype(np.int64),
        "labels": rng.integers(0, 50304, (batch, seq)).astype(np.int64),
        "loss_mask": np.ones((batch, seq), np.float32),
        "position_ids": np.tile(np.arange(seq), (batch, 1)),
    }

    hook = ProfilerHook(
        {
            "enable": True,
            # warmup 3 compile+steady steps before the window
            "scheduler": [4, 4 + args.steps],
            "log_dir": args.log_dir,
            "summary_top": args.top,
        }
    )
    with mesh:
        engine = Engine(cfg, module, mesh)
        dev_batch = engine._put_batch(host_batch)
        for step in range(1, 5 + args.steps):
            engine.state, m = engine.train_step(engine.state, dev_batch)
            float(m["loss"])  # keep each step synchronous inside the trace
            hook.step(step)
    hook.close()
    print(open(os.path.join(os.path.abspath(args.log_dir), "summary_ops.txt")).read())


if __name__ == "__main__":
    main()
