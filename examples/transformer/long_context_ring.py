"""Long-context training with ring attention + the zigzag balanced layout.

The long-context recipe end to end, via the Engine (the reference has no
context-parallel path — SURVEY §5.7; this is the TPU-native answer):

  - ``Distributed.sep_degree``: the sequence stays sharded over the `sep`
    mesh axis; K/V shards rotate the ring (`parallel/ring_attention.py`),
    so per-device memory is O(s/P) and no device ever holds the full
    sequence.
  - ``Distributed.sep_zigzag``: sequences are fed in the zigzag block
    order so causal masking wastes the same work on every ring device
    (contiguous shards leave the first device almost fully masked).
  - ``Model.ring_chunk_k``: bounds each ring step's score buffer to
    [s_local, chunk_k] via an inner rematerialized scan — the
    flash-attention memory trade in plain XLA.

Run (virtual 8-device CPU mesh; on TPU drop the env vars):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PFX_PLATFORM=cpu \
    python examples/transformer/long_context_ring.py [--seq 4096] [--steps 2]
"""

import argparse
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

from paddlefleetx_tpu.utils.device import apply_platform_env

apply_platform_env()  # tpu unless a CPU pin is set; before backend init


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    args = ap.parse_args(argv)

    import numpy as np

    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs
    from paddlefleetx_tpu.utils.log import logger

    import jax

    n_dev = jax.device_count()
    sep = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
    dp = n_dev // sep
    batch = dp

    cfg = AttrDict.from_nested(
        {
            "Global": {"global_batch_size": batch, "micro_batch_size": 1, "seed": 7},
            "Engine": {
                "max_steps": args.steps,
                "eval_freq": 0,
                "logging_freq": 1,
                "mix_precision": {"enable": False},
                "save_load": {"save_steps": 0},
            },
            "Model": {
                "module": "GPTModule",
                "vocab_size": 256,
                "hidden_size": args.hidden,
                "num_layers": args.layers,
                "num_attention_heads": 8,
                "max_position_embeddings": args.seq,
                "hidden_dropout_prob": 0.0,
                "attention_probs_dropout_prob": 0.0,
                "attn_impl": "ring",
                "ring_chunk_k": 512,
                "use_recompute": True,
                "recompute_granularity": "full",
                "dtype": "float32",
            },
            "Distributed": {"dp_degree": dp, "sep_degree": sep, "sep_zigzag": True},
            "Optimizer": {
                "name": "FusedAdamW",
                "weight_decay": 0.01,
                "lr": {"name": "Constant", "learning_rate": 1e-4},
                "grad_clip": {"name": "ClipGradByGlobalNorm", "clip_norm": 1.0},
            },
        }
    )
    cfg = process_configs(cfg, num_devices=n_dev)
    mesh = init_dist_env(cfg)
    module = build_module(cfg)

    rng = np.random.default_rng(0)
    s = args.seq

    def loader():
        while True:
            toks = rng.integers(0, 256, (batch, s)).astype(np.int64)
            yield {
                "tokens": toks,
                "labels": np.roll(toks, -1, 1),
                "loss_mask": np.ones((batch, s), np.float32),
                "position_ids": np.tile(np.arange(s), (batch, 1)),
            }

    with mesh:
        engine = Engine(cfg, module, mesh)
        state = engine.fit(loader())
    logger.info(
        f"long-context ring+zigzag: seq {s} over sep={sep} "
        f"(s_local {s // sep}), {args.steps} steps done; final step "
        f"{int(state.step)}"
    )


if __name__ == "__main__":
    main()
