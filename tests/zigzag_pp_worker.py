"""Worker for test_engine_zigzag_pp_loss_parity (subprocess-isolated).

The pp2 x sep2 nested-shard_map executable is fragile inside a long-lived
CPU test process: with the persistent compilation cache it fails the
serialization round-trip (warm rerun SIGABRTs), and even cache-disabled it
has aborted in XLA CPU runtime after ~190 prior tests' worth of in-process
state (test-std, 2026-07-30).  A fresh process runs it reliably, so the
pytest wrapper execs this worker and parses the three losses.

Prints one JSON line: {"ref": float, "zz": float, "bad": float}.
"""

import json
import os
import sys


def main() -> None:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    # the nested-map executable fails the persistent-cache serialization
    # round-trip (a warm rerun SIGABRTs), so the cache stays off here
    jax.config.update("jax_enable_compilation_cache", False)

    import dataclasses

    import numpy as np
    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    def run(zigzag, sabotage=False):
        cfg = AttrDict.from_nested(
            {
                "Global": {"global_batch_size": 8, "micro_batch_size": 4, "seed": 7},
                "Engine": {
                    "max_steps": 1, "eval_freq": 0, "logging_freq": 10**9,
                    "mix_precision": {"enable": False},
                    "save_load": {"save_steps": 0},
                },
                "Model": {
                    "module": "GPTModule",
                    "vocab_size": 64, "hidden_size": 32, "num_layers": 4,
                    "num_attention_heads": 4, "max_position_embeddings": 32,
                    "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
                    "attn_impl": "ring", "dtype": "float32",
                },
                "Distributed": {
                    "dp_degree": 2, "pp_degree": 2, "sep_degree": 2,
                    "sep_zigzag": zigzag,
                    "pipeline": {"micro_batches": 2, "virtual_pp_degree": 2},
                },
                "Optimizer": {"name": "FusedAdamW",
                              "lr": {"name": "Constant", "learning_rate": 1e-4}},
            }
        )
        cfg = process_configs(cfg, num_devices=8)
        mesh = init_dist_env(cfg, devices=jax.devices()[:8])
        module = build_module(cfg)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": rng.integers(0, 64, (8, 32)).astype(np.int64),
            "labels": rng.integers(0, 64, (8, 32)).astype(np.int64),
            "loss_mask": np.ones((8, 32), np.float32),
            "position_ids": np.tile(np.arange(32), (8, 1)),
        }
        with mesh:
            eng = Engine(cfg, module, mesh)
            if zigzag:
                # eager install must have fired with a non-identity perm
                assert eng._zigzag_perm is not None
                assert not np.array_equal(eng._zigzag_perm, np.arange(32))
            if sabotage:
                # negative control: what a stale positions-less graph would
                # compute — causal mask by storage order on permuted data
                eng.ctx = dataclasses.replace(eng.ctx, attn_positions=None)
                eng._train_step = eng._build_train_step()
            dev = eng._put_batch(batch)
            eng.state, m = eng.train_step(eng.state, dev)
            return float(m["loss"])

    print(json.dumps({
        "ref": run(False),
        "zz": run(True),
        "bad": run(True, sabotage=True),
    }), flush=True)


if __name__ == "__main__":
    main()
