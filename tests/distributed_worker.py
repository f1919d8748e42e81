"""Worker process for the 2-process jax.distributed e2e test.

Not a pytest file (no test_ prefix): launched by tests/test_distributed.py
as `python distributed_worker.py <proc_id> <nproc> <port> <outdir>`.

This is the repo's analogue of the reference's multi-node TIPC evidence
(the reference's test_tipc N4C32 cases, SURVEY §4.1): the real
multi-host code paths — jax.distributed bootstrap (parallel/env.py),
cross-process collectives from a sharded train step, the process_allgather
branch of check_replica_consistency (parallel/check.py), and distributed
orbax save/load — exercised on a 2-process × 4-virtual-CPU-device cluster.
"""

import os
import sys


def main() -> None:
    proc_id, nproc, port, outdir = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    )
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.environ["PFX_COORDINATOR_ADDRESS"] = f"localhost:{port}"
    os.environ["PFX_NUM_PROCESSES"] = str(nproc)
    os.environ["PFX_PROCESS_ID"] = str(proc_id)

    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.check import check_replica_consistency
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    batch, seq = 8, 32
    cfg = AttrDict.from_nested(
        {
            "Global": {"global_batch_size": batch, "micro_batch_size": 2, "seed": 7},
            "Engine": {
                "max_steps": 2,
                "eval_freq": 0,
                "logging_freq": 10**9,
                "mix_precision": {"enable": False},
                "save_load": {"save_steps": 0, "output_dir": outdir},
            },
            "Model": {
                "module": "GPTModule",
                "vocab_size": 64,
                "hidden_size": 32,
                "num_layers": 2,
                "num_attention_heads": 4,
                "max_position_embeddings": seq,
                "hidden_dropout_prob": 0.0,
                "attention_probs_dropout_prob": 0.0,
                "dtype": "float32",
            },
            # data axis (2) spans the process boundary; model axis (2) and
            # fsdp axis (2) stay intra-process: grad psum + fsdp
            # all-gather/reduce-scatter cross hosts every step
            "Distributed": {
                "dp_degree": 2,
                "mp_degree": 2,
                "sharding": {"sharding_degree": 2, "sharding_stage": 3,
                             "min_shard_size": 0},
            },
            "Optimizer": {
                "name": "FusedAdamW",
                "weight_decay": 0.01,
                "lr": {"name": "Constant", "learning_rate": 1e-3},
                "grad_clip": {"name": "ClipGradByGlobalNorm", "clip_norm": 1.0},
            },
        }
    )
    cfg = process_configs(cfg, num_devices=8)
    mesh = init_dist_env(cfg)
    assert jax.process_count() == nproc, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    module = build_module(cfg)

    # identical host batch on every process (global arrays are laid out by
    # sharding; each process transfers its addressable shards)
    rng = np.random.default_rng(0)
    host_batch = {
        "tokens": rng.integers(0, 64, (batch, seq)).astype(np.int64),
        "labels": rng.integers(0, 64, (batch, seq)).astype(np.int64),
        "loss_mask": np.ones((batch, seq), np.float32),
        "position_ids": np.tile(np.arange(seq), (batch, 1)),
    }

    with mesh:
        engine = Engine(cfg, module, mesh)
        dev = engine._put_batch(host_batch)
        losses = []
        for _ in range(2):
            engine.state, m = engine.train_step(engine.state, dev)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(x) for x in losses), losses

        # the process_allgather branch (parallel/check.py:96-105): every
        # process must fingerprint the sharded params identically
        fp = check_replica_consistency(engine.state.params)
        print(f"worker {proc_id}: losses {losses} fp {fp:#010x}", flush=True)

        # a deliberately host-divergent tree must be detected on EVERY rank
        import jax.numpy as jnp

        diverged = {"x": jnp.full((8,), float(proc_id))}
        try:
            check_replica_consistency(diverged, name="diverged")
        except RuntimeError:
            print(f"worker {proc_id}: divergence detected OK", flush=True)
        else:
            raise AssertionError("host-divergent tree passed the check")

        # distributed checkpoint: all processes save their shards; only
        # process 0 writes the completeness marker
        path = engine.save()
        engine.wait_for_save()
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("ckpt_written")
        assert os.path.exists(os.path.join(path, "meta.json"))

        # load back and verify the restored tree fingerprints identically
        engine.load(path)
        fp2 = check_replica_consistency(engine.state.params, name="restored")
        assert fp2 == fp, (hex(fp2), hex(fp))

    # ---- phase 2: ring attention + zigzag with the sep axis SPANNING the
    # process boundary (sep8 over 2x4 devices: K/V ppermute hops cross
    # hosts every ring step — the multi-host long-context path)
    cfg2 = AttrDict.from_nested(
        {
            "Global": {"global_batch_size": 2, "micro_batch_size": 2, "seed": 7},
            "Engine": {
                "max_steps": 1,
                "eval_freq": 0,
                "logging_freq": 10**9,
                "mix_precision": {"enable": False},
                "save_load": {"save_steps": 0},
            },
            "Model": {
                "module": "GPTModule",
                "vocab_size": 64,
                "hidden_size": 32,
                "num_layers": 2,
                "num_attention_heads": 8,
                "max_position_embeddings": 64,
                "hidden_dropout_prob": 0.0,
                "attention_probs_dropout_prob": 0.0,
                "attn_impl": "ring",
                "dtype": "float32",
            },
            "Distributed": {"dp_degree": 1, "sep_degree": 8, "sep_zigzag": True},
            "Optimizer": {
                "name": "FusedAdamW",
                "lr": {"name": "Constant", "learning_rate": 1e-4},
            },
        }
    )
    cfg2 = process_configs(cfg2, num_devices=8)
    mesh2 = init_dist_env(cfg2)
    module2 = build_module(cfg2)
    batch2 = {
        "tokens": rng.integers(0, 64, (2, 64)).astype(np.int64),
        "labels": rng.integers(0, 64, (2, 64)).astype(np.int64),
        "loss_mask": np.ones((2, 64), np.float32),
        "position_ids": np.tile(np.arange(64), (2, 1)),
    }
    with mesh2:
        engine2 = Engine(cfg2, module2, mesh2)
        dev2 = engine2._put_batch(batch2)
        engine2.state, m2 = engine2.train_step(engine2.state, dev2)
        loss2 = float(m2["loss"])
        assert np.isfinite(loss2), loss2
        fp3 = check_replica_consistency(engine2.state.params, name="ring_zz")
    print(f"worker {proc_id}: ring_zz loss {loss2:.5f} fp {fp3:#010x}", flush=True)

    print(f"DIST_WORKER_OK {proc_id}", flush=True)


if __name__ == "__main__":
    main()
