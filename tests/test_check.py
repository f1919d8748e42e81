"""Replica-consistency fingerprint (parallel/check.py — the reference
`check` fused comm group analogue, comm_groups.py:64)."""

import jax
import jax.numpy as jnp
import numpy as np

from paddlefleetx_tpu.parallel.check import (
    check_replica_consistency,
    tree_fingerprint,
)


def _tree(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(8, 16)), dtype),
        "b": jnp.asarray(rng.normal(size=(16,)), dtype),
        "n": {"scale": jnp.ones((16,), dtype), "step": jnp.int32(3)},
    }


def test_fingerprint_deterministic_and_structural():
    a, b = _tree(0), _tree(0)
    assert int(tree_fingerprint(a)) == int(tree_fingerprint(b))
    assert int(tree_fingerprint(a)) != int(tree_fingerprint(_tree(1)))


def test_fingerprint_detects_one_ulp():
    a = _tree(0)
    fp = int(tree_fingerprint(a))
    # flip the lowest mantissa bit of ONE element
    w = np.asarray(a["w"]).copy()
    bits = w.view(np.uint32)
    bits[3, 7] ^= 1
    b = dict(a, w=jnp.asarray(bits.view(np.float32)))
    assert int(tree_fingerprint(b)) != fp


def test_fingerprint_detects_int_and_bf16_divergence():
    a = _tree(0, jnp.bfloat16)
    b = dict(a, b=a["b"].at[0].add(jnp.bfloat16(2**-7)))
    assert int(tree_fingerprint(a)) != int(tree_fingerprint(b))
    c = dict(a)
    c["n"] = dict(a["n"], step=jnp.int32(4))
    assert int(tree_fingerprint(a)) != int(tree_fingerprint(c))


def test_fingerprint_sharding_invariant(devices8):
    """The same values fingerprint identically replicated vs sharded (the
    reduction must not depend on layout)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh

    a = _tree(0)
    ref = int(tree_fingerprint(a))
    mesh = build_mesh(MeshConfig(dp_degree=8), devices8)
    sharded = dict(
        a,
        w=jax.device_put(a["w"], NamedSharding(mesh, P("data", None))),
        b=jax.device_put(a["b"], NamedSharding(mesh, P())),
    )
    with mesh:
        got = int(jax.jit(tree_fingerprint)(sharded))
    assert got == ref


def test_check_replica_consistency_single_process():
    fp = check_replica_consistency(_tree(0), name="t")
    assert isinstance(fp, int) and 0 <= fp < 2**32


def test_engine_runs_consistency_check(devices8, monkeypatch):
    """Engine.consistency_check_freq wires the check into the fit loop."""
    import paddlefleetx_tpu.parallel.check as check_mod
    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = AttrDict.from_nested(
        {
            "Global": {"global_batch_size": 8, "micro_batch_size": 1, "seed": 7},
            "Engine": {
                "max_steps": 2,
                "eval_freq": 0,
                "logging_freq": 10**9,
                "consistency_check_freq": 1,
                "mix_precision": {"enable": False},
                "save_load": {"save_steps": 0},
            },
            "Model": {
                "module": "GPTModule",
                "vocab_size": 64,
                "hidden_size": 32,
                "num_layers": 2,
                "num_attention_heads": 4,
                "max_position_embeddings": 16,
                "dtype": "float32",
            },
            "Distributed": {"dp_degree": 8},
            "Optimizer": {
                "name": "FusedAdamW",
                "lr": {"name": "Constant", "learning_rate": 1e-4},
            },
        }
    )
    cfg = process_configs(cfg, num_devices=8)
    mesh = init_dist_env(cfg, devices=devices8)
    module = build_module(cfg)

    rng = np.random.default_rng(0)

    def loader():
        while True:
            yield {
                "tokens": rng.integers(0, 64, (8, 16)).astype(np.int64),
                "labels": rng.integers(0, 64, (8, 16)).astype(np.int64),
                "loss_mask": np.ones((8, 16), np.float32),
                "position_ids": np.tile(np.arange(16), (8, 1)),
            }

    calls = []
    real = check_mod.check_replica_consistency
    monkeypatch.setattr(
        check_mod,
        "check_replica_consistency",
        lambda tree, **kw: calls.append(1) or real(tree, **kw),
    )
    with mesh:
        engine = Engine(cfg, module, mesh)
        engine._fit_loop(loader(), None, 16, 0.0, 0)
    assert len(calls) == 2  # freq=1 over 2 steps


def test_fingerprint_detects_transposition():
    """Swapping two values (same multiset of bit patterns — e.g. a
    misordered checkpoint restore) must change the fingerprint: the
    per-element index weight breaks sum commutativity."""
    a = _tree(0)
    w = np.asarray(a["w"]).copy()
    w[[0, 1]] = w[[1, 0]]
    assert not np.array_equal(w, np.asarray(a["w"]))
    b = dict(a, w=jnp.asarray(w))
    assert int(tree_fingerprint(a)) != int(tree_fingerprint(b))
