"""Ulysses (sep-axis alltoall) + ring attention parity tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.ops.attention import xla_attention
from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
from paddlefleetx_tpu.parallel.ring_attention import ring_attention
from paddlefleetx_tpu.parallel.sharding import make_rules, tree_logical_to_sharding

# whole file runs in ~17s warm on a 1-core CPU mesh: context parallelism
# belongs in the default safety net (was blanket-marked slow until round 4)

TINY = GPTConfig(
    vocab_size=128,
    hidden_size=64,
    num_layers=2,
    num_attention_heads=8,
    max_position_embeddings=64,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype="float32",
)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_xla(devices8, causal):
    mesh = build_mesh(MeshConfig(sep_degree=4, dp_degree=2), devices8)
    b, s, n, d = 2, 64, 4, 16
    key = jax.random.key(0)
    q = jax.random.normal(key, (b, s, n, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, n, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, n, d), jnp.float32)
    ref = xla_attention(q, k, v, causal=causal)
    with mesh:
        got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_grads_match(devices8):
    mesh = build_mesh(MeshConfig(sep_degree=4, dp_degree=2), devices8)
    b, s, n, d = 1, 32, 2, 16
    key = jax.random.key(1)
    q = jax.random.normal(key, (b, s, n, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, n, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, n, d))
    ct = jax.random.normal(jax.random.fold_in(key, 3), (b, s, n, d))

    g_ref = jax.grad(lambda q, k, v: jnp.sum(xla_attention(q, k, v, causal=True) * ct), (0, 1, 2))(q, k, v)
    with mesh:
        g = jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(ring_attention(q, k, v, mesh, causal=True) * ct),
                (0, 1, 2),
            )
        )(q, k, v)
    for a, b_ in zip(g_ref, g):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a), rtol=5e-4, atol=5e-4)


def test_ulysses_layout_loss_parity(devices8):
    """sep-sharded (Ulysses) model loss == single-device loss."""
    params = gpt.init(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, TINY.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    ref = float(gpt.loss_fn(params, batch, TINY, train=False))

    mesh = build_mesh(MeshConfig(sep_degree=4, dp_degree=2), devices8)
    rules = make_rules()
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(TINY), mesh, rules)
    ctx = gpt.ShardingCtx(mesh, rules)
    with mesh:
        got = float(
            jax.jit(lambda p, b: gpt.loss_fn(p, b, TINY, ctx=ctx, train=False))(
                jax.device_put(params, shardings), batch
            )
        )
    np.testing.assert_allclose(got, ref, rtol=2e-5)


def test_ring_model_loss_parity(devices8):
    """attn_impl='ring' over sep mesh == single-device xla attention model."""
    cfg_ring = GPTConfig(**{**TINY.__dict__, "attn_impl": "ring"})
    params = gpt.init(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, TINY.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    ref = float(gpt.loss_fn(params, batch, TINY, train=False))

    mesh = build_mesh(MeshConfig(sep_degree=4, dp_degree=2), devices8)
    rules = make_rules()
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(TINY), mesh, rules)
    ctx = gpt.ShardingCtx(mesh, rules)
    with mesh:
        got = float(
            jax.jit(lambda p, b: gpt.loss_fn(p, b, cfg_ring, ctx=ctx, train=False))(
                jax.device_put(params, shardings), batch
            )
        )
    np.testing.assert_allclose(got, ref, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_chunked_parity(devices8, causal):
    """chunk_k bounds the per-ring-step score buffer; values and grads
    must match the unchunked ring exactly (same online-softmax math)."""
    mesh = build_mesh(MeshConfig(sep_degree=2, dp_degree=4), devices8)
    b, s, n, d = 1, 64, 2, 8  # s_local = 32, chunked into 4 x 8
    key = jax.random.key(7)
    q = jax.random.normal(key, (b, s, n, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, n, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, n, d), jnp.float32)
    ct = jax.random.normal(jax.random.fold_in(key, 3), (b, s, n, d), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * ct)

    with mesh:
        ref_fn = lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal, chunk_k=None)
        got_fn = lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal, chunk_k=8)
        ref = jax.jit(ref_fn)(q, k, v)
        got = jax.jit(got_fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
        g_ref = jax.jit(jax.grad(loss(ref_fn), (0, 1, 2)))(q, k, v)
        g_got = jax.jit(jax.grad(loss(got_fn), (0, 1, 2)))(q, k, v)
    for a, b_ in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a), rtol=1e-4, atol=1e-4)
    # non-dividing / too-small chunks silently fall back to unchunked
    with mesh:
        fb = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal, chunk_k=7))(q, k, v)
    np.testing.assert_allclose(np.asarray(fb), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ring_attention_zigzag_positions_parity(devices8):
    """Permuted (zigzag) feeds with explicit positions produce exactly the
    contiguous result, just reordered: out_zz[:, inv] == out for both the
    values and the gradients."""
    from paddlefleetx_tpu.parallel.ring_attention import zigzag_permutation

    ring = 4
    mesh = build_mesh(MeshConfig(sep_degree=ring, dp_degree=2), devices8)
    b, s, n, d = 1, 64, 2, 8
    key = jax.random.key(3)
    q = jax.random.normal(key, (b, s, n, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, n, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, n, d), jnp.float32)

    perm = np.asarray(zigzag_permutation(s, ring))
    inv = np.argsort(perm)
    with mesh:
        ref = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, causal=True))(q, k, v)
        zz = jax.jit(
            lambda q, k, v: ring_attention(
                q, k, v, mesh, causal=True, positions=jnp.asarray(perm)
            )
        )(q[:, perm], k[:, perm], v[:, perm])
    np.testing.assert_allclose(
        np.asarray(zz)[:, inv], np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_zigzag_permutation_structure():
    from paddlefleetx_tpu.parallel.ring_attention import zigzag_permutation

    perm = np.asarray(zigzag_permutation(16, 2))
    # device 0 shard = blocks 0 and 3; device 1 shard = blocks 1 and 2
    np.testing.assert_array_equal(perm[:8], [0, 1, 2, 3, 12, 13, 14, 15])
    np.testing.assert_array_equal(perm[8:], [4, 5, 6, 7, 8, 9, 10, 11])
    assert sorted(perm.tolist()) == list(range(16))
    import pytest as _pytest

    with _pytest.raises(ValueError, match="divisible by"):
        zigzag_permutation(10, 4)


@pytest.mark.slow  # ~9s (two engine boots); tier-1 budget funding for
# the shard_map-port tests.  Replacement coverage: the engine's zigzag
# install + ring positions-masking stays tier-1 via the STRICTLY HARDER
# pp2 x sep2 composition (test_engine_zigzag_pp_loss_parity, which also
# asserts the non-parity negative control) and the ring zigzag-positions
# parity test above; still in make test-parallel / test-mid / test-all.
def test_engine_zigzag_loss_parity(devices8, tmp_path):
    """Distributed.sep_zigzag: the engine permutes the batch, ring masks by
    true positions, and the loss matches the contiguous sep layout."""
    import os

    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    def run(zigzag):
        cfg = AttrDict.from_nested(
            {
                "Global": {"global_batch_size": 4, "micro_batch_size": 1, "seed": 7},
                "Engine": {
                    "max_steps": 1, "eval_freq": 0, "logging_freq": 10**9,
                    "mix_precision": {"enable": False},
                    "save_load": {"save_steps": 0},
                },
                "Model": {
                    "module": "GPTModule",
                    "vocab_size": 64, "hidden_size": 32, "num_layers": 2,
                    "num_attention_heads": 4, "max_position_embeddings": 32,
                    "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
                    "attn_impl": "ring", "dtype": "float32",
                },
                "Distributed": {"dp_degree": 4, "sep_degree": 2,
                                "sep_zigzag": zigzag},
                "Optimizer": {"name": "FusedAdamW",
                              "lr": {"name": "Constant", "learning_rate": 1e-4}},
            }
        )
        cfg = process_configs(cfg, num_devices=8)
        mesh = init_dist_env(cfg, devices=jax.devices()[:8])
        module = build_module(cfg)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": rng.integers(0, 64, (4, 32)).astype(np.int64),
            "labels": rng.integers(0, 64, (4, 32)).astype(np.int64),
            "loss_mask": np.ones((4, 32), np.float32),
            "position_ids": np.tile(np.arange(32), (4, 1)),
        }
        with mesh:
            eng = Engine(cfg, module, mesh)
            dev = eng._put_batch(batch)
            eng.state, m = eng.train_step(eng.state, dev)
            return float(m["loss"])

    ref = run(False)
    zz = run(True)
    # permuted accumulation order shifts fp32 sums by a few ulps
    np.testing.assert_allclose(zz, ref, rtol=2e-4)


def test_engine_zigzag_pp_loss_parity():
    """sep_zigzag composes with pipeline parallelism: ctx.attn_positions
    rides into the 1F1B chunk fns as a stage-replicated constant and ring
    attention nests its sep shard_map inside the stages-manual map.  The
    175B-class layout (VERDICT r3 item 6): pp2 x sep2 x dp2, interleaved
    virtual stages.

    Subprocess-isolated (tests/zigzag_pp_worker.py): the nested
    (stages-manual over sep) shard_map executable is fragile in a
    long-lived CPU test process -- it fails the persistent-cache
    serialization round-trip AND has aborted in XLA CPU runtime deep into
    a full-suite process even cache-disabled (test-std, 2026-07-30); a
    fresh process runs it reliably."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = ""
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tests", "zigzag_pp_worker.py")],
        capture_output=True, text=True, cwd=repo, env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    losses = json.loads(out.stdout.strip().splitlines()[-1])
    ref, zz, bad = losses["ref"], losses["zz"], losses["bad"]
    # correct positions: parity up to permuted-reduction rounding
    np.testing.assert_allclose(zz, ref, atol=2e-5, rtol=0)
    # wrong (storage-order) masking must NOT be parity -- guards against
    # the positions constant silently dropping out of the pipeline path
    assert abs(bad - ref) > 2e-5, (bad, ref)


def test_pipeline_sep_ring_1f1b_grads_match(devices8):
    """1F1B pipeline COMPOSED with nested ring attention (pp2 x sep2 x
    dp2): loss AND per-parameter grads match the single-device reference.

    A nested map can get the LOSS exactly right while its parameter grads
    are sep-rank-varying (a seam that counts a block's cotangent twice, or
    not at all), so a loss-only assertion (zigzag_pp_worker's) is not
    enough — this test must assert GRADS, not just loss."""
    from paddlefleetx_tpu.parallel.pipeline import PipelineConfig

    # 2 layers = 1 per stage: the smallest shape that runs both stages'
    # chunk bodies through the nested ring (the bug reproduced identically
    # at any depth; 4 layers only added compile time to tier-1)
    cfg = GPTConfig(**{**TINY.__dict__, "attn_impl": "ring"})
    params = gpt.init(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)
    batch = {
        "tokens": tokens,
        "labels": jnp.roll(tokens, -1, 1),
        "loss_mask": jnp.ones((8, 32), jnp.float32),
    }
    ref_loss, g_ref = jax.value_and_grad(
        lambda p: gpt.loss_fn(p, batch, cfg, train=True)
    )(params)

    mesh = build_mesh(
        MeshConfig(dp_degree=2, pp_degree=2, sep_degree=2), devices8
    )
    rules = make_rules()
    ctx = gpt.ShardingCtx(mesh, rules, pipeline=PipelineConfig(2, 2))
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(cfg), mesh, rules)
    with mesh:
        loss, g = jax.jit(
            jax.value_and_grad(
                lambda p, b: gpt.loss_fn(p, b, cfg, ctx=ctx, train=True)
            )
        )(jax.device_put(params, shardings), batch)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    for a, b_ in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g)):
        np.testing.assert_allclose(
            np.asarray(b_), np.asarray(a), rtol=5e-4, atol=1e-5
        )
