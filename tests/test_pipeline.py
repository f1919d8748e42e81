"""Pipeline-parallel tests: stage schedule output/grads match plain scan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
from paddlefleetx_tpu.parallel.pipeline import PipelineConfig
from paddlefleetx_tpu.parallel.sharding import make_rules, tree_logical_to_sharding

TINY = GPTConfig(
    vocab_size=128,
    hidden_size=64,
    num_layers=4,
    num_attention_heads=8,
    max_position_embeddings=32,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype="float32",
)


def _ctx(devices, pp, extra=None, microbatches=None):
    mesh = build_mesh(
        MeshConfig(pp_degree=pp, **(extra or {"dp_degree": 8 // pp})), devices
    )
    rules = make_rules()
    ctx = gpt.ShardingCtx(
        mesh,
        rules,
        pipeline=PipelineConfig(num_stages=pp, num_microbatches=microbatches or pp),
    )
    return mesh, rules, ctx


@pytest.mark.parametrize("pp,extra", [
    (2, {"dp_degree": 4}),
    (4, {"dp_degree": 2}),
    (2, {"mp_degree": 2, "dp_degree": 2}),
])
def test_pipeline_loss_matches_scan(devices8, pp, extra):
    params = gpt.init(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, TINY.vocab_size)
    batch = {
        "tokens": tokens,
        "labels": jnp.roll(tokens, -1, 1),
        "loss_mask": jnp.ones((8, 16), jnp.float32),
    }
    ref = float(gpt.loss_fn(params, batch, TINY, train=False))

    mesh, rules, ctx = _ctx(devices8, pp, extra)
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(TINY), mesh, rules)
    p_sharded = jax.device_put(params, shardings)

    @jax.jit
    def f(p, b):
        return gpt.loss_fn(p, b, TINY, ctx=ctx, train=False)

    with mesh:
        got = float(f(p_sharded, batch))
    np.testing.assert_allclose(got, ref, rtol=2e-5)


def test_pipeline_grads_match_scan(devices8):
    params = gpt.init(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, TINY.vocab_size)
    batch = {
        "tokens": tokens,
        "labels": jnp.roll(tokens, -1, 1),
        "loss_mask": jnp.ones((8, 16), jnp.float32),
    }
    g_ref = jax.grad(lambda p: gpt.loss_fn(p, batch, TINY, train=False))(params)

    mesh, rules, ctx = _ctx(devices8, 2, {"dp_degree": 4})
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(TINY), mesh, rules)
    p_sharded = jax.device_put(params, shardings)

    with mesh:
        g = jax.jit(jax.grad(lambda p, b: gpt.loss_fn(p, b, TINY, ctx=ctx, train=False)))(
            p_sharded, batch
        )
    flat_ref = jax.tree.leaves(g_ref)
    flat = jax.tree.leaves(g)
    for a, b in zip(flat_ref, flat):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=5e-4, atol=1e-5)


def test_pipeline_more_microbatches(devices8):
    """M > S exercises the fill/steady/drain phases properly."""
    params = gpt.init(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, TINY.vocab_size)
    batch = {
        "tokens": tokens,
        "labels": jnp.roll(tokens, -1, 1),
        "loss_mask": jnp.ones((8, 16), jnp.float32),
    }
    ref = float(gpt.loss_fn(params, batch, TINY, train=False))
    mesh, rules, ctx = _ctx(devices8, 2, {"dp_degree": 4}, microbatches=4)
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(TINY), mesh, rules)
    with mesh:
        got = float(
            jax.jit(lambda p, b: gpt.loss_fn(p, b, TINY, ctx=ctx, train=False))(
                jax.device_put(params, shardings), batch
            )
        )
    np.testing.assert_allclose(got, ref, rtol=2e-5)


@pytest.mark.parametrize("pp,extra,mb,vpp", [
    (2, {"dp_degree": 4}, 2, 1),
    (2, {"dp_degree": 4}, 4, 1),          # M > S: steady-state 1F1B
    (4, {"dp_degree": 2}, 4, 1),
    (2, {"mp_degree": 2, "dp_degree": 2}, 2, 1),   # TP inside stages
    (2, {"dp_degree": 4}, 4, 2),          # interleaved virtual stages
])
def test_pipeline_1f1b_train_loss_and_grads(devices8, pp, extra, mb, vpp):
    """Training path: 1F1B schedule (grads computed inside the forward
    schedule via custom_vjp) matches single-device loss AND grads."""
    params = gpt.init(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, TINY.vocab_size)
    batch = {
        "tokens": tokens,
        "labels": jnp.roll(tokens, -1, 1),
        "loss_mask": jnp.ones((8, 16), jnp.float32),
    }
    ref_loss, g_ref = jax.value_and_grad(
        lambda p: gpt.loss_fn(p, batch, TINY, train=True)
    )(params)

    mesh, rules, ctx = _ctx(devices8, pp, extra, microbatches=mb)
    ctx = gpt.ShardingCtx(
        mesh, rules, pipeline=PipelineConfig(pp, mb, num_virtual_stages=vpp)
    )
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(TINY), mesh, rules)
    p_sharded = jax.device_put(params, shardings)
    with mesh:
        loss, g = jax.jit(
            jax.value_and_grad(
                lambda p, b: gpt.loss_fn(p, b, TINY, ctx=ctx, train=True)
            )
        )(p_sharded, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=5e-4, atol=1e-5)


def test_pipeline_1f1b_bf16_params_grads(devices8):
    """bf16 params (multi_precision=False pairing): the 1F1B schedule must
    return bf16 cotangents matching the param dtype — the fp32 liveness
    mask and fp32 gbar scalar would otherwise promote the scan's grad
    carry and kill the compile (found by the 6.7B fit check, r5)."""
    import dataclasses

    cfg = dataclasses.replace(TINY, dtype="bfloat16")
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), gpt.init(TINY, jax.random.key(0))
    )
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    batch = {
        "tokens": tokens,
        "labels": jnp.roll(tokens, -1, 1),
        "loss_mask": jnp.ones((8, 16), jnp.float32),
    }
    ref_loss = gpt.loss_fn(params, batch, cfg, train=True)

    mesh, rules, ctx = _ctx(devices8, 2, {"dp_degree": 4}, microbatches=2)
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(cfg), mesh, rules)
    p_sharded = jax.device_put(params, shardings)
    with mesh:
        loss, g = jax.jit(
            jax.value_and_grad(
                lambda p, b: gpt.loss_fn(p, b, cfg, ctx=ctx, train=True)
            )
        )(p_sharded, batch)
    # bf16 fwd: schedules agree to bf16 tolerance
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-2)
    for leaf in jax.tree.leaves(g):
        assert leaf.dtype == jnp.bfloat16
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


def test_pipeline_1f1b_masked_loss(devices8):
    """Partial loss_mask: the in-schedule numerator / global denominator
    decomposition must reproduce the global masked mean."""
    params = gpt.init(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, TINY.vocab_size)
    mask = (jax.random.uniform(jax.random.key(3), (8, 16)) > 0.4).astype(jnp.float32)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1), "loss_mask": mask}
    ref = float(gpt.loss_fn(params, batch, TINY, train=True))
    mesh, rules, ctx = _ctx(devices8, 2, {"dp_degree": 4}, microbatches=4)
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(TINY), mesh, rules)
    with mesh:
        got = float(
            jax.jit(lambda p, b: gpt.loss_fn(p, b, TINY, ctx=ctx, train=True))(
                jax.device_put(params, shardings), batch
            )
        )
    np.testing.assert_allclose(got, ref, rtol=2e-5)


def test_indivisible_layers_raises(devices8):
    cfg = GPTConfig(**{**TINY.__dict__, "num_layers": 3})
    params = gpt.init(cfg, jax.random.key(0))
    mesh, rules, ctx = _ctx(devices8, 2, {"dp_degree": 4})
    batch = {
        "tokens": jnp.zeros((8, 16), jnp.int32),
        "labels": jnp.zeros((8, 16), jnp.int32),
    }
    with pytest.raises(ValueError, match="not divisible"):
        with mesh:
            gpt.loss_fn(params, batch, cfg, ctx=ctx, train=False)


def test_1f1b_dead_events_cannot_poison_grads_at_depth(devices8):
    """Dead schedule events pull a real cotangent back through a stage fed
    all-zero activations.  LayerNorm's backward at zero variance scales by
    rsqrt(eps) ~ 316 per layer; a dozen layers deep that overflows, and an
    accumulator that MULTIPLIES the dead event out turns inf into NaN
    (0 * inf) while the loss stays exact.  Found on the chip at GPT-345M
    dp2·pp2 — NaN grads in the first four layers of the second stage — and
    reproduced here by depth (12 layers per stage) plus a hot init; the
    accumulators now select (``pipeline._masked``)."""
    cfg = GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=24, num_attention_heads=4,
        max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, dtype="bfloat16", attn_impl="xla",
        initializer_range=0.1,
    )
    params = gpt.init(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1),
             "loss_mask": jnp.ones((8, 32), jnp.float32)}
    mesh, _, ctx = _ctx(devices8, pp=2, microbatches=2)
    with mesh:
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: gpt.loss_fn(p, batch, cfg, ctx=ctx, train=True)))(params)
    assert np.isfinite(float(loss))
    bad = [jax.tree_util.keystr(k)
           for k, g in jax.tree_util.tree_leaves_with_path(grads)
           if not bool(jnp.isfinite(g).all())]
    assert not bad, bad
