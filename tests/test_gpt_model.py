"""GPT model unit tests: shapes, init-loss sanity, determinism, recompute,
and TP/SP/FSDP layout parity on the 8-device CPU mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt.config import GPTConfig, preset
from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
from paddlefleetx_tpu.parallel.sharding import make_rules, tree_logical_to_sharding

TINY = GPTConfig(
    vocab_size=128,
    hidden_size=64,
    num_layers=2,
    num_attention_heads=8,
    max_position_embeddings=64,
    dtype="float32",
)


def _batch(key, cfg, b=2, s=16):
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    return {
        "tokens": tokens,
        "labels": jnp.roll(tokens, -1, axis=1),
        "loss_mask": jnp.ones((b, s), jnp.float32),
    }


def test_forward_shapes():
    params = gpt.init(TINY, jax.random.key(0))
    logits = gpt.forward(params, jnp.zeros((2, 16), jnp.int32), TINY)
    assert logits.shape == (2, 16, TINY.vocab_size)


def test_param_count_345m():
    cfg = preset("gpt-345M", vocab_size=51200)
    import paddlefleetx_tpu.models.common as common

    specs = gpt.gpt_specs(cfg)
    n = sum(np.prod(s.shape) for s in jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "shape")))
    # ~355M params for GPT-medium with vocab 51200
    assert 330e6 < n < 420e6


def test_init_loss_near_log_vocab():
    """Reference sanity anchor: step-0 loss ~ ln(vocab) (SURVEY §6: 10.99 for
    51200 ≈ ln(51200)=10.84 + init noise)."""
    params = gpt.init(TINY, jax.random.key(0))
    batch = _batch(jax.random.key(1), TINY)
    loss = gpt.loss_fn(params, batch, TINY, train=False)
    assert abs(float(loss) - np.log(TINY.vocab_size)) < 0.5


def test_dropout_determinism_and_train_eval():
    params = gpt.init(TINY, jax.random.key(0))
    batch = _batch(jax.random.key(1), TINY)
    k = jax.random.key(2)
    l1 = gpt.loss_fn(params, batch, TINY, dropout_key=k, train=True)
    l2 = gpt.loss_fn(params, batch, TINY, dropout_key=k, train=True)
    assert float(l1) == float(l2)
    l3 = gpt.loss_fn(params, batch, TINY, dropout_key=jax.random.key(3), train=True)
    assert float(l1) != float(l3)


@pytest.mark.parametrize("gran", ["full", "full_attn", "core_attn"])
def test_recompute_matches(gran):
    cfg_rc = GPTConfig(**{**TINY.__dict__, "use_recompute": True, "recompute_granularity": gran})
    params = gpt.init(TINY, jax.random.key(0))
    batch = _batch(jax.random.key(1), TINY)

    g0 = jax.grad(lambda p: gpt.loss_fn(p, batch, TINY, train=False))(params)
    g1 = jax.grad(lambda p: gpt.loss_fn(p, batch, cfg_rc, train=False))(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def _sharded_loss(devices, mesh_cfg, rules_kwargs, params, batch):
    mesh = build_mesh(mesh_cfg, devices)
    rules = make_rules(**rules_kwargs)
    logical = gpt.gpt_logical_axes(TINY)
    shardings = tree_logical_to_sharding(logical, mesh, rules)
    p_sharded = jax.device_put(params, shardings)
    ctx = gpt.ShardingCtx(mesh, rules)

    @jax.jit
    def f(p, b):
        return gpt.loss_fn(p, b, TINY, ctx=ctx, train=False)

    return float(f(p_sharded, batch))


def test_layout_parity(devices8):
    """Loss identical across parallel layouts (the reference's 'precision
    validation across layouts' guarantee, env.py:62-71)."""
    params = gpt.init(TINY, jax.random.key(0))
    batch = _batch(jax.random.key(1), TINY)
    ref = float(gpt.loss_fn(params, batch, TINY, train=False))

    layouts = [
        (MeshConfig(dp_degree=8), {}),
        (MeshConfig(mp_degree=8), {}),
        (MeshConfig(dp_degree=2, mp_degree=4), {}),
        (MeshConfig(mp_degree=4, dp_degree=2), {"sequence_parallel": True}),
        (MeshConfig(sharding_degree=4, mp_degree=2), {"fsdp_enabled": True}),
        (MeshConfig(dp_degree=2, sharding_degree=2, mp_degree=2), {"fsdp_enabled": True}),
    ]
    for mesh_cfg, rk in layouts:
        got = _sharded_loss(devices8, mesh_cfg, rk, params, batch)
        np.testing.assert_allclose(got, ref, rtol=2e-5, err_msg=f"{mesh_cfg} {rk}")


def test_grad_layout_parity(devices8):
    params = gpt.init(TINY, jax.random.key(0))
    batch = _batch(jax.random.key(1), TINY)
    g_ref = jax.grad(lambda p: gpt.loss_fn(p, batch, TINY, train=False))(params)

    mesh = build_mesh(MeshConfig(dp_degree=2, mp_degree=4), devices8)
    rules = make_rules()
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(TINY), mesh, rules)
    p_sharded = jax.device_put(params, shardings)
    ctx = gpt.ShardingCtx(mesh, rules)
    g = jax.jit(jax.grad(lambda p, b: gpt.loss_fn(p, b, TINY, ctx=ctx, train=False)))(
        p_sharded, batch
    )
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_selective_remat_parity():
    """'selective' remat (named save-set, default qkv+attn_out) never changes
    values — loss and grads match the no-remat graph exactly."""
    import dataclasses

    params = gpt.init(TINY, jax.random.key(0))
    batch = _batch(jax.random.key(1), TINY)
    sel = dataclasses.replace(TINY, use_recompute=True, recompute_granularity="selective")

    ref = jax.value_and_grad(lambda p: gpt.loss_fn(p, batch, TINY, train=False))(params)
    got = jax.jit(jax.value_and_grad(lambda p: gpt.loss_fn(p, batch, sel, train=False)))(
        params
    )
    np.testing.assert_allclose(float(got[0]), float(ref[0]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(ref[1]), jax.tree.leaves(got[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    # train=True with dropout: the recomputed mask in the backward pass must
    # match the forward mask (the 345M recipe runs exactly this),
    # for both threefry and rbg key impls
    drop = dataclasses.replace(
        TINY, hidden_dropout_prob=0.3, use_recompute=True, recompute_granularity="selective"
    )
    nore = dataclasses.replace(TINY, hidden_dropout_prob=0.3)
    for impl in (None, "rbg"):
        key = jax.random.key(42, impl=impl)
        ref = jax.value_and_grad(
            lambda p: gpt.loss_fn(p, batch, nore, dropout_key=key, train=True)
        )(params)
        got = jax.jit(
            jax.value_and_grad(
                lambda p: gpt.loss_fn(p, batch, drop, dropout_key=key, train=True)
            )
        )(params)
        np.testing.assert_allclose(float(got[0]), float(ref[0]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(ref[1]), jax.tree.leaves(got[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@functools.lru_cache(maxsize=None)
def _flash_step(granularity):
    """(forward-kernel calls in the jaxpr, loss, gradient leaves) of the toy
    model's differentiated loss on the flash path, dropout on as the 345M
    recipe runs it, under one recompute granularity (None: no recompute)."""
    import dataclasses
    import re

    cfg = dataclasses.replace(
        TINY, attn_impl="flash", hidden_dropout_prob=0.3,
        use_recompute=granularity is not None, recompute_granularity=granularity or "full")
    params = gpt.init(TINY, jax.random.key(0))
    batch = _batch(jax.random.key(1), TINY)
    key = jax.random.key(42)
    step = jax.value_and_grad(
        lambda p: gpt.loss_fn(p, batch, cfg, dropout_key=key, train=True))
    calls = len(re.findall(r"name=pfx_flash_fwd\b", str(jax.make_jaxpr(step)(params))))
    loss, grads = jax.jit(step)(params)
    return calls, np.asarray(loss), [np.asarray(g) for g in jax.tree.leaves(grads)]


@pytest.mark.parametrize("granularity, forward_calls", [
    pytest.param("selective", 1, id="selective"),
    pytest.param("full", 2, id="full"),
    pytest.param(None, 1, id="no_recompute"),
])
def test_flash_forward_runs_once_unless_recompute_is_full(granularity, forward_calls):
    """The flash kernel's output carries the name ``attn_out`` beside its
    ``attn_lse``, so under "selective" the differentiated loss calls
    ``pfx_flash_fwd`` ONCE (the backward reads the saved residual; before
    PR 49 it re-ran the whole kernel to get ``out`` back), under "full",
    which saves nothing, twice, and without recompute once.  The saved
    tensor is the one the re-run would have produced: loss and every
    gradient leaf are equal TO THE BIT across the three."""
    calls, loss, grads = _flash_step(granularity)
    assert calls == forward_calls
    _, other_loss, other_grads = _flash_step(None if granularity == "full" else "full")
    assert loss.tobytes() == other_loss.tobytes()
    assert len(grads) == len(other_grads)
    for a, b in zip(grads, other_grads):
        assert a.tobytes() == b.tobytes()
