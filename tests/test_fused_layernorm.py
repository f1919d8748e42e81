"""Fused LayerNorm kernel: forward/grad parity vs naive XLA."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt.model import layer_norm
from paddlefleetx_tpu.ops.fused_layernorm import fused_layer_norm


def _naive(x, scale, bias, residual=None, eps=1e-5):
    if residual is not None:
        x = x + residual
    return layer_norm(x, scale, bias, eps)


@pytest.mark.parametrize("shape", [(4, 16, 64), (2, 128)])
@pytest.mark.parametrize("with_res", [False, True])
def test_fused_ln_forward_parity(shape, with_res):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    res = jnp.asarray(rng.normal(size=shape), jnp.float32) if with_res else None
    scale = jnp.asarray(rng.normal(size=shape[-1:]), jnp.float32)
    bias = jnp.asarray(rng.normal(size=shape[-1:]), jnp.float32)
    got = fused_layer_norm(x, scale, bias, residual=res)
    want = _naive(x, scale, bias, residual=res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("with_res", [False, True])
def test_fused_ln_grad_parity(with_res):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 8, 32)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(4, 8, 32)), jnp.float32) if with_res else None
    scale = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(32,)), jnp.float32)

    def loss_fused(x, scale, bias, res):
        return jnp.sum(jnp.sin(fused_layer_norm(x, scale, bias, residual=res)))

    def loss_naive(x, scale, bias, res):
        return jnp.sum(jnp.sin(_naive(x, scale, bias, residual=res)))

    argnums = (0, 1, 2) if res is None else (0, 1, 2, 3)
    gf = jax.grad(loss_fused, argnums)(x, scale, bias, res)
    gn = jax.grad(loss_naive, argnums)(x, scale, bias, res)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_fused_ln_bf16():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.bfloat16)
    scale = jnp.ones((64,), jnp.float32)
    bias = jnp.zeros((64,), jnp.float32)
    out = fused_layer_norm(x, scale, bias)
    assert out.dtype == jnp.bfloat16
    want = _naive(x.astype(jnp.float32), scale, bias)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), rtol=2e-2, atol=2e-2
    )
