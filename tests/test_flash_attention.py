"""Flash-attention kernel vs XLA reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.ops.attention import xla_attention
from paddlefleetx_tpu.ops.flash_attention import flash_attention

# Pallas interpret-mode / big-compile file: excluded from the fast
# subset (pytest -m 'not slow'); run the full suite for release checks
pytestmark = pytest.mark.slow


@pytest.mark.parametrize("b,s,n,d", [(2, 256, 4, 64), (1, 512, 2, 32)])
def test_forward_matches_xla(b, s, n, d):
    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, n, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, n, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, n, d), jnp.float32)

    ref = xla_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_grads_match_xla():
    b, s, n, d = 1, 256, 2, 32
    key = jax.random.key(1)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, s, n, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, n, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, n, d), jnp.float32)
    ct = jax.random.normal(kg, (b, s, n, d), jnp.float32)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) * ct)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) * ct)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a), rtol=5e-4, atol=5e-4)


def test_causality():
    """Changing future tokens must not affect earlier outputs."""
    b, s, n, d = 1, 256, 2, 32
    key = jax.random.key(2)
    q = jax.random.normal(key, (b, s, n, d), jnp.float32)
    k, v = q + 1.0, q - 1.0
    out1 = flash_attention(q, k, v)
    k2 = k.at[:, -1].set(99.0)
    v2 = v.at[:, -1].set(99.0)
    out2 = flash_attention(q, k2, v2)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), rtol=1e-5)


def test_bf16_runs():
    b, s, n, d = 1, 256, 2, 64
    q = jnp.ones((b, s, n, d), jnp.bfloat16)
    out = flash_attention(q, q, q)
    assert out.dtype == jnp.bfloat16
    assert np.all(np.isfinite(np.asarray(out, np.float32)))


@pytest.mark.parametrize("d", [32, 128])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_fused_bwd_matches_split(dtype, tol, d):
    """The single-kernel backward (``bwd_mode="fused"``: dq, dk and dv from
    one kernel) must reproduce the split two-kernel backward up to f32
    accumulation order; in bfloat16, the dtype the model path runs, up to a
    rounding a tile (with fp32 inputs the kernel-internal downcasts in
    ``_bwd_tile`` are no-ops).  Every gradient comes back in its input's
    dtype: dq is summed in a float32 scratch and cast inside the kernel.

    Block 64 at seq 256 gives 4 kv blocks, so the fused kernel's core
    mechanism — the dq slab zeroed at kj==0, read-modify-written across
    kv-block grid steps and written out at the last — is actually
    exercised (a single-block grid would pass even with broken
    cross-block accumulation)."""
    from paddlefleetx_tpu.ops.flash_attention import _flash_bsnd

    b, s, n = 1, 256, 2
    kq, kk, kv, kg = jax.random.split(jax.random.key(4), 4)
    q, k, v, ct = (jax.random.normal(key, (b, s, n, d), dtype) for key in (kq, kk, kv, kg))

    def grads(bwd):
        def loss(q, k, v):
            out = _flash_bsnd(q, k, v, float(d ** -0.5), (64, 64), bwd)
            return jnp.sum(out.astype(jnp.float32) * ct.astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for a, b_ in zip(grads("split"), grads("fused")):
        assert a.dtype == b_.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(b_, np.float32), np.asarray(a, np.float32), rtol=tol, atol=tol)
    with pytest.raises(ValueError, match="schedule"):
        grads("fuse")


def test_the_schedule_is_not_a_caller_s_to_choose():
    """Nothing outside the kernel's file selects a backward schedule: the
    public call takes no such argument (``_bwd_schedule`` reads the shapes)."""
    q = jnp.zeros((1, 64, 2, 32), jnp.float32)
    with pytest.raises(TypeError, match="bwd_schedule"):
        flash_attention(q, q, q, bwd_schedule="fused")


def test_asymmetric_tiles_match_the_square_ones():
    """bq != bk must give the same output and gradients as the square
    tile: the causal bounds inside the forward, dq and dk/dv kernels use
    ceil/floor divisions that have to hold for unequal blocks, in both
    backward schedules (the kernels take the pair; ``_block_sizes`` hands
    them a square one today)."""
    from paddlefleetx_tpu.ops.flash_attention import _flash_bsnd

    b, s, n, d = 2, 256, 2, 64
    kq, kk, kv, kg = jax.random.split(jax.random.key(3), 4)
    q, k, v, ct = (jax.random.normal(key, (b, s, n, d), jnp.float32) for key in (kq, kk, kv, kg))
    scale = float(1.0 / d**0.5)

    def run(block, bwd="split"):
        def loss(q, k, v):
            return jnp.sum(_flash_bsnd(q, k, v, scale, block, bwd) * ct)

        return (_flash_bsnd(q, k, v, scale, block, bwd),) + jax.grad(loss, (0, 1, 2))(q, k, v)

    square = run((64, 64))
    for block, bwd in (((64, 128), "split"), ((128, 64), "split"), ((64, 128), "fused")):
        for got, want in zip(run(block, bwd), square):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_bf16_grads_of_several_blocks_track_the_f32_ones():
    """bfloat16, the dtype the model path runs, over 4 q and 4 kv blocks:
    with fp32 inputs the kernel-internal downcasts (p_lo / ds in
    ``_bwd_tile``) are no-ops, so only a bf16 run over several blocks can
    catch a dtype slip in the carries between them."""
    b, s, n, d = 1, 256, 2, 32
    kq, kk, kv, kg = jax.random.split(jax.random.key(6), 4)
    q, k, v, ct = (jax.random.normal(key, (b, s, n, d), jnp.float32) for key in (kq, kk, kv, kg))

    def grads(dtype):
        def loss(q, k, v):
            out = flash_attention(q.astype(dtype), k.astype(dtype), v.astype(dtype), block=64)
            return jnp.sum(out.astype(jnp.float32) * ct)

        return jax.grad(loss, (0, 1, 2))(q, k, v)

    for got, want in zip(grads(jnp.bfloat16), grads(jnp.float32)):
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=0.0, atol=0.35)


def test_bf16_accuracy_vs_f32_reference():
    """The kernels keep MXU dots in the input dtype (bf16 on the model
    path) with fp32 accumulation; bf16 outputs must still track the fp32
    XLA reference to bf16 resolution (~3 decimal digits)."""
    b, s, n, d = 2, 256, 2, 64
    key = jax.random.key(3)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, s, n, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, n, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, n, d), jnp.float32)
    ct = jax.random.normal(kg, (b, s, n, d), jnp.float32)

    ref = xla_attention(q, k, v, causal=True)
    got = flash_attention(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref), rtol=0.0, atol=0.05
    )

    def loss_flash_bf16(q, k, v):
        out = flash_attention(
            q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        )
        return jnp.sum(out.astype(jnp.float32) * ct)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) * ct)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash_bf16, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        np.testing.assert_allclose(
            np.asarray(b_, np.float32), np.asarray(a), rtol=0.0, atol=0.35
        )
