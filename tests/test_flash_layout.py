"""The flash kernels in the MODEL's layout (``layout="bsh"``: q, k, v, dO, the
result and the gradients as [batch, seq, heads*head_dim], a 128-lane block of
whole heads a grid row, lse and delta with the sequence in the lanes) against
the transposed path they replace where ``_operand_layout`` names them, and
against the XLA reference.  Interpret mode on the CPU, small shapes; the tests
hand ``_flash_bsnd`` its static ``layout`` as they hand it ``bwd_mode``
(the public call reads both from the shapes: tests/test_flash_tiles.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.ops.attention import xla_attention
from paddlefleetx_tpu.ops.flash_attention import _flash_bsnd, _flash_fwd_bsh

# (batch, seq, heads, head_dim, tile): a batch of 2 and two lane blocks, so a
# wrong head-to-lane map or a wrong grid row cannot hide; several tiles a
# sequence, so the dq slab is carried across the kv-block programs of a row
CASES = {
    "two-heads-one-block": (2, 256, 2, 64, 64),
    "four-heads-two-blocks": (2, 256, 4, 64, 64),
    "two-tiles-of-128": (2, 256, 4, 64, 128),
    "one-tile": (2, 128, 2, 64, 128),
    "four-heads-of-32-a-block": (2, 128, 4, 32, 64),
    "one-head-a-block": (2, 128, 2, 128, 64),
}
DTYPES = {"float32": (jnp.float32, 2e-6, 5e-4, 5e-4), "bfloat16": (jnp.bfloat16, 2e-2, 0.05, 0.35)}


def _operands(case, dtype, seed=0):
    b, s, n, d, _ = CASES[case]
    keys = jax.random.split(jax.random.key(seed), 4)
    return tuple(jax.random.normal(key, (b, s, n, d), jnp.float32).astype(dtype) for key in keys)


def _result_and_grads(fn, q, k, v, ct):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * ct.astype(jnp.float32))

    return (fn(q, k, v),) + jax.grad(loss, (0, 1, 2))(q, k, v)


def _flash(case, layout):
    _, _, _, d, tile = CASES[case]
    return lambda q, k, v: _flash_bsnd(q, k, v, float(d ** -0.5), (tile, tile), "fused", 0, layout)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_model_s_layout_gives_the_transposed_path_s_numbers(case, dtype):
    """Result and all three gradients: the same sums head by head, so in
    float32 the two layouts agree to a rounding of the last place and in
    bfloat16 to what the two backward schedules are allowed of each other;
    both stand as close to the XLA reference as the old path is held."""
    dt, tol_old, tol_out, tol_grad = DTYPES[dtype]
    q, k, v, ct = _operands(case, dt)
    new = _result_and_grads(_flash(case, "bsh"), q, k, v, ct)
    old = _result_and_grads(_flash(case, "bh"), q, k, v, ct)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref = _result_and_grads(lambda q, k, v: xla_attention(q, k, v, causal=True), *f32, ct)
    for name, a, b_, r in zip(("out", "dq", "dk", "dv"), new, old, ref):
        assert a.dtype == dt and a.shape == q.shape, name
        a, b_, r = (np.asarray(x, np.float32) for x in (a, b_, r))
        np.testing.assert_allclose(a, b_, rtol=tol_old, atol=tol_old, err_msg=f"{name} against the old path")
        tol = tol_out if name == "out" else tol_grad
        np.testing.assert_allclose(a, r, rtol=0.0, atol=tol, err_msg=f"{name} against XLA")


@pytest.mark.parametrize("case", sorted(CASES))
def test_lse_leaves_with_the_sequence_in_the_lanes(case):
    """[batch, lane blocks, heads a block, seq]: head ``blk * P + h``'s row is
    the logsumexp of its masked, scaled reference scores."""
    b, s, n, d, tile = CASES[case]
    q, k, v, _ = _operands(case, jnp.float32, seed=1)
    fold = lambda x: x.reshape(b, s, n * d)
    out, lse = _flash_fwd_bsh(fold(q), fold(k), fold(v), float(d ** -0.5), (tile, tile), d)
    heads = 128 // d
    assert out.shape == (b, s, n * d) and lse.shape == (b, n // heads, heads, s) and lse.dtype == jnp.float32
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    want = jax.scipy.special.logsumexp(scores, axis=-1)  # [b, n, s]
    np.testing.assert_allclose(np.asarray(lse.reshape(b, n, s)), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["four-heads-two-blocks", "two-tiles-of-128"])
def test_a_future_token_changes_nothing_before_it(case):
    """The last token's q, k and v changed: every earlier row's result is the
    same, and (its cotangent zero, so that it asks nothing of the rows it
    sees) every earlier row's dq, dk and dv too."""
    q, k, v, ct = _operands(case, jnp.float32, seed=2)
    ct = ct.at[:, -1].set(0.0)
    before = _result_and_grads(_flash(case, "bsh"), q, k, v, ct)
    q2, k2, v2 = (x.at[:, -1].set(7.0) for x in (q, k, v))
    after = _result_and_grads(_flash(case, "bsh"), q2, k2, v2, ct)
    for name, a, b_ in zip(("out", "dq", "dk", "dv"), before, after):
        np.testing.assert_allclose(np.asarray(a[:, :-1]), np.asarray(b_[:, :-1]), rtol=1e-5, atol=1e-6,
                                   err_msg=name)
    assert not np.allclose(np.asarray(before[0][:, -1]), np.asarray(after[0][:, -1]))


@pytest.mark.parametrize("what,bwd,window,kv_heads,match", [
    ("the-split-backward", "split", 0, 2, "fused"),
    ("a-window", "fused", 64, 2, "window"),
    ("shared-kv-heads", "fused", 0, 1, "shared KV heads"),
])
def test_what_the_model_s_layout_does_not_know_raises_by_name(what, bwd, window, kv_heads, match):
    """``_operand_layout`` never names it for these; handed it all the same,
    nothing is silently dropped."""
    q, k, v, ct = _operands("one-tile", jnp.float32)
    k, v = k[:, :, :kv_heads], v[:, :, :kv_heads]
    with pytest.raises(NotImplementedError, match=match):
        _result_and_grads(lambda q, k, v: _flash_bsnd(q, k, v, 0.125, (128, 128), bwd, window, "bsh"),
                          q, k, v, ct)
