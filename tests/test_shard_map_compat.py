"""Unit tests for the shard_map adapter (shard_map_compat).

The adapter must (a) run manual bodies whose collectives match the
equivalent pjit/GSPMD computation, (b) expose the manual axis set to
in-body code via the thread-local, and (c) strip manual axes from logical
sharding constraints inside a mapped region."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddlefleetx_tpu.parallel import shard_map_compat as smc
from paddlefleetx_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_MODEL,
    AXIS_SEP,
    AXIS_STAGES,
    MeshConfig,
    build_mesh,
)


def _mesh(devices8, **kw):
    return build_mesh(MeshConfig(**kw), devices8)


def test_adapter_is_partially_manual(devices8):
    """One lowering: ``jax.shard_map`` manual over exactly the requested
    axes — every other mesh axis stays GSPMD-auto inside the body, so a
    constraint naming one is legal there."""
    mesh = _mesh(devices8, pp_degree=2, dp_degree=4)

    def body(x):
        return jax.lax.with_sharding_constraint(x * 2.0, P(None, AXIS_DATA))

    f = smc.shard_map(body, mesh, P(AXIS_STAGES), P(AXIS_STAGES), {AXIS_STAGES})
    with mesh:
        got = jax.jit(f)(jnp.arange(16.0).reshape(2, 8))
    np.testing.assert_allclose(np.asarray(got), 2.0 * np.arange(16.0).reshape(2, 8))


def test_manual_axes_thread_local_scoping(devices8):
    """current_manual_axes(): empty outside, the body's set inside,
    restored after."""
    mesh = _mesh(devices8, pp_degree=2, dp_degree=4)
    seen = {}

    def body(x):
        seen["inside"] = smc.current_manual_axes()
        return x

    assert smc.current_manual_axes() == frozenset()
    f = smc.shard_map(body, mesh, P(AXIS_STAGES), P(AXIS_STAGES), {AXIS_STAGES})
    with mesh:
        jax.jit(f)(jnp.arange(8.0).reshape(2, 4))
    assert seen["inside"] == frozenset({AXIS_STAGES})
    assert smc.current_manual_axes() == frozenset()


def test_unknown_manual_axis_raises(devices8):
    mesh = _mesh(devices8, pp_degree=2, dp_degree=4)
    with pytest.raises(ValueError, match="not in mesh axes"):
        smc.shard_map(lambda x: x, mesh, P(), P(), {"nonexistent"})


def test_ppermute_psum_body_matches_pjit(devices8):
    """A manual ring-shift + psum body must equal the same computation
    spelled as plain (pjit-able) array ops on the global view."""
    mesh = _mesh(devices8, pp_degree=4, dp_degree=2)
    S = 4
    x = jnp.arange(4.0 * 6).reshape(4, 6) + 1.0

    def body(xs):  # xs: [1, 6] local stage shard
        s = jax.lax.axis_index(AXIS_STAGES)
        y = xs * (s + 1).astype(xs.dtype)
        y = jax.lax.ppermute(y, AXIS_STAGES, [(i, (i + 1) % S) for i in range(S)])
        total = jax.lax.psum(y, AXIS_STAGES)
        return y + 0.25 * total

    f = smc.shard_map(body, mesh, P(AXIS_STAGES), P(AXIS_STAGES), {AXIS_STAGES})
    with mesh:
        got = jax.jit(f)(x)

    # global-view reference: scale row i by (i+1), roll rows by one, add
    # a quarter of the row-sum broadcast
    y = x * jnp.arange(1.0, S + 1)[:, None]
    y = jnp.roll(y, 1, axis=0)
    ref = y + 0.25 * y.sum(axis=0, keepdims=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


def test_grad_through_manual_body_matches_pjit(devices8):
    mesh = _mesh(devices8, pp_degree=2, dp_degree=4)
    x = jnp.arange(8.0).reshape(2, 4)

    def body(xs):
        y = jnp.sin(xs)
        y = jax.lax.ppermute(y, AXIS_STAGES, [(i, (i + 1) % 2) for i in range(2)])
        return y * 3.0

    f = smc.shard_map(body, mesh, P(AXIS_STAGES), P(AXIS_STAGES), {AXIS_STAGES})
    ref_g = jax.grad(lambda x: jnp.sum(jnp.roll(jnp.sin(x), 1, 0) * 3.0))(x)
    with mesh:
        got_g = jax.jit(jax.grad(lambda x: jnp.sum(f(x))))(x)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(ref_g), rtol=1e-6)


def test_logical_constraint_stripped_inside_manual_region(devices8):
    """with_logical_constraint inside a manual body must not name manual
    axes; they are stripped and the values flow through unchanged."""
    from paddlefleetx_tpu.parallel.sharding import make_rules, with_logical_constraint

    mesh = _mesh(devices8, pp_degree=2, mp_degree=2, dp_degree=2)
    rules = make_rules()
    x = jnp.arange(8.0 * 4).reshape(8, 4)

    def body(xs):
        y = with_logical_constraint(xs, ("batch", "mlp"), rules, mesh)
        return jax.lax.ppermute(y, AXIS_STAGES, [(i, (i + 1) % 2) for i in range(2)])

    f = smc.shard_map(body, mesh, P(AXIS_STAGES), P(AXIS_STAGES), {AXIS_STAGES})
    with mesh:
        got = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.roll(x, 4, 0)), rtol=1e-6)


def test_strip_manual_axes_keeps_free_axes():
    from paddlefleetx_tpu.parallel.sharding import _strip_manual_axes

    spec = P((AXIS_DATA, AXIS_SEP), AXIS_MODEL, None)
    out = _strip_manual_axes(spec, {AXIS_SEP})
    assert tuple(out) == (AXIS_DATA, AXIS_MODEL, None)
    out = _strip_manual_axes(spec, {AXIS_DATA, AXIS_SEP, AXIS_MODEL})
    assert all(e is None for e in out)


def test_pytree_specs_and_multiple_outputs(devices8):
    """Tuple in_specs/out_specs over a pytree of args round-trip (the
    1F1B signature shape)."""
    mesh = _mesh(devices8, pp_degree=2, dp_degree=4)
    params = {"w": jnp.arange(4.0), "b": jnp.ones((2, 2))}
    x = jnp.arange(8.0).reshape(2, 4)

    def body(p, xs):
        y = xs + p["w"]
        partial = jnp.sum(y) + jnp.sum(p["b"])
        return y, partial[None]

    f = smc.shard_map(
        body,
        mesh,
        in_specs=(P(), P(AXIS_STAGES)),
        out_specs=(P(AXIS_STAGES), P(AXIS_STAGES)),
        manual_axes={AXIS_STAGES},
    )
    with mesh:
        y, partials = jax.jit(f)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x + params["w"]), rtol=1e-6)
    # stage partials concatenate on the stage axis; their sum is the total
    np.testing.assert_allclose(
        float(jnp.sum(partials)),
        float(jnp.sum(x + params["w"]) + 2 * jnp.sum(params["b"])),
        rtol=1e-6,
    )


# ---------------------------------------------------------------------------
# shard_kernel: Pallas kernels under a mesh (parallel/sharding.shard_kernel)
# ---------------------------------------------------------------------------


def _kernel_ctx(mesh):
    from paddlefleetx_tpu.models.gpt.model import ShardingCtx
    from paddlefleetx_tpu.parallel.sharding import make_rules

    return ShardingCtx(mesh, make_rules(mesh=mesh))


@pytest.mark.parametrize("degrees", [
    pytest.param({"dp_degree": 2, "mp_degree": 2, "sep_degree": 2}, id="dp2mp2sep2"),
    pytest.param({"sharding_degree": 4, "mp_degree": 2}, id="fsdp4mp2"),
])
def test_shard_kernel_matches_unsharded(devices8, degrees, monkeypatch):
    """Flash attention + fused LayerNorm through ``shard_kernel``: values
    AND grads equal the bare kernels' — incl. the LayerNorm scale/bias
    cotangents, which every shard contributes a partial sum to.
    (``layer_norm``'s rule is held to the kernel: on the CPU it names the
    composite.)"""
    from paddlefleetx_tpu.models.gpt import model as gpt_model
    from paddlefleetx_tpu.models.gpt.model import layer_norm
    from paddlefleetx_tpu.ops.attention import attention

    monkeypatch.setattr(gpt_model, "_norm_schedule", lambda *a: "kernel")

    mesh = _mesh(devices8, **degrees)
    ctx = _kernel_ctx(mesh)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 64, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 3, 4, 8)) * 0.2, jnp.float32)
    scale = jnp.asarray(rng.normal(size=(32,)) + 1.0, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(32,)), jnp.float32)

    def loss(x, w, scale, bias, ctx):
        y = layer_norm(x, scale, bias, ctx=ctx)
        qkv = jnp.einsum("bsh,htnd->bstnd", y, w)
        out = attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                        impl="flash", ctx=ctx)
        return jnp.sum(jnp.sin(out))

    grad = jax.value_and_grad(loss, (0, 1, 2, 3))
    ref_l, ref_g = jax.jit(lambda *a: grad(*a, None))(x, w, scale, bias)
    with mesh:
        got_l, got_g = jax.jit(lambda *a: grad(*a, ctx))(x, w, scale, bias)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=1e-5)
    for g, r in zip(got_g, ref_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-3, atol=1e-3)


def test_shard_kernel_skips_axes_that_do_not_divide(devices8):
    """A dim the mesh axes do not divide stays whole (replicated compute)
    instead of failing shard_map's exact-split check; a one-device mesh
    runs the kernel bare."""
    from paddlefleetx_tpu.parallel.sharding import make_rules, shard_kernel

    mesh = _mesh(devices8, dp_degree=4, mp_degree=2)
    seen = {}

    def kernel(x):
        seen["shape"] = x.shape
        return x * 2.0

    x = jnp.arange(6.0 * 4).reshape(6, 4)  # batch 6: data=4 does not divide
    f = shard_kernel(kernel, mesh, make_rules(mesh=mesh),
                     (("batch", "mlp"),), ("batch", "mlp"))
    with mesh:
        got = jax.jit(f)(x)
    assert seen["shape"] == (6, 2)  # mlp split over model=2, batch whole
    np.testing.assert_allclose(np.asarray(got), 2.0 * np.asarray(x))

    one = build_mesh(MeshConfig(), devices8[:1])
    assert shard_kernel(kernel, one, make_rules(), (("batch", "mlp"),),
                        ("batch", "mlp"))(x).shape == (6, 4)
    assert seen["shape"] == (6, 4)
