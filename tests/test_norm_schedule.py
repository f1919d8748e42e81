"""What runs a LayerNorm is chosen in one place, ``model._norm_schedule``,
from the call's shapes: the cells' shapes are pinned here, a lowered
``layer_norm`` shows that the choice is what runs, and a classic decoder
layer through the kernel (interpreted) is held to the same layer through the
composite, bare and under a CPU mesh through ``ctx.shard_kernel``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.common import init_params
from paddlefleetx_tpu.models.gpt import model as gpt_model
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.models.gpt.model import (
    ShardingCtx, _decoder_layer, _layer_specs, _norm_schedule, layer_norm,
)

# (one shard's rows, width, dtype, kernels compiled) -> what runs the norm
SCHEDULES = {
    # train-345m-1chip: 16 x 1,024 tokens x 1,024, forward and backward
    "345m": ((16384, 1024, "bfloat16", True), "kernel"),
    "345m-on-the-cpu": ((16384, 1024, "bfloat16", False), "composite"),
    "345m-float32": ((16384, 1024, "float32", True), "composite"),
    # the 345M step over four chips (dp 4, or dp 2 with the sequence split over mp 2)
    "345m-a-quarter": ((4096, 1024, "bfloat16", True), "kernel"),
    "the-most-measured": ((32768, 1024, "bfloat16", True), "kernel"),
    "above-the-measured": ((65536, 1024, "bfloat16", True), "composite"),
    "below-the-measured": ((2048, 1024, "bfloat16", True), "composite"),
    "rows-off-the-block": ((16384 + 8, 1024, "bfloat16", True), "composite"),
    "between-the-measured": ((12288, 1024, "bfloat16", True), "composite"),
    # serve-1.3b-docs: a decode step's rows, a prompt bucket, forward only
    "docs-decode-8": ((8, 2048, "bfloat16", True), "composite"),
    "docs-decode-64": ((64, 2048, "bfloat16", True), "composite"),
    "docs-prefill-512": ((512, 2048, "bfloat16", True), "composite"),
    "docs-prefill-896": ((896, 2048, "bfloat16", True), "composite"),
    "docs-prefill-1024": ((1024, 2048, "bfloat16", True), "composite"),
    # ``GenerationServer``'s batched prefill of 8 prompts of 512: one of the measured eight
    "server-prefill-8x512": ((4096, 2048, "bfloat16", True), "kernel"),
    "an-odd-width": ((16384, 1000, "bfloat16", True), "composite"),
    "a-width-not-measured": ((16384, 768, "bfloat16", True), "composite"),
}


@pytest.mark.parametrize("case", sorted(SCHEDULES))
def test_norm_schedule_of_the_cells(case):
    (rows, width, dtype, compiled), want = SCHEDULES[case]
    assert _norm_schedule(rows, width, jnp.dtype(dtype), compiled) == want


@pytest.mark.parametrize("shape,want", [
    ((16, 1024, 1024), "kernel"), ((8, 1, 2048), "composite"), ((1, 896, 2048), "composite")])
def test_layer_norm_lowers_what_the_rule_names(monkeypatch, shape, want):
    """The public call as the models make it (no argument selects anything):
    where kernels compile, its text holds the kernel exactly where the rule
    says so.  ``pallas_interpret`` is steered here, as
    ``tests/test_chip_compile.py`` does; nothing runs."""
    from paddlefleetx_tpu.utils import device as device_mod

    monkeypatch.setattr(device_mod, "pallas_interpret", lambda: False)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    w = jax.ShapeDtypeStruct(shape[-1:], jnp.float32)
    text = jax.jit(jax.value_and_grad(lambda x, s, b: jnp.sum(layer_norm(x, s, b).astype(jnp.float32)),
                                      (0, 1, 2))).trace(x, w, w).jaxpr.pretty_print()
    kernels = ("pfx_ln_fwd" in text, "pfx_ln_bwd" in text)  # noqa: E10 — kernel names
    assert kernels == ((want == "kernel"),) * 2


def test_layer_norm_takes_no_argument_that_selects():
    import inspect

    assert list(inspect.signature(layer_norm).parameters) == ["x", "scale", "bias", "eps", "ctx"]


CFG = dict(hidden_size=64, num_layers=1, num_attention_heads=4, ffn_hidden_size=128,
           hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0, attn_impl="xla")


def _layer_grads(dtype, ctx, mesh=None):
    """Loss and gradients (every parameter's, and x's) of one classic decoder
    layer, x [4, 32, 64]."""
    cfg = GPTConfig(dtype=dtype, **CFG)
    params = init_params(jax.random.key(0), _layer_specs(cfg))
    # the norms' parameters off their ones and zeros, so that their gradients say something
    rng = np.random.default_rng(3)
    for ln in ("ln_1", "ln_2"):
        params[ln] = {"scale": jnp.asarray(1.0 + 0.2 * rng.normal(size=(64,)), jnp.float32),
                      "bias": jnp.asarray(0.2 * rng.normal(size=(64,)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.dtype(dtype))

    def loss(p, x):
        out, _ = _decoder_layer(p, x, cfg, ctx, None, False)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    fn = jax.jit(jax.value_and_grad(loss, (0, 1)))
    if mesh is None:
        return fn(params, x)
    with mesh:
        return fn(params, x)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("layout", ["bare", "dp2mp2sep2", "fsdp4mp2"])
def test_decoder_layer_through_the_kernel_matches_the_composite(
        monkeypatch, request, dtype, tol, layout):
    """The rule held to ``kernel`` (the interpreter runs it here): the layer's
    output and the gradient of every parameter and of x equal the composite's
    in the tolerances ``tests/test_fused_layernorm.py`` holds the kernel to;
    under a mesh the kernel sits inside ``shard_map`` over the batch and seq
    axes and every shard adds its part of the norms' scale and bias
    gradients."""
    ctx = mesh = None
    if layout != "bare":
        from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
        from paddlefleetx_tpu.parallel.sharding import make_rules

        degrees = {"dp2mp2sep2": {"dp_degree": 2, "mp_degree": 2, "sep_degree": 2},
                   "fsdp4mp2": {"sharding_degree": 4, "mp_degree": 2}}[layout]
        mesh = build_mesh(MeshConfig(**degrees), request.getfixturevalue("devices8"))
        ctx = ShardingCtx(mesh, make_rules(mesh=mesh))
    want_l, want_g = _layer_grads(dtype, ctx, mesh)  # the composite, in the same layout
    seen = []

    def rule(rows, width, dt, compiled):
        seen.append((rows, width))
        return "kernel"

    monkeypatch.setattr(gpt_model, "_norm_schedule", rule)
    got_l, got_g = _layer_grads(dtype, ctx, mesh)
    # the rule read ONE shard's rows: 4 x 32 tokens over the batch and seq axes of the layout
    assert set(seen) == {({"bare": 128, "dp2mp2sep2": 32, "fsdp4mp2": 32}[layout], 64)}
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=tol)
    flat_got, tree = jax.tree.flatten(got_g)
    flat_want, tree_want = jax.tree.flatten(want_g)
    assert tree == tree_want
    for g, w in zip(flat_got, flat_want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * max(1.0, float(np.abs(w).max())))


@pytest.mark.parametrize("layout,rows", [("bare", {4 * 8, 4}), ("dp4mp2", {8, 1})])
def test_generate_hands_its_ctx_to_the_norms(monkeypatch, request, layout, rows):
    """``generate`` under a mesh is one program over its devices, so its
    norms hand the ``ctx`` in (a bare Mosaic call there is refused by the
    compiler; ``tests/test_chip_compile.py`` compiles one): the rule is shown
    ONE shard's rows, 4 prompts of 8 over ``dp`` 4, at the prefill and at a
    decode step."""
    from paddlefleetx_tpu.models.gpt import model as gpt
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig, generate

    cfg = GPTConfig(vocab_size=128, max_position_embeddings=64, dtype="float32",
                    **{**CFG, "num_layers": 2, "num_attention_heads": 8})
    params = gpt.init(cfg, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(6), (4, 8), 0, cfg.vocab_size)
    gen = GenerationConfig(max_dec_len=4, decode_strategy="greedy_search", eos_token_id=-1)
    seen = set()
    real = _norm_schedule

    def rule(n_rows, width, dt, compiled):
        seen.add(n_rows)
        return real(n_rows, width, dt, compiled)

    monkeypatch.setattr(gpt_model, "_norm_schedule", rule)
    if layout == "bare":
        generate(params, prompt, cfg, gen)
    else:
        from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
        from paddlefleetx_tpu.parallel.sharding import make_rules

        mesh = build_mesh(MeshConfig(dp_degree=4, mp_degree=2), request.getfixturevalue("devices8"))
        with mesh:
            jax.jit(lambda p, x: generate(
                p, x, cfg, gen, ctx=ShardingCtx(mesh, make_rules(mesh=mesh)))).lower(params, prompt)
    assert seen == rows
