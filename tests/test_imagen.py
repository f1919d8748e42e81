"""Imagen tests: diffusion schedule identities, unet shapes (base + SR),
CFG wiring, loss training step, cascade sampling smoke, dataset."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.multimodal.imagen import diffusion as diff
from paddlefleetx_tpu.models.multimodal.imagen import imagen, unet as unet_lib
from paddlefleetx_tpu.models.multimodal.imagen.imagen import ImagenConfig
from paddlefleetx_tpu.models.multimodal.imagen.unet import UnetConfig

# Pallas interpret-mode / big-compile file: excluded from the fast
# subset (pytest -m 'not slow'); run the full suite for release checks
pytestmark = pytest.mark.slow

TINY_UNET = dict(
    dim=16, dim_mults=(1, 2), layer_attns=(False, True),
    layer_cross_attns=(False, True), num_resnet_blocks=1,
    attn_heads=2, attn_head_dim=8, num_time_tokens=2,
)

TINY = ImagenConfig(
    unets=(TINY_UNET,),
    image_sizes=(16,),
    text_embed_dim=24,
    timesteps=8,
    dtype="float32",
)

TINY_SR = ImagenConfig(
    unets=(TINY_UNET, TINY_UNET),
    image_sizes=(8, 16),
    text_embed_dim=24,
    timesteps=8,
    unet_number=2,
    dtype="float32",
)


def _batch(b=2, size=16, L=5, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "images": jnp.asarray(rng.uniform(size=(b, size, size, 3)), jnp.float32),
        "text_embeds": jnp.asarray(rng.normal(size=(b, L, 24)), jnp.float32),
        "text_mask": jnp.asarray([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], jnp.int32),
    }


def test_schedule_identities():
    sched = diff.GaussianDiffusionContinuousTimes("cosine", 10)
    t = jnp.asarray([0.0, 0.25, 0.5, 0.75, 1.0])
    alpha, sigma = diff.log_snr_to_alpha_sigma(sched.log_snr(t))
    # variance preserving: alpha^2 + sigma^2 == 1
    np.testing.assert_allclose(np.asarray(alpha**2 + sigma**2), 1.0, atol=1e-5)
    # t=0 nearly clean, t=1 nearly pure noise
    assert float(alpha[0]) > 0.99 and float(alpha[-1]) < 0.05

    # q_sample -> predict_start_from_noise round-trips x0 (t < 1: at t=1
    # alpha ~ 4e-8 and the fp32 subtraction cancels catastrophically)
    t = jnp.asarray([0.0, 0.25, 0.5, 0.75, 0.9])
    x0 = jnp.ones((5, 4, 4, 3)) * 0.3
    noise = jax.random.normal(jax.random.key(0), x0.shape)
    x_t, _, _ = sched.q_sample(x0, t, noise)
    rec = sched.predict_start_from_noise(x_t, t, noise)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(x0), atol=1e-3)

    # v parameterization round-trip
    v = sched.calculate_v(x0, t, noise)
    rec_v = sched.predict_start_from_v(x_t, t, v)
    np.testing.assert_allclose(np.asarray(rec_v), np.asarray(x0), atol=1e-3)


def test_unet_base_shapes():
    ucfg = UnetConfig.from_config({**TINY_UNET, "text_embed_dim": 24, "dtype": "float32"})
    params = unet_lib.init(ucfg, jax.random.key(0))
    b = _batch()
    x = jnp.zeros((2, 16, 16, 3))
    out = unet_lib.forward(
        params, x, jnp.asarray([0.1, 0.9]), ucfg,
        text_embeds=b["text_embeds"], text_mask=b["text_mask"],
    )
    assert out.shape == (2, 16, 16, 3)
    assert np.all(np.isfinite(np.asarray(out)))


def test_unet_sr_lowres_cond():
    ucfg = UnetConfig.from_config(
        {**TINY_UNET, "text_embed_dim": 24, "lowres_cond": True, "dtype": "float32"}
    )
    params = unet_lib.init(ucfg, jax.random.key(1))
    x = jnp.zeros((2, 16, 16, 3))
    out = unet_lib.forward(
        params, x, jnp.asarray([0.5, 0.5]), ucfg,
        text_embeds=_batch()["text_embeds"],
        lowres_cond_img=jnp.ones_like(x) * 0.1,
        lowres_aug_time=jnp.asarray([0.2, 0.2]),
    )
    assert out.shape == (2, 16, 16, 3)


def test_cfg_drop_changes_output():
    """Dropping text cond must route through the null embeddings."""
    ucfg = UnetConfig.from_config({**TINY_UNET, "text_embed_dim": 24, "dtype": "float32"})
    params = unet_lib.init(ucfg, jax.random.key(2))
    b = _batch()
    x = jnp.ones((2, 16, 16, 3)) * 0.1
    t = jnp.asarray([0.5, 0.5])
    kept = unet_lib.forward(params, x, t, ucfg, text_embeds=b["text_embeds"],
                            text_mask=b["text_mask"],
                            cond_drop_mask=jnp.asarray([False, False]))
    dropped = unet_lib.forward(params, x, t, ucfg, text_embeds=b["text_embeds"],
                               text_mask=b["text_mask"],
                               cond_drop_mask=jnp.asarray([True, True]))
    assert float(jnp.max(jnp.abs(kept - dropped))) > 1e-4
    # dropped output is text-independent
    b2 = _batch(seed=9)
    dropped2 = unet_lib.forward(params, x, t, ucfg, text_embeds=b2["text_embeds"],
                                text_mask=b2["text_mask"],
                                cond_drop_mask=jnp.asarray([True, True]))
    np.testing.assert_allclose(np.asarray(dropped), np.asarray(dropped2), atol=1e-5)


def test_p_losses_and_grad_step():
    import optax

    params = imagen.init(TINY, jax.random.key(3))
    batch = _batch()
    loss = imagen.p_losses(params, batch, TINY, jax.random.key(0), train=True)
    assert np.isfinite(float(loss))
    # ~unit-variance noise target at random init -> loss near 1
    assert 0.2 < float(loss) < 5.0

    tx = optax.adam(1e-3)
    opt = tx.init(params)

    @jax.jit
    def step(p, o, k):
        loss, g = jax.value_and_grad(
            lambda pp: imagen.p_losses(pp, batch, TINY, k, train=True)
        )(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    losses = []
    for i in range(10):
        params, opt, loss = step(params, opt, jax.random.key(i))
        losses.append(float(loss))
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_p_losses_bf16_compute():
    """AMP path: fp32 master params + bfloat16 compute dtype.  The unet
    casts its fp32 params per use (unet.py forward entry), so the conv
    lhs/rhs dtypes agree — regression for imagen training under Engine
    mix_precision bf16."""
    import dataclasses

    cfg = dataclasses.replace(TINY, dtype="bfloat16")
    params = imagen.init(TINY, jax.random.key(3))  # fp32 masters
    loss = imagen.p_losses(params, _batch(), cfg, jax.random.key(0), train=True)
    assert np.isfinite(float(loss))
    g = jax.grad(
        lambda p: imagen.p_losses(p, _batch(), cfg, jax.random.key(0), train=True)
    )(params)
    # grads arrive in the master dtype (fp32) and are finite
    leaves = jax.tree.leaves(g)
    assert all(x.dtype == jnp.float32 for x in leaves)
    assert all(bool(jnp.isfinite(x).all()) for x in leaves)


def test_sr_unet_p_losses():
    params = imagen.init(TINY_SR, jax.random.key(4))
    loss = imagen.p_losses(params, _batch(), TINY_SR, jax.random.key(0), train=True)
    assert np.isfinite(float(loss))


def test_cascade_sample_smoke():
    p0 = imagen.init(TINY, jax.random.key(5))
    sr_params = imagen.init(TINY_SR, jax.random.key(6))
    b = _batch()
    out = imagen.sample(
        [p0, sr_params], TINY_SR, jax.random.key(7),
        text_embeds=b["text_embeds"], text_mask=b["text_mask"],
        guidance_scale=3.0,
    )
    assert out.shape == (2, 16, 16, 3)
    assert np.all(np.isfinite(np.asarray(out)))
    assert 0.0 <= float(out.min()) and float(out.max()) <= 1.0


def test_imagen_dataset(tmp_path):
    from paddlefleetx_tpu.data.multimodal_dataset import (
        ImagenDataset,
        write_synthetic_image_text_corpus,
    )
    from paddlefleetx_tpu.data.tokenizers.t5_tokenizer import T5Tokenizer

    path = write_synthetic_image_text_corpus(str(tmp_path / "corpus.jsonl"), n=4)
    tok = T5Tokenizer.from_tiny_corpus(["red green cat dog sky tree sun sea"])
    ds = ImagenDataset(path, image_size=16, max_seq_len=8, tokenizer=tok)
    assert len(ds) == 4
    item = ds[0]
    assert item["images"].shape == (16, 16, 3)
    assert 0.0 <= item["images"].min() and item["images"].max() <= 1.0
    assert item["input_ids"].shape == (8,)

    # tokenizer from a saved vocab (the config-yaml path) + resize of a
    # FLOAT npy image must not truncate to black
    import base64 as b64
    import io
    import json

    vocab_path = str(tmp_path / "vocab.json")
    tok.save(vocab_path)
    buf = io.BytesIO()
    np.save(buf, np.full((24, 24, 3), 0.6, np.float32))
    float_corpus = str(tmp_path / "float.jsonl")
    with open(float_corpus, "w") as f:
        f.write(json.dumps({
            "image_npy_base64": b64.b64encode(buf.getvalue()).decode(),
            "caption": "red cat",
        }) + "\n")
    ds2 = ImagenDataset(float_corpus, image_size=16, max_seq_len=8,
                        tokenizer_vocab=vocab_path)
    item2 = ds2[0]
    assert item2["images"].shape == (16, 16, 3)
    np.testing.assert_allclose(item2["images"], 0.6, atol=1e-3)
    assert item2["input_ids"].shape == (8,)


def test_imagen_module_with_frozen_t5(tmp_path):
    """ImagenModule end-to-end with a frozen T5 text encoder in extra."""
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.utils.config import AttrDict

    cfg = AttrDict.from_nested(
        {
            "Model": dict(
                module="ImagenModule",
                unets=[dict(TINY_UNET)],
                image_sizes=[16],
                text_embed_dim=32,  # == t5 d_model
                timesteps=8,
                dtype="float32",
                text_encoder=dict(name="t5", vocab_size=96, d_model=32, d_kv=8,
                                  d_ff=48, num_layers=1, num_decoder_layers=1,
                                  num_heads=4, dtype="float32", dropout_rate=0.0),
            ),
            "Data": {},
        }
    )
    mod = build_module(cfg)
    params = mod.init_params(jax.random.key(0))
    extra = mod.init_extra(jax.random.key(1), params)
    rng = np.random.default_rng(0)
    batch = {
        "images": jnp.asarray(rng.uniform(size=(2, 16, 16, 3)), jnp.float32),
        "input_ids": jnp.asarray(rng.integers(2, 96, (2, 6))),
    }
    loss, _ = mod.loss_fn(params, batch, extra=extra, train=True)
    assert np.isfinite(float(loss))
    # frozen encoder: no gradient reaches extra
    g = jax.grad(
        lambda p, e: mod.loss_fn(p, batch, extra=e, train=False)[0],
        argnums=1,
    )(params, extra)
    assert max(
        (float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(g)), default=0.0
    ) == 0.0
