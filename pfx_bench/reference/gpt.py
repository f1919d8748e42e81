"""Plain reference of the GPT block family the cells run (gpt-345m,
gpt-1.3b): the published forward pass in straightforward jax.numpy, float32,
matmuls at "highest" precision, no kernel, no cache, no sharding rule, no
recompute.  Independent of ``paddlefleetx_tpu.models``: it only reads the
program's parameter tree by its key names.

Architecture (GPT-2 / Megatron as PaddleFleetX trains it): learned word +
position embeddings; per layer pre-LayerNorm (eps 1e-5), causal softmax
attention scaled by 1/sqrt(head_dim) with a fused qkv projection, residual,
pre-LayerNorm, tanh-approximated GELU MLP, residual; final LayerNorm; logits
through the tied word embedding."""

import jax
import jax.numpy as jnp


def _ln(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _layer(x, p):
    b, s, _ = x.shape
    h = _ln(x, p["ln_1"])
    qkv = jnp.einsum("bsh,htnd->tbnsd", h, p["attn"]["qkv_kernel"])
    qkv = qkv + p["attn"]["qkv_bias"][:, None, :, None, :]
    q, k, v = qkv[0], qkv[1], qkv[2]  # [b, n, s, d]
    scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("bnsd,ndh->bsh", att, p["attn"]["out_kernel"]) + p["attn"]["out_bias"]
    h = _ln(x, p["ln_2"])
    h = jax.nn.gelu(h @ p["mlp"]["fc_in_kernel"] + p["mlp"]["fc_in_bias"], approximate=True)
    return x + h @ p["mlp"]["fc_out_kernel"] + p["mlp"]["fc_out_bias"]


def logits(params, tokens):
    """tokens [b, s] int -> logits [b, s, vocab] float32 (no dropout)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        emb = p["embeddings"]
        x = emb["word"][tokens] + emb["position"][jnp.arange(tokens.shape[1])][None]
        # jax.checkpoint changes no value: when the reference is differentiated
        # (the train cells' gradient check) each layer is recomputed in the
        # backward pass instead of keeping 24 layers of float32 attention maps
        x, _ = jax.lax.scan(lambda x, lp: (jax.checkpoint(_layer)(x, lp), None), x, p["layers"])
        x = _ln(x, p["final_ln"])
        return jnp.einsum("bsh,vh->bsv", x, emb["word"])


def loss(params, tokens, labels, loss_mask):
    """Masked-mean token cross-entropy of the reference logits."""
    lg = logits(params, tokens)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)
