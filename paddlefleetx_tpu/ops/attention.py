"""Attention ops: XLA reference implementation + dispatch to Pallas flash.

TPU-native replacement for the reference's attention stack
(``MultiHeadAttention.core_attn`` single_model.py:83-200, fused
softmax-mask-triu path and the ``flash_attention`` hook
hybrid_model.py:284-301): one causal-attention entry point, implemented as
plain XLA einsum (always available, any platform) or a Pallas TPU kernel
(``ops/flash_attention.py``) selected by ``impl``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


def causal_mask_bias(seq_len: int, dtype) -> jax.Array:
    """Additive causal bias [1, 1, s, s] (triu -> -inf)."""
    mask = jnp.tril(jnp.ones((seq_len, seq_len), dtype=jnp.bool_))
    bias = jnp.where(mask, 0.0, -1e9).astype(dtype)
    return bias[None, None, :, :]


def xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bias: Optional[jax.Array] = None,
    dropout_key: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    train: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference attention.  q,k,v: [batch, seq, heads, head_dim].

    ``scale=None`` means 1/sqrt(head_dim); pass ``scale=1.0`` for T5-style
    unscaled attention (scale folded into initialization)."""
    seq_q = q.shape[1]
    seq_k = k.shape[1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    # scores in fp32 for softmax stability (reference uses fused fp16 softmax
    # with max-subtract; bf16 TPU matmul accumulates fp32 natively)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    elif causal:
        scores = scores + causal_mask_bias(seq_k, scores.dtype)[:, :, -seq_q:, :]
    probs = jax.nn.softmax(scores, axis=-1)
    if train and dropout_rate > 0.0 and dropout_key is not None:
        keep = 1.0 - dropout_rate
        probs = probs * jax.random.bernoulli(dropout_key, keep, probs.shape) / keep
    probs = probs.astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "xla",
    causal: bool = True,
    bias: Optional[jax.Array] = None,
    dropout_key: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    train: bool = False,
    scale: Optional[float] = None,
    ctx=None,
    window: int = 0,
) -> jax.Array:
    """Dispatching attention entry point used by all models.

    ``window`` > 0 (causal only): position i sees positions i-window+1 .. i.
    k and v may carry fewer heads than q (a divisor: grouped-query
    attention, KV head h serving query heads g*h .. g*h+g-1).

    ``ctx`` (a model ``ShardingCtx``) is the mesh the call runs under: the
    flash kernel is independent per (batch, head), so under a mesh it runs
    inside ``shard_map`` over the batch and heads axes — GSPMD cannot
    partition a Mosaic kernel."""
    if impl == "flash" and bias is None and causal and scale is None:
        from paddlefleetx_tpu.ops.flash_attention import flash_attention, flash_supported

        if not flash_supported(q.shape[1]):
            # odd sequence lengths fall back to the XLA path (one warning)
            import warnings

            warnings.warn(
                f"flash attention unsupported for seq={q.shape[1]}; using XLA path",
                stacklevel=2,
            )
        else:
            # NB: attention-prob dropout is skipped on the flash path (the
            # reference likewise disables dropout when flash is active,
            # hybrid_model.py:284-301)
            def kernel(q, k, v):
                return flash_attention(q, k, v, causal=True, window=window)

            if ctx is not None:
                qkv = ("batch", None, "heads", "kv")
                kernel = ctx.shard_kernel(kernel, (qkv, qkv, qkv), qkv)
            return kernel(q, k, v)
    if k.shape[2] != q.shape[2]:
        k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    if window and causal and bias is None and window < k.shape[1]:
        i = jnp.arange(k.shape[1])
        seen = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)
        bias = jnp.where(seen, 0.0, -1e9)[None, None, -q.shape[1]:, :]
    out = xla_attention(
        q,
        k,
        v,
        causal=causal,
        bias=bias,
        dropout_key=dropout_key,
        dropout_rate=dropout_rate,
        train=train,
        scale=scale,
    )
    # Whenever the XLA path actually runs (configured, or flash fell back),
    # name the output so selective remat can skip the O(s^2) recompute.
    # The flash kernel names its own output the same, and its lse
    # ("attn_lse"), inside its custom_vjp forward rule. Tagging here (not at
    # call sites) keeps the which-impl-ran decision in one place.
    return checkpoint_name(out, "attn_out")
