"""Chunked softmax cross-entropy: logits never materialize.

The GPT loss tail (logits = hidden @ word.T -> fp32 softmax-CE) is the
single largest activation of the whole model: [b*s, vocab] fp32 is ~3 GB
at the bench shape, and it is the buffer that caps the per-chip batch
size.  This op streams the vocab in chunks with an online logsumexp
(fwd) and recomputes each chunk's softmax in the backward — peak memory
drops from O(N*V) to O(N*chunk), trading one extra hidden@word_c matmul
pass in the backward.

Semantics match ``gpt.model.cross_entropy`` exactly (fp32 reductions,
masked token mean).  Single-shard vocab only: under tensor parallelism
the vocab dim is model-sharded and the plain GSPMD path already handles
the reduction — callers gate on that (see gpt/model.py loss_fn).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG = -1e30  # a Python float: a device array here would be a closure constant of every trace


def _chunks(word: jax.Array, chunk: int) -> jax.Array:
    """[V, h] -> [nc, chunk, h], zero-padding the tail chunk (padded rows
    are masked out of the softmax by the scan bodies)."""
    v, h = word.shape
    pad = (-v) % chunk
    if pad:
        word = jnp.concatenate([word, jnp.zeros((pad, h), word.dtype)], axis=0)
    return word.reshape(-1, chunk, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _nll(hidden2d: jax.Array, word: jax.Array, labels1d: jax.Array, chunk: int):
    """Per-token nll [N] for flattened hidden [N, h], labels [N]."""
    nll, _ = _nll_fwd(hidden2d, word, labels1d, chunk)
    return nll


def _scan_lse_picked(hidden2d, word, labels1d, chunk):
    v = word.shape[0]
    wc = _chunks(word, chunk)
    n = hidden2d.shape[0]

    def body(carry, inp):
        m, s, picked = carry
        w_c, off = inp
        # cast to the activation dtype first (bf16 MXU matmul, fp32
        # accumulate) — matching logits_from_hidden exactly
        logits = (hidden2d @ w_c.astype(hidden2d.dtype).T).astype(jnp.float32)
        cols = off + jnp.arange(chunk, dtype=jnp.int32)
        logits = jnp.where(cols[None, :] < v, logits, NEG)  # pad-tail mask
        cm = jnp.maximum(m, logits.max(axis=-1))
        s = s * jnp.exp(m - cm) + jnp.exp(logits - cm[:, None]).sum(axis=-1)
        local = labels1d - off
        hit = (local >= 0) & (local < chunk)
        one = jax.nn.one_hot(jnp.where(hit, local, 0), chunk, dtype=logits.dtype)
        picked = picked + jnp.where(hit, (logits * one).sum(-1), 0.0)
        return (cm, s, picked), None

    init = (jnp.full((n,), NEG, jnp.float32), jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    offs = jnp.arange(wc.shape[0], dtype=jnp.int32) * chunk
    (m, s, picked), _ = jax.lax.scan(body, init, (wc, offs))
    lse = m + jnp.log(jnp.maximum(s, 1e-30))
    return lse, picked


def _nll_fwd(hidden2d, word, labels1d, chunk):
    lse, picked = _scan_lse_picked(hidden2d, word, labels1d, chunk)
    return lse - picked, (hidden2d, word, labels1d, lse)


def _nll_bwd(chunk, res, g):
    hidden2d, word, labels1d, lse = res
    v = word.shape[0]
    wc = _chunks(word, chunk)
    gf = g.astype(jnp.float32)

    def body(dh, inp):
        w_c, off = inp
        logits = (hidden2d @ w_c.astype(hidden2d.dtype).T).astype(jnp.float32)
        cols = off + jnp.arange(chunk, dtype=jnp.int32)
        logits = jnp.where(cols[None, :] < v, logits, NEG)
        p = jnp.exp(logits - lse[:, None])  # softmax chunk (0 at pad cols)
        local = labels1d - off
        hit = (local >= 0) & (local < chunk)
        one = jax.nn.one_hot(jnp.where(hit, local, 0), chunk, dtype=p.dtype)
        dlogits = (p - jnp.where(hit[:, None], one, 0.0)) * gf[:, None]
        # dlogits drops to the activation dtype for the two big matmuls
        # (bf16 MXU at full rate, fp32 quarters it — same finding as the
        # flash kernels); the dh CARRY stays fp32 so per-chunk rounding
        # does not compound across the vocab scan
        dlo = dlogits.astype(hidden2d.dtype)
        dh = dh + jax.lax.dot(
            dlo, w_c.astype(hidden2d.dtype),
            preferred_element_type=jnp.float32,
        )
        dw_c = jax.lax.dot(
            dlo.T, hidden2d, preferred_element_type=jnp.float32
        ).astype(word.dtype)
        return dh, dw_c

    offs = jnp.arange(wc.shape[0], dtype=jnp.int32) * chunk
    dh32 = jnp.zeros(hidden2d.shape, jnp.float32)
    dh, dwc = jax.lax.scan(body, dh32, (wc, offs))
    dword = dwc.reshape(-1, word.shape[1])[:v]
    return dh.astype(hidden2d.dtype), dword, None


_nll.defvjp(_nll_fwd, _nll_bwd)


def chunked_cross_entropy(
    hidden: jax.Array,
    word: jax.Array,
    labels: jax.Array,
    loss_mask: Optional[jax.Array] = None,
    chunk: int = 4096,
) -> jax.Array:
    """Masked-mean CE of ``hidden @ word.T`` vs labels, without the
    [b, s, V] logits buffer.  hidden [b, s, h], word [V, h], labels [b, s]."""
    b, s, h = hidden.shape
    v = word.shape[0]
    chunk = min(chunk, v)  # tail chunk is zero-padded and masked
    nll = _nll(hidden.reshape(b * s, h), word, labels.reshape(b * s), chunk)
    nll = nll.reshape(b, s)
    if loss_mask is None:
        return nll.mean()
    m = loss_mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
