"""Sampling ops: top-k / top-p (nucleus) filtering and sampling.

TPU-native replacement for the reference's fused CUDA nucleus-sampling
kernel (``ppfleetx/ops/topp_sampling.cu``: per-batch top-k beam pass + cub
segmented radix sort + prefix-scan threshold cut) and the Python
``TopKProcess``/``TopPProcess`` (single_model.py:1237-1257, processor.py).

On TPU the full sort + scan route maps directly onto XLA's highly tuned
``sort``/``cumsum``; the reference kernel's beam shortcut (skip the sort
when a prefix of top-k tokens already covers p) is the DEFAULT fast path:
``lax.top_k`` over a fixed candidate count (64, the CUDA kernel's max
beam), exact whenever every row's nucleus fits the candidates, with a
``lax.cond``-guarded fallback to the full sort when one overflows — see
:func:`sample_top_p_topk`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e10


def top_k_filter(logits: jax.Array, k: int) -> jax.Array:
    """Mask all but the top-k logits (reference TopKProcess)."""
    if k <= 0:
        return logits
    vals, _ = jax.lax.top_k(logits, k)
    thresh = vals[..., -1:]
    return jnp.where(logits < thresh, NEG_INF, logits)


def top_p_filter(logits: jax.Array, p: float) -> jax.Array:
    """Mask logits outside the nucleus of cumulative probability p
    (reference TopPProcess processor.py; sorted high->low, tokens after the
    threshold crossing removed, best token always kept)."""
    if p >= 1.0:
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens until cumulative prob exceeds p (the crossing token stays)
    keep_sorted = cum - probs < p
    # threshold = smallest kept logit
    thresh = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < thresh, NEG_INF, logits)


def sample_top_p(
    key: jax.Array,
    probs: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """Fused nucleus sample from probabilities (the ``topp_sampling`` custom
    op's contract: inputs (probs, per-batch top_ps) -> sampled ids).

    Sort once, renormalise the nucleus, Gumbel-free inverse-CDF draw on the
    sorted distribution (one uniform per row), map back through the sort
    permutation — equivalent to multinomial over the truncated distribution.
    """
    b, v = probs.shape
    order = jnp.argsort(-probs, axis=-1)
    sorted_p = jnp.take_along_axis(probs, order, axis=-1)
    cum = jnp.cumsum(sorted_p, axis=-1)
    in_nucleus = cum - sorted_p < top_p[:, None]
    # always keep the argmax
    in_nucleus = in_nucleus.at[:, 0].set(True)
    trunc = jnp.where(in_nucleus, sorted_p, 0.0)
    total = trunc.sum(axis=-1, keepdims=True)
    u = jax.random.uniform(key, (b, 1)) * total
    idx_sorted = jnp.argmax(jnp.cumsum(trunc, axis=-1) >= u, axis=-1)
    return jnp.take_along_axis(order, idx_sorted[:, None], axis=-1)[:, 0]


def sample_top_p_topk(
    key: jax.Array,
    probs: jax.Array,
    top_p: jax.Array,
    k: int = 64,
) -> jax.Array:
    """Nucleus sample with a top-k prefilter (the ``topp_sampling.cu``
    contract: a fixed top-k beam pass first, the expensive full sort only
    when the beam does not cover p).

    ``lax.top_k(probs, k)`` returns the k best already sorted descending,
    so when the whole batch's top-k mass covers its ``top_p`` the nucleus
    lives entirely inside the k candidates: truncate/renormalize those,
    inverse-CDF draw, and map the drawn index back through the top-k
    indices — EXACT against :func:`sample_top_p` (same nucleus, same
    uniform draw, same prefix sums) while sorting k instead of the 50k
    vocab.  Rows are batched under jit, so the guard is all-rows-covered;
    any overflow row (``cum_k < p``) routes the WHOLE batch to the full
    sort via ``lax.cond`` (one runtime branch, both traced)."""
    b, v = probs.shape
    k = min(int(k), v)
    top_probs, top_idx = jax.lax.top_k(probs, k)  # sorted descending
    cum = jnp.cumsum(top_probs, axis=-1)

    def fast(_):
        in_nucleus = cum - top_probs < top_p[:, None]
        in_nucleus = in_nucleus.at[:, 0].set(True)  # always keep argmax
        trunc = jnp.where(in_nucleus, top_probs, 0.0)
        total = trunc.sum(axis=-1, keepdims=True)
        u = jax.random.uniform(key, (b, 1)) * total
        sel = jnp.argmax(jnp.cumsum(trunc, axis=-1) >= u, axis=-1)
        return jnp.take_along_axis(top_idx, sel[:, None], axis=-1)[:, 0]

    def slow(_):
        return sample_top_p(key, probs, top_p)

    covered = jnp.all(cum[:, -1] >= top_p)
    return jax.lax.cond(covered, fast, slow, operand=None)


def filtered_logits(
    logits: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jax.Array:
    """The sampling pipeline's FILTER stages only (temperature -> top-k ->
    top-p), returning the filtered logits instead of a draw.

    The speculative-decoding verify path (``ops/speculative.py``) needs
    the target DISTRIBUTION, not a sample: acceptance tests draft tokens
    against ``softmax(filtered_logits)`` and the Leviathan residual rule
    re-samples from the same filtered distribution with the rejected
    draft masked — both must see exactly the distribution the baseline
    sampler draws from, which is what these filters define
    (:func:`sample_top_p_topk` is distribution-identical to the full
    sort by construction)."""
    if temperature != 1.0:
        logits = logits / temperature
    if top_k > 0:
        logits = top_k_filter(logits, top_k)
    if top_p < 1.0:
        logits = top_p_filter(logits, top_p)
    return logits


def sample_logits(
    key: jax.Array,
    logits: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    top_p_prefilter_k: int = 64,
) -> jax.Array:
    """Reference sampling pipeline (single_model.py:1237-1257):
    temperature -> top-k -> top-p -> categorical.

    The top-p stage goes through the top-k-prefilter fast path
    (:func:`sample_top_p_topk`, ``top_p_prefilter_k`` candidates, 0 for
    the full sort alone) so the per-step cost is a top-k
    over the vocab instead of a full argsort+cumsum; the full sort runs
    only when some row's nucleus overflows the prefilter.

    ``logits`` may be [b, vocab] (one position -> ids [b], the original
    contract, unchanged) or [b, k, vocab] (k positions -> ids [b, k]):
    the multi-position form splits ``key`` into k per-position subkeys
    and samples each position independently.  The speculative verify
    step (``ops/speculative.py``) draws its fresh/residual candidates
    through this form with the filters at identity settings — it
    filters ONCE itself via :func:`filtered_logits`, so passing
    non-default filter args there would double-filter."""
    if logits.ndim == 3:
        b, k, _ = logits.shape
        subkeys = jax.random.split(key, k)

        def one(pos_key, pos_logits):  # pos_logits [b, vocab]
            return sample_logits(
                pos_key, pos_logits, temperature=temperature, top_k=top_k,
                top_p=top_p, top_p_prefilter_k=top_p_prefilter_k,
            )

        # vmap over the position axis: per-position subkeys, independent
        # draws, [k, b] -> [b, k]
        return jax.vmap(one, in_axes=(0, 1), out_axes=1)(
            subkeys, logits
        )
    if temperature != 1.0:
        logits = logits / temperature
    if top_k > 0:
        logits = top_k_filter(logits, top_k)
    if top_p < 1.0:
        probs = jax.nn.softmax(logits, axis=-1)
        top_ps = jnp.full((logits.shape[0],), top_p)
        if top_p_prefilter_k <= 0:
            return sample_top_p(key, probs, top_ps)
        return sample_top_p_topk(key, probs, top_ps, k=top_p_prefilter_k)
    return jax.random.categorical(key, logits, axis=-1)
