"""Ring attention: context parallelism for long sequences.

The reference has NO long-context context-parallel path (SURVEY §5.7: max
trained context 1024; closest features are Megatron SP + the DAP axial
alltoall).  This is the idiomatic TPU answer: the sequence stays sharded
over the ``sep`` axis end-to-end; each device keeps its Q shard and the K/V
shards rotate around the ring (``ppermute`` hops over ICI), with
online-softmax accumulation so no device ever materialises the full
sequence — memory O(s/P), compute O(s²/P) per device.

Implemented as a ``sep``-manual ``shard_map`` through the adapter
(``parallel/shard_map_compat.py``), with ``lax.scan`` over ring steps so
reverse-mode autodiff produces the reverse-ring backward automatically.
The map is partially manual: batch/heads/model axes stay GSPMD-auto
inside, and it nests inside the 1F1B pipeline's ``stages``-manual map.
Complements Ulysses (sharding.py heads/(model,sep) rule): Ulysses reshards
seq<->heads with all-to-alls and needs heads >= sep degree; ring has no
head-count constraint and overlaps compute with neighbour exchange.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddlefleetx_tpu.parallel import shard_map_compat
from paddlefleetx_tpu.parallel.mesh import AXIS_SEP

NEG_INF = -1e30


def zigzag_permutation(seq_len: int, ring: int):
    """Balanced causal context-parallel layout (the zigzag/striped CP used
    by Megatron/llama3-scale training): split the sequence into 2*ring
    blocks and give device i blocks (i, 2*ring-1-i), so every device owns
    an early AND a late block and causal masking wastes the same ~half of
    the score blocks everywhere — with contiguous sharding device 0 is
    almost fully masked (idle) while device ring-1 does full work.

    Returns ``perm`` (int32 [seq_len]): feed ``tokens[:, perm]`` and pass
    ``positions=perm`` to :func:`ring_attention`; per-token outputs/losses
    are order-invariant, or invert with ``jnp.argsort(perm)``."""
    import numpy as np

    if seq_len % (2 * ring):
        raise ValueError(
            f"seq_len {seq_len} must be divisible by 2*ring = {2 * ring}"
        )
    block = seq_len // (2 * ring)
    idx = np.arange(seq_len).reshape(2 * ring, block)
    order = []
    for i in range(ring):
        order.append(idx[i])
        order.append(idx[2 * ring - 1 - i])
    return jnp.asarray(np.concatenate(order), jnp.int32)


def _softmax_update(q, k_c, v_c, m, l, acc, q_pos, k_pos, causal, scale):
    """Online-softmax update of (m, l, acc) with one K/V block.
    q: [b, sq, n, d]; k_c/v_c: [b, sk, n, d]; positions are GLOBAL token
    indices ([sq,1] / [1,sk]) for the causal mask."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_c, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        s = jnp.where((k_pos <= q_pos)[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    # p in the V dtype: a bf16 p x bf16 v einsum runs the MXU at full
    # rate (fp32 operands quarter it — as in the flash kernels,
    # docs/performance_tuning.md); accumulation stays fp32 via
    # preferred_element_type.  No-op for fp32 inputs.
    acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v_c.dtype), v_c,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _ring_body(q, q_pos, kv, step, *, ring_size, seq_local, causal, scale, chunk_k):
    """One ring step: partial attention of local q vs the currently-held
    K/V chunk.  q: [b, sl, n, d]; returns running (m, l, acc) update.

    Positions are explicit arrays (global token indices) carried alongside
    K/V around the ring — the causal mask never assumes the shard holds a
    contiguous block, which is what lets zigzag layouts balance causal
    work across the ring.

    ``chunk_k`` bounds the score buffer: the held K/V shard is processed in
    [sl, chunk_k] blocks under an inner ``lax.scan`` with rematerialised
    bodies, so peak memory is O(sl * chunk_k) instead of O(sl**2) — the
    flash-attention trade (recompute probabilities in the backward) in
    plain XLA einsums, which is what keeps very long local shards
    trainable."""
    k_c, v_c, k_pos_c, m, l, acc = kv
    q_pos2 = q_pos[:, None]

    if chunk_k is None or chunk_k >= seq_local:
        m, l, acc = _softmax_update(
            q, k_c, v_c, m, l, acc, q_pos2, k_pos_c[None, :], causal, scale
        )
    else:
        assert seq_local % chunk_k == 0, (seq_local, chunk_k)
        n_chunks = seq_local // chunk_k
        b, _, n, d = k_c.shape
        k_r = k_c.reshape(b, n_chunks, chunk_k, n, d).transpose(1, 0, 2, 3, 4)
        v_r = v_c.reshape(b, n_chunks, chunk_k, n, d).transpose(1, 0, 2, 3, 4)
        kp_r = k_pos_c.reshape(n_chunks, chunk_k)

        @jax.checkpoint
        def chunk_step(carry, args):
            m, l, acc = carry
            k_ch, v_ch, kp_ch = args
            m, l, acc = _softmax_update(
                q, k_ch, v_ch, m, l, acc, q_pos2, kp_ch[None, :], causal, scale
            )
            return (m, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            chunk_step, (m, l, acc), (k_r, v_r, kp_r)
        )

    # rotate K/V (and their positions) to the next rank
    perm = [(i, (i + 1) % ring_size) for i in range(ring_size)]
    k_c = jax.lax.ppermute(k_c, AXIS_SEP, perm)
    v_c = jax.lax.ppermute(v_c, AXIS_SEP, perm)
    k_pos_c = jax.lax.ppermute(k_pos_c, AXIS_SEP, perm)
    return (k_c, v_c, k_pos_c, m, l, acc)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    *,
    causal: bool = True,
    chunk_k: Optional[int] = 1024,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """q,k,v: [b, s, n, d] with s sharded over ``sep``.  Output same spec.

    ``chunk_k``: inner K-block size bounding the per-ring-step score
    buffer to [s_local, chunk_k] (None = unchunked).  Shards shorter than
    the chunk (or not dividing it) run unchunked.

    ``positions``: [s] global token index of each row (sep-sharded with
    the sequence); defaults to arange — pass the permuted positions when
    the sequence is fed in a balanced layout (``zigzag_permutation``) so
    the causal mask follows the true token order."""
    ring = mesh.shape[AXIS_SEP]
    if ring == 1:
        from paddlefleetx_tpu.ops.attention import xla_attention

        if positions is None or not causal:
            return xla_attention(q, k, v, causal=causal)
        # permuted feed on a 1-device ring: honor the positions via an
        # explicit bias mask (silently masking by storage order would
        # return wrong values for zigzag-ordered inputs)
        allowed = positions[None, :] <= positions[:, None]  # [s, s]
        bias = jnp.where(allowed, 0.0, NEG_INF)[None, None].astype(jnp.float32)
        return xla_attention(q, k, v, causal=False, bias=bias)
    d = q.shape[-1]
    scale = 1.0 / (d**0.5)
    seq_local = q.shape[1] // ring
    # falsy = unchunked (the config layer documents 0 that way); shards
    # shorter than / not dividing the chunk also run unchunked
    if not chunk_k or seq_local <= chunk_k or seq_local % chunk_k:
        chunk_k = None
    if positions is None:
        positions = jnp.arange(q.shape[1], dtype=jnp.int32)

    def local_fn(q, k, v, pos):
        b, sl, n, _ = q.shape
        m0 = jnp.full((b, n, sl), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n, sl), jnp.float32)
        acc0 = jnp.zeros((b, sl, n, d), jnp.float32)

        body = functools.partial(
            _ring_body, q, pos, ring_size=ring, seq_local=sl, causal=causal,
            scale=scale, chunk_k=chunk_k,
        )

        def scan_step(carry, _):
            return body(carry, None), None

        (k_f, v_f, _, m, l, acc), _ = jax.lax.scan(
            scan_step, (k, v, pos, m0, l0, acc0), None, length=ring
        )
        l_safe = jnp.maximum(l, 1e-30)
        out = acc / l_safe.transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype)

    # nested-map support (ring inside the 1F1B pipeline's stages-manual
    # shard_map): the inner map must be built against the AMBIENT abstract
    # mesh — passing the concrete Mesh from inside a manual context trips a
    # context-mesh mismatch
    from jax.sharding import get_abstract_mesh

    amesh = get_abstract_mesh()
    inner_mesh = amesh if AXIS_SEP in amesh.axis_names else mesh
    return shard_map_compat.shard_map(
        local_fn,
        inner_mesh,
        in_specs=(P(None, AXIS_SEP), P(None, AXIS_SEP), P(None, AXIS_SEP), P(AXIS_SEP)),
        out_specs=P(None, AXIS_SEP),
        manual_axes={AXIS_SEP},
    )(q, k, v, positions)
