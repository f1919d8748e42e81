"""Flash-decode attention: length-aware blocked KV-cache attention.

TPU-native replacement for the decode step's attend-over-everything
``xla_attention(q, k_cache, v_cache, bias=[.,1,t,max_len])``: the cache is
preallocated at ``prompt_len + max_dec_len``, but at step ``pos`` only the
first ``pos + t`` slots hold real keys.  The dense path pays FLOPs and HBM
reads for the whole buffer every token; this op visits only the cache
blocks ``< ceil((pos + t) / block)`` and folds the causal + left-pad
(``kv_valid_from``) masks into per-block masking, so per-token cost scales
with the tokens generated so far instead of the preallocated maximum.

Online-softmax accumulation across blocks (same residual trick as the
flash forward in ``ops/flash_attention.py``): running max ``m``, running
denominator ``l``, fp32 accumulator rescaled by ``exp(m - m_new)`` per
block — bitwise layout-independent of how many blocks are visited.

Two implementations behind one entry point:

  - ``pallas``: one grid program per (batch, head); the kernel fori-loops
    over visited blocks with a runtime trip count read from a scalar
    input.  Compiled by Mosaic on a TPU (``impl="auto"`` there ALWAYS
    means this spelling — it never quietly becomes ``lax``), interpreted
    on the CPU when a test asks for it.  Mosaic must be able to PROVE
    every dynamic block load tile-aligned, so the cache length has to be
    a multiple of :data:`KV_ALIGN` (:data:`KV_ALIGN_INT8` for int8, whose
    scale rows are sliced along lanes) — :func:`kv_cache_len` is what
    ``init_cache`` allocates; a misaligned cache is refused, not rerouted.
    KNOWN LIMIT (contiguous spelling only): the BlockSpec streams the
    full [max_len, d] cache row into VMEM per program, so the length
    scaling applies to FLOPs but NOT to the HBM reads; note the partial
    last block must keep the in-kernel dslice clamp, since a grid-blocked
    tail would matmul against out-of-bounds padding (0 * NaN poisons the
    accumulator even under the mask).  The paged spelling
    (:func:`paged_decode_attention`, used by the continuous-batching
    engine) retires this: one grid step per (row, group of pages) with
    every head inside, scalar-prefetch-clamped index maps that DMA only
    the pages a row holds, and no work in a grid step past a row's
    context — HBM reads and compute scale with each row's real length.
  - ``lax``: the same blocked loop as ``lax.fori_loop`` +
    ``dynamic_slice`` — what ``auto`` means on the CPU, and the spelling
    the generation layer picks under GSPMD sharding (a pallas_call inside
    a partitioned jit would need shard_map; XLA partitions the lax loop
    for free).

Cache layout is [batch, heads, max_len, head_dim] (heads-major) so the
Pallas block tiling keeps (seq, head_dim) as the minor dims — see
``models/gpt/generation.KVCache``.

The kv block is ``decode_block``'s (256, clamped to the cache; a caller's
``block`` must be a positive multiple of 8).  The cache's storage dtype is
``kv_cache_dtype``'s, carried by ``--kv-dtype`` and the
``Generation.speculative.kv_dtype`` config key:

  "bf16" (default: the cache stays in the model dtype) | "int8".  Int8
  quantizes ON WRITE (generation-layer scatter paths, symmetric
  per-(slot, head) amax/127 scales stored alongside the cache/arena) and
  dequantizes IN-KERNEL in every spelling here: the scores absorb the
  per-key scale (``s *= k_scale[col]``) and the probabilities absorb the
  per-value scale (``p *= v_scale[col]``) — no dequantized cache is ever
  materialized, so the decode step's HBM reads HALVE vs bf16 (which is
  exactly what the flash/paged kernels made the bottleneck)

Inference-only: the blocked loop has a data-dependent trip count (a
``while_loop`` under the hood), so it is not reverse-differentiable.
Training attention stays on ``ops/attention.py`` / ``ops/flash_attention``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddlefleetx_tpu.utils import device as _device

NEG_INF = -1e30

_DEFAULT_BLOCK = 256

# Alignment Mosaic can prove for the contiguous kernel's dynamic block
# loads: the clamped start min(j*block, max_len-block) is a multiple of
# the alignment exactly when block and max_len both are.  Sublane slices
# (K/V rows) need 8; the int8 spelling also slices its [1, max_len] scale
# rows along LANES, which need 128.
KV_ALIGN = 8
KV_ALIGN_INT8 = 128


def kv_cache_len(slots: int, quantized: bool = False) -> int:
    """Allocated length of a contiguous cache that must hold ``slots``:
    rounded up to the kernel's alignment.  The slack is never visited
    (every spelling stops at ``pos + t``)."""
    align = KV_ALIGN_INT8 if quantized else KV_ALIGN
    return -(-int(slots) // align) * align


def decode_block(max_len: int, block: int = 0) -> int:
    """Resolve the kv block size: explicit ``block`` arg, else
    ``_DEFAULT_BLOCK``; clamped to ``max_len``.

    Unlike the flash block, the decode block need NOT divide the cache
    length — the last block is handled by a clamped start + dedup mask —
    but it must be a positive multiple of 8 (TPU sublane tiling).  When the CLAMP
    breaks alignment (a cache shorter than the requested block and not
    itself a multiple of 8, e.g. max_len 20) the block rounds DOWN to the
    nearest multiple of 8; only a cache shorter than 8 slots yields a
    sub-8 block (lax spelling only — the pallas spelling refuses a cache
    that is not :func:`kv_cache_len`-aligned)."""
    force = int(block) or _DEFAULT_BLOCK
    if force < 0 or force % 8:
        raise ValueError(f"decode block {force} must be a positive multiple of 8")
    clamped = min(force, max_len)
    if clamped % 8 and clamped > 8:
        clamped -= clamped % 8
    return clamped


KV_QMAX = 127.0


def kv_cache_dtype(override: str = "") -> str:
    """Resolve the KV-cache storage dtype: ``override`` (``--kv-dtype``,
    the ``Generation.speculative.kv_dtype`` config key), else "bf16".
    "bf16" means NATIVE — the cache stays in the model dtype (an f32
    model keeps f32; the name follows the flag's); "int8" enables
    quantize-on-write + dequantize-in-kernel.  A typo raises: it must
    not pass for a quantized run."""
    raw = str(override or "bf16").strip().lower()
    if raw not in ("bf16", "int8"):
        raise ValueError(f"kv dtype {raw!r}; valid: bf16 (native), int8")
    return raw


def quantize_kv(x: jax.Array):
    """Symmetric per-vector int8 quantization of a K/V chunk.

    ``x`` [..., d] -> (int8 values [..., d], f32 scales [...]): one
    amax/127 scale per (slot, head) [d]-vector — finer than a per-block
    scale, so writing one token into a half-full block never forces a
    requantization of its neighbors (the scatter paths write exactly the
    new slots).  Deterministic round-to-nearest: parity suites need
    bit-stable runs.  The scale floor keeps all-zero vectors (fresh
    arena blocks) finite; their dequantized values stay exactly 0."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scl = jnp.maximum(amax / KV_QMAX, 1e-8)
    q = jnp.clip(
        jnp.round(xf / scl[..., None]), -KV_QMAX, KV_QMAX
    ).astype(jnp.int8)
    return q, scl


def blocks_visited(limit, block: int, max_len: int):
    """Number of kv blocks the kernel visits for keys [0, limit).

    ``limit`` may be traced (pos + t inside the decode loop); the result
    bounds the fori_loop trip count.  Exposed for tests asserting the
    decode step no longer touches cache blocks beyond ``pos + t``."""
    total = -(-max_len // block)
    return jnp.minimum((limit + block - 1) // block, total)


# ---------------------------------------------------------------------------
# lax fallback (CPU + GSPMD path)
# ---------------------------------------------------------------------------


def _decode_lax(q_t, k_cache, v_cache, limit, valid_from, block, scale,
                k_scale=None, v_scale=None):
    """q_t [b, n, t, d]; caches [b, n, L, d]; limit = pos + t (traced ok).

    Returns [b, n, t, d] fp32-accumulated attention over keys [vf, limit).
    With int8 caches, ``k_scale``/``v_scale`` [b, n, L] dequantize
    in-loop: per-key scales fold into the score columns and per-value
    scales into the probability columns — the cache itself streams as
    int8."""
    b, n, t, d = q_t.shape
    max_len = k_cache.shape[2]
    quant = k_scale is not None
    q_pos = limit - t + jnp.arange(t)  # global position of each query row

    m0 = jnp.full((b, n, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n, t), jnp.float32)
    acc0 = jnp.zeros((b, n, t, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        # the last block would overrun the cache; clamp the start and mask
        # the overlap (col < j*block was handled by the previous block)
        start = jnp.maximum(jnp.minimum(j * block, max_len - block), 0)
        k = jax.lax.dynamic_slice_in_dim(k_cache, start, block, axis=2)
        v = jax.lax.dynamic_slice_in_dim(v_cache, start, block, axis=2)
        if quant:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
            ksl = jax.lax.dynamic_slice_in_dim(k_scale, start, block, axis=2)
            vsl = jax.lax.dynamic_slice_in_dim(v_scale, start, block, axis=2)
        s = scale * jnp.einsum(
            "bntd,bnkd->bntk", q_t, k, preferred_element_type=jnp.float32
        )  # [b, n, t, block]
        if quant:
            s = s * ksl[:, :, None, :]
        col = start + jnp.arange(block)  # [block]
        mask = (col[None, :] <= q_pos[:, None]) & (col[None, :] >= j * block)
        mask = mask[None, None]  # [1, 1, t, block]
        if valid_from is not None:
            mask = mask & (
                col[None, None, None, :] >= valid_from[:, None, None, None]
            )
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        pv = p * vsl[:, :, None, :] if quant else p.astype(v.dtype)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bntk,bnkd->bntd", pv, v,
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    nvisit = blocks_visited(limit, block, max_len)
    m, l, acc = jax.lax.fori_loop(0, nvisit, body, (m0, l0, acc0))
    # fully-masked query rows (left-pad positions) get 0, not NaN: they
    # feed nothing downstream (only the last, always-real row is sampled)
    return acc / jnp.maximum(l, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# Pallas kernel (TPU; interpret mode in tests)
# ---------------------------------------------------------------------------


def _decode_kernel(
    q_ref, k_ref, v_ref, limit_ref, vf_ref, o_ref, *, scale, block, max_len, t
):
    q = q_ref[0, 0]  # [t, d], native dtype; dots accumulate fp32
    d = q.shape[-1]
    limit = limit_ref[0, 0]
    vf = vf_ref[0, 0, 0]
    row_pos = (limit - t) + jax.lax.broadcasted_iota(jnp.int32, (t, block), 0)

    m0 = jnp.full((t,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((t,), jnp.float32)
    acc0 = jnp.zeros((t, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        start = jnp.maximum(jnp.minimum(j * block, max_len - block), 0)
        start = pl.multiple_of(start, KV_ALIGN)
        k = k_ref[0, 0, pl.dslice(start, block), :]
        v = v_ref[0, 0, pl.dslice(start, block), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [t, block]
        col = start + jax.lax.broadcasted_iota(jnp.int32, (t, block), 1)
        mask = (col <= row_pos) & (col >= j * block) & (col >= vf)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    nvisit = blocks_visited(limit, block, max_len)
    m, l, acc = jax.lax.fori_loop(0, nvisit, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _decode_kernel_q8(
    q_ref, k_ref, v_ref, ks_ref, vs_ref, limit_ref, vf_ref, o_ref,
    *, scale, block, max_len, t
):
    """int8 spelling of :func:`_decode_kernel`: the kv refs stream the
    cache as int8 and the per-slot scales ride two [1, max_len] f32 rows
    (lane-major, so a block's scales come out as the [1, block] row the
    score columns need) — scores absorb the key scale per COLUMN,
    probabilities absorb the value scale per column, so the dequantized
    cache never exists and the block's HBM bytes are half the bf16
    kernel's."""
    q = q_ref[0, 0].astype(jnp.float32)  # [t, d]
    d = q.shape[-1]
    limit = limit_ref[0, 0]
    vf = vf_ref[0, 0, 0]
    row_pos = (limit - t) + jax.lax.broadcasted_iota(jnp.int32, (t, block), 0)

    m0 = jnp.full((t,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((t,), jnp.float32)
    acc0 = jnp.zeros((t, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        start = jnp.maximum(jnp.minimum(j * block, max_len - block), 0)
        start = pl.multiple_of(start, KV_ALIGN_INT8)
        k = k_ref[0, 0, pl.dslice(start, block), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(start, block), :].astype(jnp.float32)
        ksl = ks_ref[0, 0, :, pl.dslice(start, block)]  # [1, block]
        vsl = vs_ref[0, 0, :, pl.dslice(start, block)]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * ksl  # [t, block]
        col = start + jax.lax.broadcasted_iota(jnp.int32, (t, block), 1)
        mask = (col <= row_pos) & (col >= j * block) & (col >= vf)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p * vsl, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    nvisit = blocks_visited(limit, block, max_len)
    m, l, acc = jax.lax.fori_loop(0, nvisit, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _decode_pallas(q_t, k_cache, v_cache, limit, valid_from, block, scale,
                   k_scale=None, v_scale=None):
    b, n, t, d = q_t.shape
    max_len = k_cache.shape[2]
    align = KV_ALIGN_INT8 if k_scale is not None else KV_ALIGN
    if max_len % align or block % align:
        # the in-kernel pl.multiple_of hint would be a lie: refuse rather
        # than hand Mosaic a misaligned load (or quietly take the lax path)
        raise ValueError(
            f"pallas decode attention needs cache length {max_len} and "
            f"block {block} to be multiples of {align} "
            f"({'int8' if k_scale is not None else 'native'} cache); "
            "allocate with init_cache / kv_cache_len, pass an aligned block, "
            "or pass impl='lax'"
        )
    limit_arr = jnp.full((1, 1), limit, jnp.int32)
    vf_arr = (
        jnp.zeros((b, 1, 1), jnp.int32)
        if valid_from is None
        else valid_from.astype(jnp.int32).reshape(b, 1, 1)
    )
    kv_spec = pl.BlockSpec((1, 1, max_len, d), lambda i, j: (i, j, 0, 0))
    if k_scale is not None:
        # scale planes enter as [b, n, 1, max_len]: a (1, max_len) block
        # equals the array's last two dims, which the (8, 128) tiling rule
        # accepts — a bare [b, n, max_len] plane's (1, max_len) block does
        # not (its second-to-last dim is the heads axis)
        scl_spec = pl.BlockSpec((1, 1, 1, max_len), lambda i, j: (i, j, 0, 0))
        kernel = functools.partial(
            _decode_kernel_q8, scale=scale, block=block, max_len=max_len, t=t
        )
        return pl.pallas_call(
            kernel,
            grid=(b, n),
            in_specs=[
                pl.BlockSpec((1, 1, t, d), lambda i, j: (i, j, 0, 0)),
                kv_spec, kv_spec, scl_spec, scl_spec,
                pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
                pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, t, d), lambda i, j: (i, j, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((b, n, t, d), jnp.float32),
            interpret=_device.pallas_interpret(),
            name="pfx_decode_contig_q8",
        )(q_t, k_cache, v_cache, k_scale[:, :, None], v_scale[:, :, None],
          limit_arr, vf_arr)
    kernel = functools.partial(
        _decode_kernel, scale=scale, block=block, max_len=max_len, t=t
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, n),
        in_specs=[
            pl.BlockSpec((1, 1, t, d), lambda i, j: (i, j, 0, 0)),
            kv_spec, kv_spec,
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, t, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, t, d), jnp.float32),
        interpret=_device.pallas_interpret(),
        name="pfx_decode_contig",
    )(q_t, k_cache, v_cache, limit_arr, vf_arr)
    return out


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    kv_valid_from: Optional[jax.Array] = None,
    block: int = 0,
    impl: str = "auto",
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Blocked KV-cache attention over keys [0, pos + t).

    q [b, t, n, d] at global positions [pos, pos+t); k_cache/v_cache
    [b, n, max_len, d] with real keys through pos+t (the current chunk
    already written).  ``kv_valid_from`` [b] masks keys before a row's
    first real token (left-padded serving buckets).  Returns [b, t, n, d].

    With an int8 cache (``kv_cache_dtype`` "int8"), ``k_scale``/``v_scale``
    [b, n, max_len] carry the per-(slot, head) quantization scales and
    both spellings dequantize IN-KERNEL (scores absorb the key scale,
    probabilities the value scale) — pass both or neither.

    ``impl``: "auto" (pallas on a TPU, lax on the CPU) | "pallas" | "lax".
    The pallas spelling needs a :func:`kv_cache_len`-aligned cache and
    refuses any other — on a TPU "auto" never degrades to lax.
    """
    if impl not in ("auto", "pallas", "lax"):
        raise ValueError(f"decode_attention impl {impl!r}; valid: auto, pallas, lax")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    b, t, n, d = q.shape
    max_len = k_cache.shape[2]
    bs = decode_block(max_len, block)
    scale = float(1.0 / (d**0.5))
    limit = pos + t
    q_t = q.transpose(0, 2, 1, 3)  # [b, n, t, d]
    if impl == "pallas" or (impl == "auto" and not _device.pallas_interpret()):
        out = _decode_pallas(q_t, k_cache, v_cache, limit, kv_valid_from,
                             bs, scale, k_scale, v_scale)
    else:
        out = _decode_lax(q_t, k_cache, v_cache, limit, kv_valid_from,
                          bs, scale, k_scale, v_scale)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged (block-table-indexed) decode attention — the continuous-batching
# serving engine's kernel (core/paged_cache.py owns the pool layout)
# ---------------------------------------------------------------------------


def live_slots(active: jax.Array):
    """A decode step's ``active`` mask [slots] -> (the live slots' numbers
    first, ascending, [slots] int32, their count [1] int32): the list the
    serving kernels' grids and block addresses follow (``pfx_decode_paged``,
    ``pfx_decode_window``, ``pfx_ssm_decode``, and ``pfx_decode_mla_paged``
    through :func:`mla_work_list`), so a dead slot costs them no grid step.
    Every layer of a step shares one mask: the step makes this ONCE and hands
    it to each layer's call."""
    live, = jnp.nonzero(active, size=active.shape[0], fill_value=0)
    return live.astype(jnp.int32), jnp.sum(active, dtype=jnp.int32)[None]


def _every_slot(b: int):
    """The list :func:`live_slots` makes of a mask with every slot live: what
    a caller without a mask sends through the same grid."""
    return jax.lax.iota(jnp.int32, b), jnp.full((1,), b, jnp.int32)


def _live_mask(live, b: int):
    """What :func:`live_slots` made, back as the mask [b] it was made of: the
    rows named among the list's first ``count`` entries (a list of any length:
    :func:`mla_work_list`'s rows name a row once a group)."""
    slots, count = live
    at = jax.lax.iota(jnp.int32, b)
    # one iota where the list is as long as the batch: the paged kernels' steps
    # keep the program text their pins hold (tests/program_text.json)
    nth = at if slots.shape[0] == b else jax.lax.iota(jnp.int32, slots.shape[0])
    return jnp.any((slots[None, :] == at[:, None]) & (nth[None, :] < count[0]), axis=1)


def _paged_lax(q_t, k_pool, v_pool, layer, tables, positions, scale,
               k_scale=None, v_scale=None, t=None, starts=None):
    """q_t [b, n, t, d] (grouped heads: [b, kv heads, group * t, d], the
    ``t`` queries of each head of a group one after the other, ``t``
    given); pools [layers, nb, n, bs, d], of which layer
    ``layer``'s blocks are read; tables [b, M] block ids; positions [b] =
    global slot of each row's FIRST query token (query qi sits at slot
    positions[i] + qi — t > 1 is the speculative multi-token verify
    chunk, causal within the chunk).

    Blocked online-softmax over each row's OWN block list: block j of row
    i holds key slots [j*bs, (j+1)*bs) of that row's logical cache, stored
    at pool block ``tables[i, j]``.  Query qi of row i attends over
    [0, positions[i] + qi + 1) — per-row, per-query limits, unlike
    :func:`_decode_lax`'s shared ``limit``.  Table entries beyond a row's
    limit (null-block padding) are masked by the causal bound, so their
    garbage never reaches the accumulator.  With int8 pools,
    ``k_scale``/``v_scale`` [layers, nb, n, bs] dequantize in-loop (scores
    absorb the key scale, probabilities the value scale).  ``starts`` [b]
    (a window layer's call): row i attends [starts[i], positions[i]] only.
    """
    b, n, rows, d = q_t.shape
    t = t or rows
    bs = k_pool.shape[3]
    quant = k_scale is not None

    m0 = jnp.full((b, n, rows), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n, rows), jnp.float32)
    acc0 = jnp.zeros((b, n, rows, d), jnp.float32)

    # each row's last needed block (its LAST query's slot): the fori
    # bound below is the BATCH max, so shorter rows clamp their gather to
    # their own last block (re-read, fully masked) — same per-row clamp
    # as the pallas index_map, keeping both spellings honestly bounded by
    # each row's real length
    last_blk = jnp.maximum(positions + t - 1, 0) // bs
    q_off = jnp.arange(t)  # query qi's slot offset within the chunk
    if rows != t:  # every head of a group asks the same t slots
        q_off = jnp.tile(q_off, rows // t)

    def body(j, carry):
        m, l, acc = carry
        jidx = jnp.minimum(j, last_blk)  # [b]
        blk = jnp.take_along_axis(tables, jidx[:, None], axis=1)[:, 0]  # [b]
        k = k_pool[layer, blk]  # [b, n, bs, d] gather
        v = v_pool[layer, blk]
        if quant:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
            ksl = k_scale[layer, blk]  # [b, n, bs]
            vsl = v_scale[layer, blk]
        s = scale * jnp.einsum(
            "bntd,bnkd->bntk", q_t, k, preferred_element_type=jnp.float32
        )  # [b, n, t, bs]
        if quant:
            s = s * ksl[:, :, None, :]
        col = j * bs + jnp.arange(bs)  # logical slot of each key column
        qpos = positions[:, None] + q_off[None, :]  # [b, t]
        mask = col[None, None, None, :] <= qpos[:, None, :, None]
        if starts is not None:
            mask = mask & (col[None, None, None, :] >= starts[:, None, None, None])
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        pv = p * vsl[:, :, None, :] if quant else p.astype(v.dtype)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bntk,bnkd->bntd", pv, v,
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    nvisit = jnp.minimum(
        (jnp.max(positions) + t + bs - 1) // bs, tables.shape[1]
    )
    first = 0 if starts is None else jnp.min(starts) // bs
    m, l, acc = jax.lax.fori_loop(first, nvisit, body, (m0, l0, acc0))
    # rows whose table is all-null (inactive slots, positions < 0 would
    # not occur — positions >= 0 always covers block 0) still get a
    # finite result; fully-masked rows divide by the epsilon floor
    return acc / jnp.maximum(l, 1e-30)[..., None]


# KV tokens one grid step of the paged kernel walks for a row: a group of
# pages this wide (8 pages of 16 slots, one of 128) makes each head's
# score product a full [t, d] x [d, 128] MXU pass and the step's DMA (all
# heads of the group, K and V) large beside the grid step's fixed cost
PAGED_STEP_TOKENS = 128
# query slots one grid step holds: the decode step (1) and the verify
# chunk (k + 1) are one tile; a prefill chunk through the paged path is
# cut into tiles so the scores [n, tile, 128] stay small in VMEM
_PAGED_Q_TILE = 64


def paged_pages_per_step(block: int, width: int, group: int = 1) -> int:
    """Pages (pool blocks of ``block`` slots) one grid step of the paged
    kernel walks, for a block table ``width`` pages wide — chosen from
    the shapes alone: fewer pages as the page grows, more (up to four
    times) where ``group`` query heads share each KV head: a token is
    then few bytes beside the grid step's fixed cost."""
    return max(1, min(PAGED_STEP_TOKENS * min(group, 4) // block, width))


def paged_tokens_computed(positions, t: int, block: int, width: int, group: int = 1):
    """KV tokens per head the paged kernel computes on for rows whose
    first query sits at slot ``positions`` (array): each row's context
    ``positions + t`` rounded up to whole grid steps, never past the
    table.  The scheduler's ``pfx_sched_decode_grid_tokens_total`` sums
    this over a step's LIVE slots: the rows the kernel's grid visits."""
    pages = paged_pages_per_step(block, width, group)
    step = pages * block
    steps = -(-width // pages)
    last_page = (positions + (t - 1)).clip(0) // block
    return (last_page // pages + 1).clip(None, steps) * step


def _paged_last_page(pos, qt, *, t, tq, bs):
    """The last page query tile ``qt`` of a row at ``pos`` needs: the one
    that holds the tile's LAST query slot."""
    return jnp.maximum(pos + jnp.minimum((qt + 1) * tq, t) - 1, 0) // bs


def _paged_kernel(
    layer_ref, tables_ref, pos_ref, live_ref, *refs, scale, bs, t, tq, pages,
    width, quant, per_kv=1, windowed=False
):
    """One (live row, query tile, page group) grid step, every head inside.
    Grid step ``i`` of the first axis is the i-th LIVE row, ``live_ref[i]``:
    the axis is as long as the step's live count, so a dead slot gets no grid
    step, no DMA and no write (its output rows are the caller's to zero).
    With ``per_kv`` > 1 query heads to a KV head, the ``per_kv * t`` queries
    that read one KV head are the ROWS of one product against its page
    (``q_ref`` [1, kv heads, per_kv * t, d], one tile), so a page is read
    once for all of them.

    ``refs`` = ``pages`` K blocks, ``pages`` V blocks (each [1, n, bs, d]:
    one whole pool page, contiguous in HBM), with int8 pools ``pages`` +
    ``pages`` scale rows [1, n, 1, bs], then o_ref and the acc / m / l
    scratch.  The index maps already DMA'd pages ``tables[i, min(j * pages
    + p, last_needed(i))]`` of layer ``layer_ref[0]`` — the scalar-prefetch
    CLAMP: past a row's last needed page they re-address the page already
    held (no new DMA).
    A group that starts past the row's last page runs NOTHING (``pl.when``):
    it costs the grid step's fixed overhead and neither load, product nor
    store.  Inside the last needed group the pages past the row's context
    are masked by the per-query causal bound.  ``t`` > 1 is the
    speculative verify chunk: query qi sits at slot pos + qi, causal
    within the chunk.  With int8 pools the scores absorb the per-key
    scale column-wise and the probabilities the per-value scale — the
    dequantized block never materializes.

    ``windowed`` (a window layer's call, ``pfx_decode_window``; one query a
    row): one more prefetched scalar a row, ``start``, is the first slot the
    row attends: a group that ends before it runs nothing, the index maps
    re-address its pages to the first one needed, and the slots before it are
    masked like the slots after ``pos``."""
    if windowed:
        start_ref, q_ref, *refs = refs
    else:
        q_ref, *refs = refs
    kv = refs[: (4 if quant else 2) * pages]
    o_ref, acc_ref, m_ref, l_ref = refs[len(kv):]
    i = live_ref[pl.program_id(0)]
    qt = pl.program_id(1)
    j = pl.program_id(2)
    pos = pos_ref[i]
    last = _paged_last_page(pos, qt, t=t, tq=tq, bs=bs)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    needed = j * pages <= last
    if windowed:
        start = start_ref[i]
        needed = needed & ((j + 1) * (pages * bs) > start)

    @pl.when(needed)
    def _group():
        def group(refs_, axis):
            blocks = [r[0] for r in refs_]
            return blocks[0] if pages == 1 else jnp.concatenate(blocks, axis)

        q = q_ref[0]  # [n, tq, d]
        k = group(kv[:pages], 1)  # [n, pages * bs, d]
        v = group(kv[pages: 2 * pages], 1)
        if quant:
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [n, tq, pages * bs], batched over heads
        if quant:
            s = s * group(kv[2 * pages: 3 * pages], 2)  # [n, 1, pages * bs]
        shape = (1, per_kv * tq, pages * bs)
        col = j * (pages * bs) + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        # query qi's own causal bound: slot pos + qi
        if per_kv == 1:
            qrow = pos + qt * tq + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        elif t == 1:
            qrow = pos
        else:
            qrow = pos + jax.lax.broadcasted_iota(jnp.int32, shape, 1) % t
        mask = col <= qrow
        if windowed:
            mask = mask & (col >= start)
        if width % pages:
            # the last group hangs over the table: its spare pages
            # re-address the table's last page and must not count twice
            mask = mask & (col < width * bs)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :, :1]  # [n, tq, 1] (lane-replicated store)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :, :1] * alpha + p.sum(axis=-1, keepdims=True)
        pv = p * group(kv[3 * pages:], 2) if quant else p.astype(v.dtype)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pv, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[:, :, :1], 1e-30)
        ).astype(o_ref.dtype)


def _paged_pallas(q_t, k_pool, v_pool, layer, tables, positions, scale, live,
                  k_scale=None, v_scale=None, t=None, starts=None):
    from jax.experimental.pallas import tpu as pltpu

    b, n, rows, d = q_t.shape
    t = t or rows
    group = rows // t
    bs = k_pool.shape[3]
    M = tables.shape[1]
    tables = tables.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    quant = k_scale is not None
    pages = paged_pages_per_step(bs, M, group)
    tq = min(t, _PAGED_Q_TILE)
    if group > 1 and (tq != t or quant):
        raise ValueError(
            f"pfx_decode_paged with {group} query heads a KV head holds all {t} queries "
            f"in one tile (at most {_PAGED_Q_TILE}) and reads no int8 pools yet")

    windowed = starts is not None
    live, count = live  # the grid's first axis: its bound is ``count``, step i is row ``live[i]``
    prefetch = [layer[None], tables, positions, live]
    if windowed:
        if t != 1 or quant:
            raise ValueError("pfx_decode_window takes one query a row and reads no int8 pools")
        prefetch.append(starts.astype(jnp.int32))

    def page_index(p, stacked):
        def index(i, qt, j, layer_ref, tables_ref, pos_ref, live_ref, *start_ref):
            # scalar-prefetch clamp: past the last page this query tile
            # needs, re-address the page already fetched — Pallas skips
            # the DMA when the index is unchanged between consecutive
            # grid steps
            i = live_ref[i]
            last = _paged_last_page(pos_ref[i], qt, t=t, tq=tq, bs=bs)
            page = jnp.minimum(j * pages + p, jnp.minimum(last, M - 1))
            if windowed:  # and before the first page the window needs, that one
                page = jnp.maximum(page, start_ref[0][i] // bs)
            at = (tables_ref[i, page], 0, 0, 0)
            return (layer_ref[0],) + at if stacked else at
        return index

    q_spec = pl.BlockSpec((1, n, group * tq, d),
                          lambda i, qt, j, _layer, _tables, _pos, live_ref, *_: (
                              live_ref[i], 0, qt, 0))
    # the pools enter WHOLE, all layers: the page address carries the
    # layer, so the caller's arena is read where it lies (a layer sliced
    # out of the stack would be copied to a buffer of its own first)
    page_specs = [
        pl.BlockSpec((None, 1, n, bs, d), page_index(p, True))
        for p in range(pages)
    ]
    in_specs = [q_spec] + page_specs * 2
    operands = [q_t] + [k_pool] * pages + [v_pool] * pages
    if quant:
        # the layer's scale planes enter as [nb, n, 1, bs] so the (1, bs)
        # tile equals the array's last two dims (the (8, 128) rule refuses
        # a (1, bs) tile of [nb, n, bs]); same clamped page address.  They
        # ARE sliced out of their stack: the device keeps [.., n, bs] f32
        # in another tiling than the kernel reads, so a plane is converted
        # on its way in, and one layer's is 1/layers of that
        def plane(x):
            return jax.lax.dynamic_index_in_dim(x, layer, keepdims=False)[:, :, None]

        in_specs += [
            pl.BlockSpec((1, n, 1, bs), page_index(p, False))
            for p in range(pages)
        ] * 2
        operands += [plane(k_scale)] * pages + [plane(v_scale)] * pages

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(count[0], -(-t // tq), -(-M // pages)),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((n, group * tq, d), jnp.float32),
            pltpu.VMEM((n, group * tq, 128), jnp.float32),
            pltpu.VMEM((n, group * tq, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, bs=bs, t=t, tq=tq, pages=pages,
        width=M, quant=quant, **({"per_kv": group} if group > 1 else {}),
        **({"windowed": True} if windowed else {}),
    )
    kw = dict(grid_spec=grid_spec, interpret=_device.pallas_interpret(),
              out_shape=jax.ShapeDtypeStruct((b, n, rows, d), jnp.float32))
    if windowed:
        # a window layer's calls under a name of their own: the trace tells
        # the two kinds of attention layer apart
        call = pl.pallas_call(kernel, name="pfx_decode_window", **kw)
    else:
        call = pl.pallas_call(kernel, name="pfx_decode_paged", **kw)
    return call(*prefetch, *operands)


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    layer: Optional[jax.Array] = None,
    impl: str = "auto",
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    starts: Optional[jax.Array] = None,
    live=None,
) -> jax.Array:
    """Block-table-indexed decode attention for the paged KV cache.

    q [b, t, n, d]; pools [num_blocks, kv heads, block, d] (one layer's
    arena — ``core/paged_cache.py``; ``kv heads`` = n or a divisor of it:
    KV head h serves query heads g*h .. g*h+g-1, whose queries read its
    pages ONCE, as the rows of one product) or, with ``layer`` (an int32
    scalar, traced or not), the whole arena [layers, num_blocks, kv heads,
    block, d], of which
    that layer's pages are read IN PLACE: the serving step carries the
    arena through its layer loop and hands it over as it is, because a
    layer's pool sliced out of the stack is copied to a buffer of its own
    before a kernel may read it.  ``block_tables`` [b, M] maps row i's
    logical block j to a pool block id; ``positions`` [b] is the slot of
    each row's FIRST query token (the chunk already written) — query qi of
    row i attends over its logical slots [0, positions[i] + qi + 1),
    causal within the chunk.  t = 1 is the plain decode step; t > 1 is
    the speculative multi-token verify chunk (k drafts + 1).  Rows are
    fully independent: each has its own length, so there is no shared
    ``limit`` and no ``kv_valid_from`` (paged rows are unpadded).
    Returns [b, t, n, d].

    With int8 pools (``kv_cache_dtype`` "int8"), ``k_scale``/``v_scale``
    [num_blocks, n, block] ([layers, num_blocks, n, block] with ``layer``)
    carry the per-(slot, head) scales stored alongside the arena; both
    spellings dequantize in-kernel (the pallas
    spelling rides the same scalar-prefetch-clamped index map, so the
    scale tiles DMA with their block) — pass both or neither.

    ``starts`` [b] (a window layer's call; t = 1): row i attends its
    logical slots [starts[i], positions[i]] and no page before the first of
    them is read; the same kernel body under ``name="pfx_decode_window"``.
    :func:`window_view` makes the table, positions and starts of a row's
    ring of pages.

    ``live``: what :func:`live_slots` makes of the step's ``active`` mask
    (the live rows' numbers first, and their count), from a decode step that
    has rows to skip.  Only the rows it lists are attended: a row it leaves
    out is never read (its table may point anywhere, its position may be
    stale) and its result is 0.  Without it every row is live.

    ``impl``: "auto" (pallas on a TPU, lax on the CPU) | "pallas" | "lax".
    The pallas spelling runs one grid step per (LIVE row, query tile, group
    of pages) with every head inside: the grid's first bound is the live
    count and every address reads the row from the list, so a dead slot
    costs nothing; a step DMAs whole pool pages
    ([n, block, d], contiguous; :func:`paged_pages_per_step` of them)
    through scalar-prefetch-clamped index maps, and a step past a row's
    context runs nothing — HBM reads and compute scale with the live rows'
    real lengths, retiring the known limit of `_decode_pallas` (which
    streams the whole cache row).  The lax spelling gathers via
    ``jnp.take`` (XLA partitions it freely under GSPMD), every row's pages,
    dead rows at position 0; both spellings zero the dead rows.
    """
    if impl not in ("auto", "pallas", "lax"):
        raise ValueError(
            f"paged_decode_attention impl {impl!r}; valid: auto, pallas, lax"
        )
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    b, t, n, d = q.shape
    if t < 1:
        raise ValueError(f"paged_decode_attention needs t >= 1; got t={t}")
    if layer is None:  # one layer's arena: a stack of one
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    layer = jnp.asarray(layer, jnp.int32)
    bs = k_pool.shape[3]
    use_pallas = impl == "pallas" or (impl == "auto" and not _device.pallas_interpret())
    if use_pallas and bs % 8:
        # pallas (asked for, or what "auto" means on a TPU) runs pallas
        # or fails LOUDLY — a silent lax fallback would mislabel A/B
        # evidence and hide a slow serving path
        raise ValueError(
            f"paged block size {bs} is not a multiple of 8 (TPU sublane "
            "tiling); the pallas spelling cannot honor it — use "
            "impl='lax' or a multiple-of-8 page size"
        )
    scale = float(1.0 / (d**0.5))
    q_t = q.transpose(0, 2, 1, 3)  # [b, n, t, d]
    kv = k_pool.shape[2]
    if kv != n:
        if n % kv:
            raise ValueError(f"{kv} KV heads do not divide {n} query heads")
        q_t = q_t.reshape(b, kv, (n // kv) * t, d)
    seen = None if live is None else _live_mask(live, b)
    if use_pallas:
        if live is None:  # no mask: the identity list through the same grid
            live = _every_slot(b)
        out = _paged_pallas(q_t, k_pool, v_pool, layer, block_tables,
                            positions, scale, live, k_scale, v_scale, t, starts)
    else:
        if seen is not None:  # a dead row's stale position bounds no loop
            positions = jnp.where(seen, positions, 0)
        out = _paged_lax(q_t, k_pool, v_pool, layer, block_tables, positions,
                         scale, k_scale, v_scale, t, starts)
    if seen is not None:  # a row that was not visited is 0, never what a buffer held
        out = jnp.where(seen[:, None, None, None], out, 0.0)
    return out.reshape(b, n, t, d).transpose(0, 2, 1, 3).astype(q.dtype)


def window_view(rings: jax.Array, positions: jax.Array, window: int, block: int):
    """A window layer's cache as :func:`paged_decode_attention` reads it.
    ``rings`` [b, R]: row i's ring of pages, token t of the row in ring slot
    ``(t // block) % R`` (R >= ceil(window / block) + 1, so the ``window``
    positions a query sees never share a slot); ``positions`` [b]: the slot
    of each row's query token, already written.  -> (tables [b, R], the ring
    turned so that its OLDEST live page comes first; positions [b] and
    starts [b] in that view): row i attends the view's slots [starts[i],
    positions[i]], which are the row's tokens (positions[i] - window,
    positions[i]].  Once a step: every window layer of it reads the same."""
    R = rings.shape[1]
    first = jnp.maximum(positions // block - (R - 1), 0)  # the oldest live page
    turned = (first[:, None] + jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)) % R
    at = positions - first * block
    return (jnp.take_along_axis(rings, turned, axis=1), at,
            jnp.maximum(at - (window - 1), 0))


# ---------------------------------------------------------------------------
# Paged decode attention over LATENT pages (latent attention's absorbed
# form: models/gpt/generation.py, docs/deepseek_v3.md).  A cached token is
# ONE vector, the normalised latent then the rotated shared key
# ([kv_lora + rope]); every head's key is that vector and every head's value
# its first kv_lora columns, so all the heads of a row are the ROWS of one
# matrix product against a page.  A page is [w, block], TOKENS MINOR: the
# TPU's tiling would pad a w = 576-wide minor dim to 640, and the compiler
# keeps such an array token-minor on the device whatever its logical shape
# says (compiled for the v5e, a [block, w] pool was converted whole on both
# sides of the kernel, 2.4 GB each way a decode step).
# ---------------------------------------------------------------------------

# latent tokens one grid step walks for a row.  A token is 1,152 bytes and
# 278,528 FLOPs, 1.4 ns of either on the v5e, and a grid step costs 0.35-0.39
# us whatever it holds (PERF.md section 5), as much as 256 tokens: at 512 a
# step the kernel's time was its 1,024 grid steps a layer (64 rows x 16),
# 23% of its roofline at 22 live rows (my chip run, PR 31); 8 pages of
# [576, 128] are 1.2 MB of VMEM, twice for the pipeline
MLA_STEP_TOKENS = 1024


def mla_pages_per_step(block: int, width: int) -> int:
    return max(1, min(MLA_STEP_TOKENS // block, width))


def _mla_last_group(positions, block: int, width: int):
    """The last group of :func:`mla_pages_per_step` pages that a row whose
    query sits at slot ``positions`` reaches, never past the table (an array
    or a scalar; the work list, the kernel and the scheduler's counter all
    read a row's reach here)."""
    pages = mla_pages_per_step(block, width)
    return (positions.clip(0) // (pages * block)).clip(None, -(-width // pages) - 1)


def mla_work_list(live, positions: jax.Array, block: int, width: int):
    """The grid of ``pfx_decode_mla_paged`` for one decode step: from what
    :func:`live_slots` made of the step's mask, the rows' ``positions`` [b]
    and the table's ``width`` in pages of ``block`` slots -> (rows, groups,
    each [b * groups a row] int32, their count [1] int32): one (row, page
    group) pair for each group a LIVE row's context reaches, the rows in
    ``live``'s order, a row's groups 0 .. :func:`_mla_last_group` one after
    the other.  Made ONCE a step, before the layer stack, beside the live
    list it is made of: every layer's call walks the same pairs."""
    slots, count = live
    b = slots.shape[0]
    steps = -(-width // mla_pages_per_step(block, width))
    listed = jax.lax.iota(jnp.int32, b) < count[0]
    reach = jnp.where(listed, _mla_last_group(positions[slots], block, width) + 1, 0)
    group = jax.lax.broadcasted_iota(jnp.int32, (b, steps), 1)
    at, = jnp.nonzero((group < reach[:, None]).reshape(-1), size=b * steps, fill_value=0)
    at = at.astype(jnp.int32)
    return slots[at // steps], at % steps, jnp.sum(reach, dtype=jnp.int32)[None]


def _mla_paged_lax(q, pool, layer, tables, positions, scale, kv_lora):
    """q [b, n, w] (absorbed query then rotated query, w = kv_lora + rope);
    pool [layers, nb, 1, w, bs]; tables [b, M]; positions [b] = the slot of
    each row's query, which attends its logical slots [0, positions].
    -> [b, n, kv_lora] float32: the probabilities' sum over the latents."""
    b, n, _ = q.shape
    bs = pool.shape[4]
    last_blk = jnp.maximum(positions, 0) // bs

    def body(j, carry):
        m, l, acc = carry
        blk = jnp.take_along_axis(tables, jnp.minimum(j, last_blk)[:, None], axis=1)[:, 0]
        page = pool[layer, blk, 0]  # [b, w, bs] gather
        s = scale * jnp.einsum("bnw,bwk->bnk", q, page, preferred_element_type=jnp.float32)
        col = j * bs + jnp.arange(bs)
        mask = (col[None, :] <= positions[:, None])[:, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bnk,bck->bnc", p.astype(page.dtype), page[:, :kv_lora],
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(axis=-1), acc

    nvisit = jnp.minimum((jnp.max(positions) + bs) // bs, tables.shape[1])
    m, l, acc = jax.lax.fori_loop(0, nvisit, body, (
        jnp.full((b, n), NEG_INF, jnp.float32), jnp.zeros((b, n), jnp.float32),
        jnp.zeros((b, n, kv_lora), jnp.float32)))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _mla_paged_kernel(layer_ref, tables_ref, pos_ref, rows_ref, groups_ref, q_ref, *refs,
                      scale, bs, pages, width, kv_lora):
    """Grid step ``k`` of the step's work list, every head inside: row
    ``rows_ref[k]``, page group ``groups_ref[k]``.  The grid is as long as
    the list's count, so a dead slot and a group past a row's context get no
    grid step, no DMA and no write (a dead row's output is the caller's to
    zero); a row's steps are consecutive, its accumulators set at its group
    0 and its result written at its last group.  ``refs`` = ``pages`` latent
    pages [w, bs] (the index maps clamp past the row's last page, as the
    per-head kernel's), then o_ref and acc / m / l."""
    kv = refs[:pages]
    o_ref, acc_ref, m_ref, l_ref = refs[pages:]
    k = pl.program_id(0)
    j = groups_ref[k]
    pos = pos_ref[rows_ref[k]]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # [n, w]
    c = kv[0][...] if pages == 1 else jnp.concatenate([r[...] for r in kv], 1)
    s = scale * jax.lax.dot_general(
        q, c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )  # [n, w] x [w, pages * bs]
    shape = (1, pages * bs)
    col = j * (pages * bs) + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = col <= pos
    if width % pages:
        mask = mask & (col < width * bs)  # spare pages of the last group
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(
        l_ref[:, :1] * alpha + p.sum(axis=-1, keepdims=True), l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(c.dtype), c[:kv_lora], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # [n, T] x [kv_lora, T]^T
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == _mla_last_group(pos, bs, width))
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def _mla_paged_pallas(q, pool, layer, tables, positions, scale, kv_lora, work):
    from jax.experimental.pallas import tpu as pltpu

    b, n, w = q.shape
    bs = pool.shape[4]
    M = tables.shape[1]
    pages = mla_pages_per_step(bs, M)
    rows, groups, count = work  # the grid: its bound is ``count``, step k is (rows[k], groups[k])

    def page_index(p):
        def index(k, layer_ref, tables_ref, pos_ref, rows_ref, groups_ref):
            i = rows_ref[k]
            last = jnp.maximum(pos_ref[i], 0) // bs
            page = jnp.minimum(groups_ref[k] * pages + p, jnp.minimum(last, M - 1))
            return (layer_ref[0], tables_ref[i, page], 0, 0, 0)
        return index

    def row_index(k, _layer, _tables, _pos, rows_ref, _groups):
        return (rows_ref[k], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(count[0],),
        in_specs=[pl.BlockSpec((1, n, w), row_index)] + [
            pl.BlockSpec((None, None, None, w, bs), page_index(p)) for p in range(pages)],
        out_specs=pl.BlockSpec((1, n, kv_lora), row_index),
        scratch_shapes=[
            pltpu.VMEM((n, kv_lora), jnp.float32),
            pltpu.VMEM((n, 128), jnp.float32),
            pltpu.VMEM((n, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_mla_paged_kernel, scale=scale, bs=bs, pages=pages,
                               width=M, kv_lora=kv_lora)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, kv_lora), jnp.float32),
        interpret=_device.pallas_interpret(),
        name="pfx_decode_mla_paged",
    )(layer[None], tables.astype(jnp.int32), positions.astype(jnp.int32), rows, groups,
      q, *([pool] * pages))


def _latent_write_kernel(layer_ref, blk_ref, off_ref, new_ref, page_ref, out_ref):
    i = pl.program_id(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, page_ref.shape, 1)
    out_ref[...] = jnp.where(lane == off_ref[i], new_ref[0], page_ref[...])


def latent_page_write(pool: jax.Array, new: jax.Array, blk: jax.Array, off: jax.Array,
                      *, layer: jax.Array, impl: str = "auto") -> jax.Array:
    """Write row i's new cached token ``new[i]`` [w] as column ``off[i]`` of
    page ``blk[i]`` of layer ``layer`` of the latent arena ``pool``
    [layers, num_blocks, 1, w, block], in place (the arena is the result).
    The Pallas spelling (``pfx_mla_write``) reads and rewrites ONE page a
    row; as an XLA scatter the same write made the compiler convert the
    whole arena to a layout of the scatter's liking and back, every layer
    of every decode step (compiled for the v5e: 2.4 GB each way)."""
    layer = jnp.asarray(layer, jnp.int32)
    new = new.astype(pool.dtype)
    use_pallas = impl == "pallas" or (impl == "auto" and not _device.pallas_interpret())
    if not use_pallas:
        return pool.at[layer, blk, 0, :, off].set(new)
    from jax.experimental.pallas import tpu as pltpu

    b, w = new.shape
    bs = pool.shape[4]
    page = pl.BlockSpec((None, None, None, w, bs),
                        lambda i, layer_ref, blk_ref, off_ref: (layer_ref[0], blk_ref[i], 0, 0, 0))
    return pl.pallas_call(
        _latent_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec((1, w, 1), lambda i, *_: (i, 0, 0)), page],
            out_specs=page),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0},
        interpret=_device.pallas_interpret(),
        name="pfx_mla_write",
    )(layer[None], blk.astype(jnp.int32), off.astype(jnp.int32), new[:, :, None], pool)


def mla_tokens_computed(positions, block: int, width: int):
    """Latent tokens the MLA kernel computes on for rows whose query sits
    at slot ``positions``: each context rounded up to whole grid steps, which
    is what :func:`mla_work_list` lists for a live row.  The scheduler's
    ``pfx_sched_decode_grid_tokens_total`` sums this over a step's LIVE slots."""
    return (_mla_last_group(positions, block, width) + 1) * (
        mla_pages_per_step(block, width) * block)


def mla_paged_decode_attention(
    q: jax.Array,
    pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    layer: jax.Array,
    scale: float,
    kv_lora: int,
    impl: str = "auto",
    work=None,
) -> jax.Array:
    """Latent attention's decode step in its ABSORBED form.  q [b, n, w]:
    each head's query carried into the latent space (``W_uk^T q_nope``,
    kv_lora wide) then its rotated part; ``pool`` the whole latent arena
    [layers, num_blocks, 1, w, block], of which layer ``layer``'s pages are
    read in place through each row's ``block_tables`` [b, M]; the query of
    row i sits at slot ``positions[i]`` (already written) and attends its
    slots [0, positions[i]].  Scores ``scale * q . page^T`` in float32,
    online softmax, -> [b, n, kv_lora] float32: sum_j p_j c_j, which the
    caller expands through ``W_uv``.

    ``work``: what :func:`mla_work_list` makes of the step's live list and
    positions, from a decode step that has rows to skip.  Only the rows it
    lists are attended: a row it leaves out is never read (its table may
    point anywhere, its position may be stale) and its result is 0.  Without
    it every row is live.

    ``impl`` as :func:`paged_decode_attention`.  The Pallas spelling
    (``pfx_decode_mla_paged``) runs one grid step per LISTED (row, group of
    :func:`mla_pages_per_step` pages): the grid is one axis whose bound is
    the list's count, and the query, the result, every page address and the
    body read the row and the group from the list, so a dead slot and a
    group past a row's context cost nothing.  The lax spelling gathers every
    row's pages, dead rows at position 0; both spellings zero the dead rows."""
    if impl not in ("auto", "pallas", "lax"):
        raise ValueError(f"mla_paged_decode_attention impl {impl!r}; valid: auto, pallas, lax")
    if pool.ndim != 5 or pool.shape[2] != 1 or pool.shape[3] != q.shape[-1]:
        raise ValueError(f"latent pool {pool.shape} does not hold vectors of {q.shape[-1]}")
    layer = jnp.asarray(layer, jnp.int32)
    bs = pool.shape[4]
    use_pallas = impl == "pallas" or (impl == "auto" and not _device.pallas_interpret())
    if use_pallas and bs % 8:
        raise ValueError(f"paged block size {bs} is not a multiple of 8; use impl='lax'")
    b = q.shape[0]
    seen = None if work is None else _live_mask((work[0], work[2]), b)
    if use_pallas:
        if work is None:  # no mask: the identity list through the same grid
            work = mla_work_list(_every_slot(b), positions, bs, block_tables.shape[1])
        out = _mla_paged_pallas(q, pool, layer, block_tables, positions, float(scale),
                                int(kv_lora), work)
    else:
        if seen is not None:  # a dead row's stale position bounds no loop
            positions = jnp.where(seen, positions, 0)
        out = _mla_paged_lax(q, pool, layer, block_tables, positions, float(scale), int(kv_lora))
    if seen is not None:  # a row that was not visited is 0, never what a buffer held
        out = jnp.where(seen[:, None, None], out, 0.0)
    return out


def dense_cache_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    kv_valid_from: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """The REFERENCE the blocked kernels are tested against
    (tests/test_decode_attention.py); no program path calls it.  Attends
    over the ENTIRE preallocated cache with a materialized
    [., 1, t, max_len] additive bias: the plain softmax(q k^T) v with the
    causal and ``kv_valid_from`` masks written out, in the kernels' own
    [b, n, L, d] cache layout.  An int8 cache is dequantized up front."""
    b, t, n, d = q.shape
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if k_scale is not None:
        # dequantize in f32 and cast the PRODUCT once: the blocked/paged
        # kernels apply scales in f32, and a comparison against them
        # must not carry extra bf16-rounded-scale error
        k_cache = (
            k_cache.astype(jnp.float32) * k_scale[..., None]
        ).astype(q.dtype)
        v_cache = (
            v_cache.astype(jnp.float32) * v_scale[..., None]
        ).astype(q.dtype)
    max_len = k_cache.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    q_pos = pos + jnp.arange(t)[:, None]
    k_pos = jnp.arange(max_len)[None, :]
    bias = jnp.where(k_pos <= q_pos, 0.0, -1e9)[None, None, :, :]  # [1,1,t,L]
    if kv_valid_from is not None:
        bias = bias + jnp.where(
            k_pos >= kv_valid_from[:, None], 0.0, -1e9
        )[:, None, None, :]
    scores = jnp.einsum(
        "btnd,bnkd->bntk", q, k_cache, preferred_element_type=jnp.float32
    ) * scale
    scores = scores + bias.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bntk,bnkd->bntd", probs, v_cache)
    return out.transpose(0, 2, 1, 3)
