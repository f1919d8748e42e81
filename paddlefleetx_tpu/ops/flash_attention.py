"""Causal flash attention — Pallas TPU kernel with custom VJP.

TPU-native replacement for the reference's fused attention path (fused
softmax-mask-triu in ``core_attn`` single_model.py:83-200 and the
``flash_attention`` hook hybrid_model.py:284-301): online-softmax tiling so
the [s, s] score matrix never materialises in HBM.

Layout: inputs and result [batch, seq, heads, head_dim] (the model's); the
differentiation rule (``_flash_bsnd``) sits around what follows, so its
residuals are in the model's layout.  The kernels are handed one of two,
chosen from the call's static shapes by ``_operand_layout``:
  bh:  [batch*heads, seq, head_dim], every operand transposed in front of a
    call and every result behind it, lse and delta [batch*heads, seq, 1].
    Knows a window, shared KV heads and both backward schedules.
  bsh: the model's own [batch, seq, heads*head_dim], a reshape that moves
    nothing.  A grid row is a batch row's 128-lane block of whole heads
    (two of 64), each worked by the code that works a ``bh`` row, on operands
    whose other heads' lanes are zeroed; lse and delta cross as
    [batch, lane blocks, heads a block, seq], the sequence in the lanes.
    No window, no shared KV heads, the fused backward alone.
Forward saves per-row logsumexp for the backward recomputation (standard
FlashAttention-2 scheme: dq swept over kv blocks, dk/dv swept over q
blocks).

``window`` (a query sees the ``window`` newest positions up to itself) adds a
lower bound on the KV blocks a query block visits, in forward, dq and dkv,
and fewer KV heads than query heads (GQA) are shared through the block
index maps; without either the programs are the causal MHA ones unchanged.

On non-TPU platforms the kernels run in Pallas interpret mode (slow but
exact) so the full test suite exercises the same code path on the CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from paddlefleetx_tpu.utils import device as _device

NEG_INF = -1e30
_LANES = 128  # a vector register's minor dimension: what a block of the model's layout holds



def _block_sizes(seq: int, block: int = 0) -> Tuple[int, int]:
    """The (q, kv) tile of a sequence length: the one place it is chosen.

    512x512 measured best on v5e at seq 1024 (8.7ms vs 10.8ms at 256x256
    and 16.2ms at 128x128 for b16/h16/d64 fwd+bwd): fewer grid programs
    amortize K/V HBM streaming; beats the stock jax.experimental Pallas
    flash (26.7ms) and splash (25.8ms) kernels at this shape.  Seqs not
    divisible by 512 use the largest dividing rung so e.g. seq 768 keeps
    flash support; small seqs run as one block; anything else reports
    unsupported and attention() falls back to XLA.  ``block`` is a
    caller's own tile (a test that wants several blocks of a short
    sequence): an invalid one raises, it is never replaced."""
    block = int(block)
    if block:
        _check_block(block, seq)
        return block, block
    for b in (512, 256, 128):
        if seq % b == 0:
            return b, b
    if seq < 256 and seq % 8 == 0:
        # single-block path needs sublane alignment too: a non-multiple-
        # of-8 seq would die in Mosaic lowering, so it falls through to
        # the unsupported return below and attention() uses XLA instead
        return seq, seq
    return 256, 256  # does not divide seq -> flash_supported() False


def _check_block(val: int, seq: int) -> None:
    if val < 0 or seq % val:
        raise ValueError(
            f"flash block {val} must be a positive divisor of seq {seq}"
        )
    if val % 8:
        # sublane alignment: a non-multiple-of-8 tile would surface as
        # an opaque Mosaic lowering error deep in the compile
        raise ValueError(
            f"flash block {val} must be a multiple of 8 (TPU sublane tiling)"
        )


def _visible(row_ids, col_ids, window):
    """Causal mask, and ``row - col < window`` where a window is given."""
    if window:
        return (col_ids <= row_ids) & (row_ids - col_ids < window)
    return col_ids <= row_ids


def _kv_block_range(qi, block_q, block_k, window):
    """KV blocks [first, end) that rows [qi*bq, (qi+1)*bq) can see."""
    end = (qi * block_q + block_q + block_k - 1) // block_k
    if not window:
        return 0, end
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k, end


def _q_block_range(kj, block_q, block_k, window, seq):
    """Q blocks [first, end) that can see columns [kj*bk, (kj+1)*bk)."""
    first, num_q = (kj * block_k) // block_q, seq // block_q
    if not window:
        return first, num_q
    last_row = kj * block_k + block_k - 1 + window - 1
    return first, jnp.minimum(last_row // block_q + 1, num_q)


def _kv_map(group):
    """Index map of a whole-sequence K/V block for query head-row ``i``:
    ``group`` consecutive query heads read one KV head."""
    if group == 1:
        return lambda i, j: (i, 0, 0)
    return lambda i, j: (i // group, 0, 0)


# Mosaic's default scoped VMEM is 16 MiB; the kernels hold whole-sequence
# K/V (dkv: Q, dO and the lane-padded lse/delta columns), double-buffered,
# which passes that from about 4k positions of 128-wide heads on.  Only
# then is a limit asked for, so shorter sequences compile as before.
_VMEM_DEFAULT = 16 * 2**20
_VMEM_CAP = 100 * 2**20  # of the 128 MiB a v5e core has


def _compiler_params(resident_bytes):
    need = 2 * resident_bytes + 8 * 2**20  # double buffers + tiles and temporaries
    if need <= _VMEM_DEFAULT:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=int(min(need, _VMEM_CAP)))}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _online_softmax(q, k_at, v_at, qi, scale, block_q, block_k, window):
    """One head's rows [qi*bq, (qi+1)*bq) against the KV blocks they see, by
    the online softmax: (result [bq, lanes of v], row maximum m [bq], row sum
    l [bq] held above 1e-30), float32.  The rows' lse is ``m + log(l)``: the
    caller forms it, ``_fwd_kernel`` behind its store of the result, which is
    the order of operations ``tests/kernel_bodies.json`` keeps for every
    cell's ``pfx_flash_fwd``.  ``k_at(j)`` / ``v_at(j)`` hand over KV block j.
    Shared by both operand layouts."""
    # MXU dots run in the INPUT dtype (bf16 on the model path) with fp32
    # accumulation via preferred_element_type — upcasting the operands to
    # fp32 first quarters MXU throughput (measured: the kernel pair sat at
    # 19% intra-kernel efficiency in the 03:17Z op table).  Softmax
    # statistics, rescaling, and the output accumulator stay fp32.
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    row_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, carry):
        m, l, acc = carry
        k = k_at(j)
        v = v_at(j)
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk] fp32
        col_ids = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(_visible(row_ids, col_ids, window), s, NEG_INF)

        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    # causal: only kv blocks intersecting rows [qi*bq, (qi+1)*bq); a window
    # also skips the blocks wholly before it.  (A row whose first visited
    # block is wholly masked carries m = NEG_INF and garbage in l and acc
    # until its first visible column, whose alpha = exp(NEG_INF - m) = 0
    # wipes them: every row sees at least its own column.)
    first_kv, num_kv = _kv_block_range(qi, block_q, block_k, window)
    m, l, acc = jax.lax.fori_loop(first_kv, num_kv, body, (m0, l0, acc0))

    l_safe = jnp.maximum(l, 1e-30)
    return acc / l_safe[:, None], m, l_safe


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_q, block_k, window=0):
    out, m, l = _online_softmax(
        q_ref[0],  # [bq, d], native dtype
        lambda j: k_ref[0, pl.dslice(j * block_k, block_k), :],
        lambda j: v_ref[0, pl.dslice(j * block_k, block_k), :],
        pl.program_id(1), scale, block_q, block_k, window)
    o_ref[0] = out.astype(o_ref.dtype)
    # lse carried as [bh, seq, 1]: TPU tiling wants the trailing block dims
    # divisible by (8, 128) or equal to the array dims — a lane dim of 1
    # satisfies the latter for this per-row scalar
    lse_ref[0, :, 0] = m + jnp.log(l)


def _flash_fwd(q, k, v, scale, block, window=0, group=1):
    bh, seq, d = q.shape
    block_q, block_k = block  # static (bq, bk) tuple
    grid = (bh, seq // block_q)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k, window=window
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, seq, d), _kv_map(group)),
            pl.BlockSpec((1, seq, d), _kv_map(group)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, seq, 1), jnp.float32),
        ],
        interpret=_device.pallas_interpret(),
        name="pfx_flash_fwd",
        **_compiler_params(2 * seq * d * k.dtype.itemsize),
    )(q, k, v)
    return out, lse


def _head_lanes(x, h, d):
    """``x`` [rows, lanes] with the lanes of every head but the ``h``-th
    zeroed: a product that contracts over the lanes then sums head ``h``'s
    terms and exact zeros, and one whose result keeps them has head ``h``'s
    columns and zeros.  ``x`` itself where one head fills the block."""
    if x.shape[-1] == d:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= h * d) & (lane < (h + 1) * d), x, jnp.zeros_like(x))


def _merge_heads(per_head, d):
    """One [rows, lanes] block from each head's own: head h's lanes of the h-th."""
    out = per_head[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    for h, x in enumerate(per_head[1:], 1):
        out = jnp.where(lane >= h * d, x, out)
    return out


def _fwd_bsh_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_q, block_k, d):
    """``_fwd_kernel`` on a 128-lane block of whole heads, one head after the
    other: head h's scores from q with the other heads' lanes zeroed; its
    ``p v`` comes out over all lanes, of which it keeps its own."""
    lanes = q_ref.shape[-1]
    heads = lanes // d
    outs, stats = [], jnp.zeros((block_q, lanes), jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, lanes), 1)
    for h in range(heads):
        out, m, l = _online_softmax(
            _head_lanes(q_ref[0], h, d),
            lambda j: k_ref[0, pl.dslice(j * block_k, block_k), :],
            lambda j: v_ref[0, pl.dslice(j * block_k, block_k), :],
            pl.program_id(1), scale, block_q, block_k, 0)
        outs.append(out)
        stats = jnp.where(lane == h, (m + jnp.log(l))[:, None], stats)
    o_ref[0] = _merge_heads(outs, d).astype(o_ref.dtype)
    # the rows' statistics leave with the sequence in the lanes: one
    # transpose a program, head h's in row h
    lse_ref[0, 0] = stats.T[:heads, :]


def _bsh_maps(blocks):
    """Index maps of a grid whose row i is (batch i // blocks, lane block
    i % blocks) over [batch, seq, lanes] operands and the statistics'
    [batch, lane blocks, heads a block, seq]."""
    tile = lambda i, j: (i // blocks, j, i % blocks)
    whole = lambda i, j: (i // blocks, 0, i % blocks)
    stat_tile = lambda i, j: (i // blocks, i % blocks, 0, j)
    stat_whole = lambda i, j: (i // blocks, i % blocks, 0, 0)
    return tile, whole, stat_tile, stat_whole


def _flash_fwd_bsh(q, k, v, scale, block, d):
    """q, k, v [batch, seq, heads*d] (the model's layout, heads folded into
    the minor dimension) -> the result in the same layout and lse
    [batch, lane blocks, heads a block, seq]: a grid row is one 128-lane
    block of ``128 // d`` whole heads."""
    b, seq, width = q.shape
    block_q, block_k = block
    lanes, blocks, heads = _LANES, width // _LANES, _LANES // d
    tile, whole, stat_tile, _ = _bsh_maps(blocks)
    return pl.pallas_call(
        functools.partial(_fwd_bsh_kernel, scale=scale, block_q=block_q, block_k=block_k, d=d),
        grid=(b * blocks, seq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, lanes), tile),
            pl.BlockSpec((1, seq, lanes), whole),
            pl.BlockSpec((1, seq, lanes), whole),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, lanes), tile),
            pl.BlockSpec((1, 1, heads, block_q), stat_tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, blocks, heads, seq), jnp.float32),
        ],
        interpret=_device.pallas_interpret(),
        name="pfx_flash_fwd_bsh",
        **_compiler_params(2 * seq * lanes * k.dtype.itemsize),
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
#
# Two schedules, chosen from the call's own shapes by ``_bwd_schedule``
# below (nothing outside this file selects one; the tests and
# ``chip_smoke.py`` that hold them to each other hand ``_flash_bsnd`` its
# ``bwd_mode``):
#   split: FlashAttention-2 style — a dq kernel swept over kv blocks and a
#     dk/dv kernel swept over q blocks.  Each (i, j) tile computes
#     s = q@k^T and p = exp(s - lse) TWICE (once per kernel).  Knows a
#     window and shared KV heads.
#   fused: one kernel, grid over kv blocks; each tile computes s/p once
#     and emits the dv/dk contributions AND accumulates the dq rows in a
#     float32 [seq, d] VMEM scratch.  TPU Pallas grids execute
#     sequentially, so the slab is zeroed at a bh row's first kv block,
#     summed into by every one, and written out once, in q's dtype, at
#     the row's last.  Knows neither a window nor shared KV heads.
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, block_q, block_k,
               window=0):
    qi = pl.program_id(1)
    q = q_ref[0]  # native dtype; dots accumulate fp32 (see _fwd_kernel)
    do = do_ref[0]
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    d = q.shape[-1]

    row_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, dq):
        k = k_ref[0, pl.dslice(j * block_k, block_k), :]
        v = v_ref[0, pl.dslice(j * block_k, block_k), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        col_ids = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        p = jnp.where(_visible(row_ids, col_ids, window), jnp.exp(s - lse[:, None]), 0.0)
        dov = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk] fp32
        ds = p * (dov - delta[:, None]) * scale
        return dq + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    first_kv, num_kv = _kv_block_range(qi, block_q, block_k, window)
    dq = jax.lax.fori_loop(first_kv, num_kv, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, block_q, block_k, seq,
    window=0
):
    kj = pl.program_id(1)
    k = k_ref[0]  # [bk, d] native dtype; dots accumulate fp32
    v = v_ref[0]
    d = k.shape[-1]

    col_ids = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def body(i, carry):
        dk, dv = carry
        sl = pl.dslice(i * block_q, block_q)
        q = q_ref[0, sl, :]
        do = do_ref[0, sl, :]
        lse = lse_ref[0, sl, 0]
        delta = delta_ref[0, sl, 0]
        row_ids = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        p_lo, ds = _bwd_tile(q, k, v, do, lse, delta, row_ids, col_ids, scale, window)
        dv_new = dv + jax.lax.dot_general(
            p_lo, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk_new, dv_new

    # causal: q blocks starting at or after this kv block's diagonal; a
    # window also ends them where its last column's last viewer sits
    first_q, num_q = _q_block_range(kj, block_q, block_k, window, seq)
    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(first_q, num_q, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_tile(q, k, v, do, lse, delta, row_ids, col_ids, scale, window=0, kq=False):
    """Shared per-(q-block, kv-block) backward tile math: recompute the
    masked softmax block from the saved lse and form ds.  Used by the split
    _dkv_kernel and both fused kernels so the mask/scaling can never diverge
    between schedules or layouts.  Returns (p_lo, ds) in the input dtype;
    dots accumulate fp32.  The tile is ``q k^T`` [bq, bk] with ``lse`` and
    ``delta`` [bq], or, with ``kq``, the same numbers as ``k q^T`` [bk, bq]
    with ``lse`` and ``delta`` [1, bq] rows, which then broadcast along the
    lanes as they arrive; ``row_ids`` / ``col_ids`` (query / key positions)
    have the tile's shape."""
    nt = (((1,), (1,)), ((), ()))
    a, b, stat = (k, q, lambda x: x) if kq else (q, k, lambda x: x[:, None])
    s = scale * jax.lax.dot_general(a, b, nt, preferred_element_type=jnp.float32)
    p = jnp.where(_visible(row_ids, col_ids, window), jnp.exp(s - stat(lse)), 0.0)
    a, b = (v, do) if kq else (do, v)
    dov = jax.lax.dot_general(a, b, nt, preferred_element_type=jnp.float32)
    ds = (p * (dov - stat(delta)) * scale).astype(q.dtype)
    return p.astype(do.dtype), ds


def _bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_acc,
    *, scale, block_q, block_k, seq
):
    kj = pl.program_id(1)
    k = k_ref[0]  # [bk, d] native dtype; dots accumulate fp32
    v = v_ref[0]
    d = k.shape[-1]

    # dq of the whole [seq, d] row is summed over the kv-block programs of
    # this bh row in a float32 scratch, zeroed at the first kv block, so the
    # accumulation rounds once at the end, not once per kv block — same
    # fp32-carry rule as the split _dq_kernel and chunked_ce's dh.
    @pl.when(kj == 0)
    def _zero_dq():
        dq_acc[...] = jnp.zeros((seq, d), jnp.float32)

    col_ids = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def body(i, carry):
        dk, dv = carry
        sl = pl.dslice(i * block_q, block_q)
        q = q_ref[0, sl, :]
        do = do_ref[0, sl, :]
        lse = lse_ref[0, sl, 0]
        delta = delta_ref[0, sl, 0]
        row_ids = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        p_lo, ds = _bwd_tile(q, k, v, do, lse, delta, row_ids, col_ids, scale)
        dv_new = dv + jax.lax.dot_general(
            p_lo, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dq_tile = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dq_acc[sl, :] = dq_acc[sl, :] + dq_tile
        return dk_new, dv_new

    first_q = (kj * block_k) // block_q
    num_q = seq // block_q
    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(first_q, num_q, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    # the row's output block stays resident until the bh row changes: it is
    # written once, from the finished sums, so dq reaches HBM in q's dtype
    @pl.when(kj == seq // block_k - 1)
    def _write_dq():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_fused(q, k, v, do, lse, delta, scale, block_q, block_k):
    from jax.experimental.pallas import tpu as pltpu

    bh, seq, d = q.shape
    lanes = max(d, 128)  # VMEM pads a narrower minor dimension to the 128-lane tile
    row = seq * lanes * q.dtype.itemsize
    return pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, scale=scale, block_q=block_q, block_k=block_k,
            seq=seq,
        ),
        grid=(bh, seq // block_k),
        in_specs=[
            pl.BlockSpec((1, seq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, seq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, seq, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, seq, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, seq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((seq, d), jnp.float32)],
        interpret=_device.pallas_interpret(),
        name="pfx_flash_bwd_fused",
        # q, dO and the dq block whole, lse and delta whole (a [seq, 1]
        # float32 column takes a 128-lane tile per 8 rows in VMEM), and
        # half the float32 slab, which is held once where those are twice
        **_compiler_params(3 * row + 2 * seq * 128 * 4 + seq * lanes * 2),
    )(q, k, v, do, lse, delta)


def _bwd_fused_bsh_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_acc,
    *, scale, block_q, block_k, seq, d
):
    """``_bwd_fused_kernel`` on a 128-lane block of whole heads, one head
    after the other.  The score tile is ``k q^T``, so lse and delta stay the
    rows they arrive as, and dk, dv take no transposed operand (dq does)."""
    kj = pl.program_id(1)
    lanes = k_ref.shape[-1]

    @pl.when(kj == 0)
    def _zero_dq():
        dq_acc[...] = jnp.zeros((seq, lanes), jnp.float32)

    col_ids = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
    first_q = (kj * block_k) // block_q
    num_q = seq // block_q
    tn = (((0,), (0,)), ((), ()))
    nn = (((1,), (0,)), ((), ()))
    dks, dvs = [], []
    for h in range(lanes // d):
        # the other heads' lanes zeroed in k and v, once a program: the
        # scores and dO v^T then sum head h's terms, dq's tile has head h's
        # lanes alone and adds into the slab as it is; dk and dv come out
        # over all lanes, of which head h keeps its own at the end
        k, v = _head_lanes(k_ref[0], h, d), _head_lanes(v_ref[0], h, d)

        def body(i, carry, h=h, k=k, v=v):
            dk, dv = carry
            sl = pl.dslice(i * block_q, block_q)
            q = q_ref[0, sl, :]
            do = do_ref[0, sl, :]
            lse = lse_ref[0, 0, h:h + 1, sl]
            delta = delta_ref[0, 0, h:h + 1, sl]
            row_ids = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1
            )
            p_lo, ds = _bwd_tile(q, k, v, do, lse, delta, row_ids, col_ids, scale, kq=True)
            dv_new = dv + jax.lax.dot_general(p_lo, do, nn, preferred_element_type=jnp.float32)
            dk_new = dk + jax.lax.dot_general(ds, q, nn, preferred_element_type=jnp.float32)
            dq_tile = jax.lax.dot_general(ds, k, tn, preferred_element_type=jnp.float32)
            dq_acc[sl, :] = dq_acc[sl, :] + dq_tile
            return dk_new, dv_new

        zero = jnp.zeros((block_k, lanes), jnp.float32)
        dk, dv = jax.lax.fori_loop(first_q, num_q, body, (zero, zero))
        dks.append(dk)
        dvs.append(dv)
    dk_ref[0] = _merge_heads(dks, d).astype(dk_ref.dtype)
    dv_ref[0] = _merge_heads(dvs, d).astype(dv_ref.dtype)

    @pl.when(kj == seq // block_k - 1)
    def _write_dq():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_fused_bsh(q, k, v, do, lse, delta, scale, block, d):
    """dq, dk, dv [batch, seq, heads*d] from operands in the same layout;
    ``lse`` as ``_flash_fwd_bsh`` leaves it and ``delta`` (the row sum of
    dO * O, a head at a time) in lse's shape."""
    from jax.experimental.pallas import tpu as pltpu

    b, seq, width = q.shape
    block_q, block_k = block
    lanes, blocks, heads = _LANES, width // _LANES, _LANES // d
    tile, whole, _, stat_whole = _bsh_maps(blocks)
    row = seq * lanes * q.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_bwd_fused_bsh_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, seq=seq, d=d),
        grid=(b * blocks, seq // block_k),
        in_specs=[
            pl.BlockSpec((1, seq, lanes), whole),
            pl.BlockSpec((1, block_k, lanes), tile),
            pl.BlockSpec((1, block_k, lanes), tile),
            pl.BlockSpec((1, seq, lanes), whole),
            pl.BlockSpec((1, 1, heads, seq), stat_whole),
            pl.BlockSpec((1, 1, heads, seq), stat_whole),
        ],
        out_specs=[
            pl.BlockSpec((1, seq, lanes), whole),
            pl.BlockSpec((1, block_k, lanes), tile),
            pl.BlockSpec((1, block_k, lanes), tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((seq, lanes), jnp.float32)],
        interpret=_device.pallas_interpret(),
        name="pfx_flash_bwd_fused_bsh",
        # ``_flash_bwd_fused``'s account with a block of whole heads where a
        # padded head stood; lse and delta are ``heads`` rows, 8 sublanes
        **_compiler_params(3 * row + 2 * 8 * seq * 4 + seq * lanes * 2),
    )(q, k, v, do, lse, delta)


# The two schedules alone on a v5e (PR 52: batch x heads 256, bfloat16, the
# ladder's 512 tile, no window, equal head counts; 8 calls of ``_flash_bwd``
# chained in one jit, ms a call: the kernels' self time from a trace of 5
# chains, and in brackets a call by the host's clock over 20 chains, which
# also pays for what reads dq):
#
#   head  seq    split = dq + dkv          [host]    fused   [host]   fused/split
#   64    512    0.921 = 0.424 + 0.497     [1.127]   0.588   [0.805]  0.64
#   64    1024   2.993 = 1.123 + 1.870     [3.497]   2.049   [2.564]  0.68
#   64    2048   9.025 = 3.455 + 5.570     [10.21]   6.630   [7.809]  0.73
#   64    4096   29.62 = 11.77 + 17.85     [32.01]   21.42   [23.80]  0.72
#   128   512    0.916 = 0.417 + 0.499     [1.085]   0.575   [0.744]  0.63
#   128   1024   2.978 = 1.119 + 1.859     [3.367]   2.067   [2.457]  0.69
#   128   2048   8.969 = 3.440 + 5.529     [9.896]   6.619   [7.540]  0.74
#   128   4096   29.44 = 11.72 + 17.72     [31.29]   21.40   [23.24]  0.73
#
# (With dq leaving the kernel in float32 and cast outside, as before PR 52:
# 2.079 [2.680] at 64 x 1024, 2.081 [2.586] at 128 x 1024, and 4096 did not
# fit the default scoped VMEM.)  The gradients of the two schedules were equal
# to the bit at every shape.  head_dim -> the longest sequence measured:
_FUSED_MAX_SEQ = {64: 4096, 128: 4096}


def _bwd_schedule(seq: int, d: int, window: int, group: int) -> str:
    """The backward schedule of a call, from its static shapes: the one place
    it is chosen.  ``fused`` where it can run (no window, no shared KV
    heads) and was measured ahead (the table above: its head sizes, the
    512 tile, up to its longest sequence); ``split`` for everything else,
    until somebody measures it."""
    if window or group > 1:
        return "split"
    measured = seq % 512 == 0 and seq <= _FUSED_MAX_SEQ.get(d, 0)
    return "fused" if measured else "split"


# The two layouts alone on a v5e (PR 56: batch 16 x 16 heads, bfloat16, the
# ladder's 512 tile, no window, equal head counts, the fused backward; 8 calls
# chained in one jit FROM AND TO [b, s, n, d], so each path pays for the layout
# changes it needs, the backward for delta too; ms a call: the kernel's self
# time + every other device op's from a trace of 5 chains, and in brackets a
# call by the host's clock over 20 chains):
#
#   head  seq    forward  bh               bsh                backward  bh              bsh
#   64    512    0.386 + 0.172  [0.565]   0.390 + 0.060  [0.457]    0.601 + 0.448  [1.056]   0.587 + 0.141  [0.736]
#   64    1024   1.181 + 0.499  [1.687]   1.183 + 0.134  [1.326]    2.134 + 1.018  [3.162]   1.751 + 0.332  [2.092]
#   64    2048   3.406 + 1.297  [4.711]   3.374 + 0.729  [4.110]    6.633 + 2.275  [8.919]   5.471 + 1.079  [6.560]
#   64    4096   10.998 + 2.819 [13.82]   10.864 + 1.799 [12.67]    21.421 + 4.808 [26.24]   18.841 + 2.447 [21.30]
#   128   512    0.392 + 0.260  [0.659]   0.420 + 0.104  [0.533]    0.592 + 0.614  [1.215]   0.609 + 0.313  [0.928]
#   128   1024   1.177 + 0.724  [1.910]   1.218 + 0.722  [1.948]    2.118 + 1.354  [3.481]   1.826 + 1.059  [2.894]
#   128   2048   3.382 + 1.598  [4.989]   3.488 + 1.792  [5.288]    6.638 + 2.893  [9.541]   5.784 + 2.632  [8.414]
#   128   4096   10.921 + 3.156 [14.09]   11.216 + 3.582 [14.81]    21.397 + 5.749 [27.16]   20.240 + 5.248 [25.50]
#
# Result, lse and all three gradients of one call were equal to the bit between
# the layouts at every shape.  At head 64 ``bsh`` is ahead everywhere, forward
# by what the transposes cost and backward by the ``k q^T`` tile too (2.134 ->
# 1.751 at 1,024).  A head taken out of its block by a lane slice at offset 64
# instead of zeroed lanes: the forward kernel 0.425 / 1.258 / 3.565 / 11.515,
# the backward 0.643 / 1.870 / 5.787 / 19.782, 6-9% behind.  delta summed
# inside the backward kernel from dO's and the result's blocks: the kernel
# 0.649 / 1.846 / 5.649 / 19.354 for 0.03-0.14 less outside it, even by the
# host's clock (0.768 / 2.104 / 6.639 / 21.67) and 1-4e-3 off the old path's
# gradients: outside stays.  At head 128 one head fills a block: nothing to
# separate, XLA's transposes of a 128-wide minor dimension are cheap, and the
# forward is 2-6% BEHIND by the host's clock; the backward's 6-24% is the tile's
# form, which the ``bh`` kernel could take as it stands.  Not entered (it would
# also move the 1.3B cell's prefill programs).
# head_dim -> the sequences at which ``bsh`` was measured ahead:
_BSH_SEQS = {64: (512, 1024, 2048, 4096)}


def _operand_layout(seq: int, heads: int, d: int, window: int, group: int, dtype,
                    tile: int = 0) -> str:
    """The layout a call's kernels are handed, from its static shapes (one
    shard's ``heads`` under a mesh): the one place it is chosen.  ``bsh``
    (the model's own [batch, seq, heads*d], a 128-lane block of whole heads
    a grid row: no transpose in front of a kernel or behind it) where it can
    run (whole heads fill whole blocks, no window, no shared KV heads, the
    fused backward) and was measured ahead; ``bh`` for everything else.
    ``tile``: a caller's own tile (0 = the ladder's), which stays on ``bh``
    unless it is the ladder's: the table was measured at that one, and the
    statistics' block has the tile in the LANES, where Mosaic takes a
    multiple of 128 or the whole sequence and nothing else."""
    if window or group > 1 or _bwd_schedule(seq, d, window, group) != "fused":
        return "bh"
    if _LANES % d or (heads * d) % _LANES or jnp.dtype(dtype) != jnp.bfloat16:
        return "bh"
    if tile and tile != _block_sizes(seq)[0]:
        return "bh"
    return "bsh" if seq in _BSH_SEQS.get(d, ()) else "bh"


def _flash_bwd(q, k, v, do, lse, delta, scale, block, bwd_mode, window, group):
    """dq, dk, dv in the kernels' layout; ``delta`` [bh, seq, 1] is the row
    sum of dO * O."""
    bh, seq, d = q.shape
    block_q, block_k = block  # static (bq, bk) tuple
    if bwd_mode not in ("split", "fused"):
        raise ValueError(f"flash bwd schedule {bwd_mode!r}; valid: split, fused")

    if bwd_mode == "fused":
        if window or group > 1:
            raise NotImplementedError(
                "the fused flash backward knows neither a window nor shared KV "
                "heads; _bwd_schedule chooses 'split' for them")
        return _flash_bwd_fused(q, k, v, do, lse, delta, scale, block_q, block_k)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, block_q=block_q, block_k=block_k, window=window
        ),
        grid=(bh, seq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, seq, d), _kv_map(group)),
            pl.BlockSpec((1, seq, d), _kv_map(group)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_device.pallas_interpret(),
        name="pfx_flash_bwd_dq",
        **_compiler_params(2 * seq * d * k.dtype.itemsize),
    )(q, k, v, do, lse, delta)

    # shared KV heads: one grid row per QUERY head, so that Q and dO stay
    # resident while the KV blocks sweep; each writes its own float32 dk/dv
    # and the ``group`` rows of a KV head are added up outside
    kv_block = ((lambda i, j: (i, j, 0)) if group == 1
                else (lambda i, j: (i // group, j, 0)))
    dkv_dtype = (k.dtype, v.dtype) if group == 1 else (jnp.float32, jnp.float32)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, block_q=block_q, block_k=block_k, seq=seq,
            window=window,
        ),
        grid=(bh, seq // block_k),
        in_specs=[
            pl.BlockSpec((1, seq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, seq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, seq, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, seq, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), dkv_dtype[0]),
            jax.ShapeDtypeStruct((bh, seq, d), dkv_dtype[1]),
        ],
        interpret=_device.pallas_interpret(),
        name="pfx_flash_bwd_dkv",
        # q and dO whole, and lse and delta whole: a [seq, 1] float32
        # column takes a 128-lane tile per 8 rows in VMEM
        **_compiler_params(2 * seq * d * q.dtype.itemsize + 2 * seq * 128 * 4),
    )(q, k, v, do, lse, delta)
    if group > 1:
        dk = dk.reshape(bh // group, group, seq, d).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(bh // group, group, seq, d).sum(axis=1).astype(v.dtype)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


def _to_bh(x):
    """[batch, seq, heads, head_dim] -> the kernels' [batch*heads, seq, head_dim]."""
    b, s, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * n, s, d)


def _from_bh(x, batch):
    """``_to_bh``'s inverse for a batch of ``batch``."""
    bh, s, d = x.shape
    return x.reshape(batch, bh // batch, s, d).transpose(0, 2, 1, 3)


def _flash_model_layout(q, k, v, scale, block, window, layout):
    """The forward of either layout on q, k, v [batch, seq, heads, head_dim]:
    the result in that shape and lse as the layout's backward wants it."""
    b, s, n, d = q.shape
    if layout == "bsh":
        if window or k.shape[2] != n:
            raise NotImplementedError(
                "the model's layout knows neither a window nor shared KV heads; "
                "_operand_layout names it for neither")
        out, lse = _flash_fwd_bsh(*(x.reshape(b, s, n * d) for x in (q, k, v)), scale, block, d)
        return out.reshape(b, s, n, d), lse
    out, lse = _flash_fwd(_to_bh(q), _to_bh(k), _to_bh(v), scale, block, window,
                          n // k.shape[2])
    return _from_bh(out, b), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bsnd(q, k, v, scale, block, bwd_mode, window=0, layout="bh"):
    """q, k, v and the result in the MODEL's layout; the differentiation rule
    sits around the layout changes so that what it saves is lane-dense.
    ``layout``: what the kernels are handed, ``_operand_layout``'s choice
    ("bh": [batch*heads, seq, head_dim], transposed to and from; "bsh": the
    model's own with the heads folded into the minor dimension)."""
    return _flash_model_layout(q, k, v, scale, block, window, layout)[0]


def _flash_bsnd_fwd(q, k, v, scale, block, bwd_mode, window=0, layout="bh"):
    out, lse = _flash_model_layout(q, k, v, scale, block, window, layout)
    # Both results carry a name so a selective-remat policy keeps them
    # ("attn_out" is what the XLA and ring paths call theirs): with either
    # one unsaved the backward re-runs the whole forward kernel to have it
    # back.  The output is named in the model's layout with the heads folded
    # into the minor dimension: the kernels' [bh, seq, 64] is padded to 128
    # lanes wherever it is kept, and the backward wants the output only for
    # delta, a sum over head_dim that it can take in any layout.  On the
    # 345M cell (v5e, batch 16 x 1024, PR 49; tokens/s/chip and a step,
    # five pairs at five seeds each): parent 32,223-32,298, 0.5050 s; the
    # kernels' own output named 33,269-33,319, 0.4897 s (the re-run was
    # 26.4 ms a step, the padded copy gave 11 back); this 34,256-34,280,
    # 0.4757 s.  Under "full" recompute nothing is saved and the re-run
    # stays, which is what "full" means.
    b, s, n, d = out.shape
    out = checkpoint_name(out.reshape(b, s, n * d), "attn_out").reshape(b, s, n, d)
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _flash_bsnd_bwd(scale, block, bwd_mode, window, layout, res, g):
    q, k, v, out, lse = res
    b, s, n, d = q.shape
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [b, s, n]
    if layout == "bsh":
        if bwd_mode != "fused":
            raise NotImplementedError(
                "the model's layout has the fused flash backward alone; "
                "_operand_layout names it only where _bwd_schedule says 'fused'")
        fold = lambda x: x.reshape(b, s, n * d)
        grads = _flash_bwd_fused_bsh(fold(q), fold(k), fold(v), fold(g), lse,
                                     delta.transpose(0, 2, 1).reshape(lse.shape), scale, block, d)
        return tuple(x.reshape(b, s, n, d) for x in grads)
    delta = delta.transpose(0, 2, 1).reshape(b * n, s, 1)
    dq, dk, dv = _flash_bwd(_to_bh(q), _to_bh(k), _to_bh(v), _to_bh(g), lse, delta,
                            scale, block, bwd_mode, window, n // k.shape[2])
    return _from_bh(dq, b), _from_bh(dk, b), _from_bh(dv, b)


_flash_bsnd.defvjp(_flash_bsnd_fwd, _flash_bsnd_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block: int = 0,
    window: int = 0,
):
    """q: [batch, seq, heads, head_dim]; k, v: the same, or with fewer heads
    (a divisor of q's: KV head h serves query heads g*h .. g*h+g-1)
    -> [batch, seq, heads, head_dim].  ``window`` > 0: position i sees
    positions i-window+1 .. i only.

    ``block`` (0 = the ladder of ``_block_sizes``): a caller's own square
    tile, for a test that wants several blocks of a short sequence.  The
    backward's schedule follows from the shapes (``_bwd_schedule``), and so
    does the layout the kernels are handed (``_operand_layout``: the
    model's own, with no transpose around a kernel, at the head sizes and
    sequences it was measured ahead at; a caller's own tile stays on the
    transposed one)."""
    if not causal:
        raise NotImplementedError("only causal flash attention")
    _, s, n, d = q.shape
    n_kv = k.shape[2]
    if n % n_kv or v.shape != k.shape:
        raise ValueError(f"{n} query heads over K {k.shape} / V {v.shape}")
    window = 0 if not window or window >= s else int(window)
    bq, bk = _block_sizes(s, block)
    if s % bq or s % bk:
        raise ValueError(
            f"flash_attention needs seq divisible by block size {bq}, got {s}; "
            "pad the sequence or use attn_impl='xla'"
        )
    scale = float(1.0 / (d**0.5))
    group = n // n_kv
    return _flash_bsnd(q, k, v, scale, (bq, bk), _bwd_schedule(s, d, window, group), window,
                       _operand_layout(s, n, d, window, group, q.dtype, bq))


def flash_supported(seq: int) -> bool:
    """True when ``_block_sizes``' tiling divides ``seq`` (dispatch helper)."""
    bq, bk = _block_sizes(seq)
    return seq % bq == 0 and seq % bk == 0
