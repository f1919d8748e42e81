"""Causal flash attention — Pallas TPU kernel with custom VJP.

TPU-native replacement for the reference's fused attention path (fused
softmax-mask-triu in ``core_attn`` single_model.py:83-200 and the
``flash_attention`` hook hybrid_model.py:284-301): online-softmax tiling so
the [s, s] score matrix never materialises in HBM.

Layout: inputs [batch, seq, heads, head_dim] (model layout), kernels run on
[batch*heads, seq, head_dim]; the differentiation rule (``_flash_bsnd``)
sits around the layout changes, so its residuals are in the model's layout.
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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_q, block_k, window=0):
    # MXU dots run in the INPUT dtype (bf16 on the model path) with fp32
    # accumulation via preferred_element_type — upcasting the operands to
    # fp32 first quarters MXU throughput (measured: the kernel pair sat at
    # 19% intra-kernel efficiency in the 03:17Z op table).  Softmax
    # statistics, rescaling, and the output accumulator stay fp32.
    qi = pl.program_id(1)
    q = q_ref[0]  # [bq, d], native dtype
    d = q.shape[-1]

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    row_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(j * block_k, block_k), :]
        v = v_ref[0, pl.dslice(j * block_k, block_k), :]
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
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse carried as [bh, seq, 1]: TPU tiling wants the trailing block dims
    # divisible by (8, 128) or equal to the array dims — a lane dim of 1
    # satisfies the latter for this per-row scalar
    lse_ref[0, :, 0] = m + jnp.log(l_safe)


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


def _bwd_tile(q, k, v, do, lse, delta, row_ids, col_ids, scale, window=0):
    """Shared per-(q-block, kv-block) backward tile math: recompute the
    masked softmax block from the saved lse and form ds.  Used by BOTH the
    split _dkv_kernel and the fused kernel so the mask/scaling can never
    diverge between schedules.  Returns (p_lo, ds) in the input dtype;
    dots accumulate fp32."""
    s = scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    p = jnp.where(_visible(row_ids, col_ids, window), jnp.exp(s - lse[:, None]), 0.0)
    dov = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = (p * (dov - delta[:, None]) * scale).astype(q.dtype)
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


def _flash_model_layout(q, k, v, scale, block, window):
    out, lse = _flash_fwd(_to_bh(q), _to_bh(k), _to_bh(v), scale, block, window,
                          q.shape[2] // k.shape[2])
    return _from_bh(out, q.shape[0]), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bsnd(q, k, v, scale, block, bwd_mode, window=0):
    """q, k, v and the result in the MODEL's layout; the differentiation rule
    sits around the layout changes so that what it saves is lane-dense."""
    return _flash_model_layout(q, k, v, scale, block, window)[0]


def _flash_bsnd_fwd(q, k, v, scale, block, bwd_mode, window=0):
    out, lse = _flash_model_layout(q, k, v, scale, block, window)
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


def _flash_bsnd_bwd(scale, block, bwd_mode, window, res, g):
    q, k, v, out, lse = res
    b, s, n, d = q.shape
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [b, s, n]
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
    backward's schedule follows from the shapes (``_bwd_schedule``)."""
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
    return _flash_bsnd(q, k, v, scale, (bq, bk), _bwd_schedule(s, d, window, n // n_kv), window)


def flash_supported(seq: int) -> bool:
    """True when ``_block_sizes``' tiling divides ``seq`` (dispatch helper)."""
    bq, bk = _block_sizes(seq)
    return seq % bq == 0 and seq % bk == 0
