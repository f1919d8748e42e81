"""A grouped matrix product for few rows a group: the serving prefill's experts.

``x`` [rows, k] holds (token, expert) pairs sorted by expert: the first
``group_sizes[0]`` rows are group 0's, the next ``group_sizes[1]`` group
1's, and the rows past ``sum(group_sizes)`` are nobody's.  ``w`` [groups, k,
n] is one matrix a group, and the result's row ``r`` is ``x[r] @ w[group of
r]``: what ``jax.lax.ragged_dot`` computes.

A serving prefill brings 10-100 rows a group to matrices of 10-30 MB, so
the product is bound by reading the matrices, and the kernel
(``pfx_grouped_matmul``) is built around reading each ONCE, where it lies:

- the grid walks VISITS, one for each (row tile, group) pair that shares a
  row, in the order of the rows.  Their lists are made from the group sizes
  outside the kernel and scalar-prefetched; the grid's bound is their count,
  so a row tile past the held pairs runs no step and an empty group none;
- a visit multiplies its row tile by a column block ``[k, tn]`` of its
  group's matrix, read from ``w`` as the caller stores it (the block address
  carries the group: no operand is copied or padded), and writes the rows
  that are the group's; consecutive visits of one row tile keep the result's
  block in fast memory, consecutive visits of one group its matrix block;
- the contracted dimension is never cut, so a ``k`` that is no multiple of a
  lane tile is the block's full dimension; an ``n`` that is none is masked
  by the block's edge.

Rows of no group come back as whatever the result's buffer held: the caller
cuts them (``models/gpt/moe.py`` does, before and after).  Forward only: the
training step's grouped products need the two transposed products as well
and stay with XLA's ``ragged_dot`` (docs/nemotron_h.md).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddlefleetx_tpu.utils import device as _device

# rows a visit multiplies: a group of a prefill holds 10-100, and a visit
# costs its matrix block's read whatever its rows, so a taller tile only
# adds products of rows that are other groups'
_ROW_TILE = 128
# bytes of one matrix block [k, tn]: two of them in flight beside the row
# tile and the result's block.  On the v5e 2 and 4 MB read alike and 8 MB a
# fifth slower (a product of 16 x 2688 x 1856: 0.243 / 0.242 / 0.293 ms; my
# chip run, PR 38); wider blocks mean fewer passes over the row tiles
_BLOCK_BYTES = 4 << 20
_VMEM_LIMIT = 32 << 20


def _tiles(rows: int, k: int, n: int, itemsize: int) -> Tuple[int, int]:
    """(tm, tn) from the static shapes: a row tile of 128 (all rows of a
    smaller buffer), and the widest column block of whole lane tiles that
    keeps ``[k, tn]`` inside :data:`_BLOCK_BYTES` (all of ``n`` if it fits)."""
    tm = rows if rows < _ROW_TILE else _ROW_TILE
    tn = max(_BLOCK_BYTES // (k * itemsize) // 128, 1) * 128
    return tm, n if tn >= n else tn


def visits(group_sizes: jax.Array, rows: int, tm: int):
    """The (row tile, group) pairs that share a row, in the rows' order ->
    (group of each visit, row tile of each visit, the groups' first rows and
    the end of the last [groups + 1], the number of visits [1]); the lists
    have the static worst case's length, ``tiles + groups - 1``."""
    groups, tiles = group_sizes.shape[0], -(-rows // tm)
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    # tiles a group touches: none if it is empty
    span = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(span)
    v = jax.lax.iota(jnp.int32, tiles + groups - 1)
    # the group of visit v: how many groups' visits end at or before it
    group = jnp.minimum(jnp.sum(v[:, None] >= upto[None, :], axis=1, dtype=jnp.int32), groups - 1)
    tile = first[group] + v - (upto - span)[group]
    offsets = jnp.concatenate([starts[:1], ends])
    return group, jnp.clip(tile, 0, tiles - 1), offsets, upto[-1:]


def _kernel(group_ref, tile_ref, offsets_ref, x_ref, w_ref, out_ref, *, tm, k_minor):
    v = pl.program_id(1)
    g = group_ref[v]
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    y = jax.lax.dot_general(x_ref[...], w_ref[...], (((1,), (1 if k_minor else 0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # the other rows of the tile are other groups': theirs is written by
    # their own visit, before or after this one
    out_ref[...] = jnp.where(mine, y.astype(out_ref.dtype), out_ref[...])


# jitted: a prefill program's 23 or 6 call sites of one shape are traced and
# lowered to Mosaic once (1.5 s of lowering a program otherwise, set-up time)
@functools.partial(jax.jit, static_argnames="interpret")
def _grouped_pallas(x, w, group_sizes, *, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    n = w.shape[2]
    tm, tn = _tiles(rows, k, n, w.dtype.itemsize)
    group, tile, offsets, count = visits(group_sizes, rows, tm)
    # the matrices as the chip holds them: the TPU keeps an array whose last
    # dimension is no multiple of 128 lanes with the one before it minor, if
    # that one is a multiple (bf16[16, 2688, 1856] lies as [16, 1856, 2688];
    # tests/test_chip_compile.py holds the compiled prefills to no copy of
    # one), so this swap is a renaming and the product contracts both minors
    k_minor = n % 128 != 0 and k % 128 == 0
    if k_minor:
        w = jnp.swapaxes(w, 1, 2)
        w_spec = pl.BlockSpec((None, tn, k), lambda j, v, group, tile, _: (group[v], j, 0))
    else:
        w_spec = pl.BlockSpec((None, k, tn), lambda j, v, group, tile, _: (group[v], 0, j))
    # the column block is the OUTER axis: inside it consecutive visits of a
    # group find its matrix block where the last one left it
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, k_minor=k_minor),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(-(-n // tn), count[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, group, tile, _: (tile[v], 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, group, tile, _: (tile[v], j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="pfx_grouped_matmul",
    )(group, tile, offsets, x, w)


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                   impl: str = "auto") -> jax.Array:
    """``x`` [rows, k] sorted by group, ``w`` [groups, k, n], ``group_sizes``
    [groups] int -> [rows, n] in ``x``'s dtype, accumulated in float32: row
    ``r`` of group ``g`` is ``x[r] @ w[g]``.  Rows past the groups' sum are
    NOT defined (cut them).  Forward only.  ``impl``: "auto" (Pallas
    ``pfx_grouped_matmul`` on a TPU, ``jax.lax.ragged_dot`` on the CPU) |
    "pallas" | "lax"."""
    if impl not in ("auto", "pallas", "lax"):
        raise ValueError(f"grouped_matmul impl {impl!r}; valid: auto, pallas, lax")
    if x.ndim != 2 or w.ndim != 3 or w.shape[1] != x.shape[1] or group_sizes.shape != w.shape[:1]:
        raise ValueError(f"grouped_matmul: x {x.shape} against w {w.shape} in groups of "
                         f"{group_sizes.shape}: want [rows, k], [groups, k, n], [groups]")
    if w.dtype != x.dtype:
        raise ValueError(f"grouped_matmul: w is {w.dtype}, x {x.dtype}: the matrices are "
                         "read as they are stored, so cast the tree, not the operand")
    interpret = _device.pallas_interpret()
    if impl == "pallas" or (impl == "auto" and not interpret):
        return _grouped_pallas(x, w, group_sizes, interpret=interpret)
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32).astype(x.dtype)
