"""LayerNorm (+ optional residual add) — Pallas TPU kernel.

TPU-native replacement for the reference's fused norm ops (paddle
``FusedMultiHeadAttention``/``FusedFeedForward`` pre/post-LN fusions the
models consume, e.g. vit.py:23-115 FusedBlock; SURVEY §7.1 "fused
LN(+residual)"): one VMEM pass computes mean/rstd in float32 and writes the
normalized output in x's dtype, optionally fusing a residual add in front.

Custom VJP: the residuals are the inputs alone.  The backward reads a row
block of x once, computes mean / rstd again (the same float32 arithmetic
as the forward: the row is in VMEM anyway) and reduces dscale / dbias in
float32 across the sequential grid — matches jax.grad of the naive form to
fp32 accuracy.  Until PR 54 the forward also wrote mean / rstd as two
``(rows, 1)`` float32 columns for the backward to read: a lane of 1 is
padded to 128, 8 MB a column at 16,384 rows, and the pair cost the backward
more than the recompute does (the table beside
``models/gpt/model._norm_schedule``).  On non-TPU platforms the kernel runs
in Pallas interpret mode so the CPU-mesh test suite exercises the same code
path.

API: ``fused_layer_norm(x, scale, bias, residual=None, eps=1e-5)`` over the
last dim.  Who calls it: ``models/gpt/model.layer_norm``, where its rule
(``_norm_schedule``) names the kernel for the call's shapes, without a
residual (no model call site fuses its add); the tests and
``chip_smoke.py`` hold both forms to the jnp composite.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddlefleetx_tpu.utils import device as _device


def _row_block(n_rows: int, row_bytes: int) -> int:
    """Rows a grid step: the largest rung that divides the rows and keeps a
    block of the inputs (x, and the residual beside it) within 1 MiB, 512
    rows of 1,024 in bfloat16 (measured ahead of 256 and 128 at every shape
    of the rule's table; 2 MiB is refused by the backward for scoped VMEM)."""
    for b in (512, 256, 128, 64, 32, 16, 8, 4, 2):
        if n_rows % b == 0 and b * row_bytes <= 2 ** 20:
            return b
    return 1


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _normalize(x_ref, res_ref, has_res, eps):
    """(xhat, rstd) of a row block in float32."""
    x = x_ref[...].astype(jnp.float32)
    if has_res:
        x = x + res_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    return (x - mean) * rstd, rstd


def _fwd_kernel(x_ref, res_ref, scale_ref, bias_ref, o_ref, *, eps, has_res):
    xhat, _ = _normalize(x_ref, res_ref, has_res, eps)
    y = xhat * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _bwd_kernel(x_ref, res_ref, scale_ref, g_ref, dx_ref, dscale_ref, dbias_ref, *, eps, has_res):
    xhat, rstd = _normalize(x_ref, res_ref, has_res, eps)
    g = g_ref[...].astype(jnp.float32)
    gs = g * scale_ref[...].astype(jnp.float32)
    # dx = rstd * (gs - mean(gs) - xhat * mean(gs * xhat))
    m1 = jnp.mean(gs, axis=-1, keepdims=True)
    m2 = jnp.mean(gs * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (gs - m1 - xhat * m2)).astype(dx_ref.dtype)
    # dscale/dbias: accumulate across the sequential TPU grid into one
    # (n,)-shaped output block (block == array dims satisfies tiling)
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    dscale_ref[...] += jnp.sum(g * xhat, axis=0)
    dbias_ref[...] += jnp.sum(g, axis=0)


# ---------------------------------------------------------------------------
# Entry + VJP
# ---------------------------------------------------------------------------


def _specs(x2, has_res):
    """(grid, the spec of a [rows, n] operand, of the residual's slot, of an
    [n] operand)."""
    rows, n = x2.shape
    bq = _row_block(rows, n * x2.dtype.itemsize * (2 if has_res else 1))
    row = pl.BlockSpec((bq, n), lambda i: (i, 0))
    res = row if has_res else pl.BlockSpec((1, n), lambda i: (0, 0))
    return (rows // bq,), row, res, pl.BlockSpec((n,), lambda i: (0,))


def _res_arg(x2, res2, has_res):
    return res2 if has_res else jnp.zeros((1, x2.shape[1]), x2.dtype)


def _run_fwd(x2, res2, scale, bias, eps, has_res):
    grid, row, res, vec = _specs(x2, has_res)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, has_res=has_res),
        grid=grid,
        in_specs=[row, res, vec, vec],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=_device.pallas_interpret(),
        name="pfx_ln_fwd",
    )(x2, _res_arg(x2, res2, has_res), scale, bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_ln(x2, res2, scale, bias, eps, has_res):
    return _run_fwd(x2, res2, scale, bias, eps, has_res)


def _fused_ln_fwd(x2, res2, scale, bias, eps, has_res):
    return _run_fwd(x2, res2, scale, bias, eps, has_res), (x2, res2, scale)


def _fused_ln_bwd(eps, has_res, saved, g):
    x2, res2, scale = saved
    n = x2.shape[1]
    grid, row, res, vec = _specs(x2, has_res)
    dx, dscale_p, dbias_p = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, has_res=has_res),
        grid=grid,
        in_specs=[row, res, vec, row],
        out_specs=(row, vec, vec),
        out_shape=(
            jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ),
        interpret=_device.pallas_interpret(),
        name="pfx_ln_bwd",
    )(x2, _res_arg(x2, res2, has_res), scale, g)
    dscale = dscale_p.astype(scale.dtype)
    dbias = dbias_p.astype(scale.dtype)
    dres = dx if has_res else None
    return dx, dres, dscale, dbias


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    residual: Optional[jax.Array] = None,
    eps: float = 1e-5,
) -> jax.Array:
    """LayerNorm over the last dim, fusing an optional residual add."""
    shape = x.shape
    n = shape[-1]
    x2 = x.reshape(-1, n)
    res2 = residual.reshape(-1, n) if residual is not None else x2  # dummy when unused
    out = _fused_ln(x2, res2, scale, bias, eps, residual is not None)
    return out.reshape(shape)
