"""Fused LayerNorm (+ optional residual add) — Pallas TPU kernel.

TPU-native replacement for the reference's fused norm ops (paddle
``FusedMultiHeadAttention``/``FusedFeedForward`` pre/post-LN fusions the
models consume, e.g. vit.py:23-115 FusedBlock; SURVEY §7.1 "fused
LN(+residual)"): one VMEM pass computes mean/rstd and writes the
normalized output, fusing the residual add that usually precedes the
norm — instead of three HBM round-trips (add, stats, scale).

Custom VJP: the backward recomputes xhat from saved (mean, rstd) and
reduces dscale/dbias on the fly — matches jax.grad of the naive form to
fp32 accuracy.  On non-TPU platforms the kernel runs in Pallas interpret
mode so the CPU-mesh test suite exercises the same code path.

API: ``fused_layer_norm(x, scale, bias, residual=None, eps=1e-5)`` over
the last dim; used as a drop-in for models' ``layer_norm(x + y, ...)``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddlefleetx_tpu.utils import device as _device



def _row_block(n_rows: int) -> int:
    for b in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if n_rows % b == 0:
            return b
    return 1


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, res_ref, scale_ref, bias_ref, o_ref, mean_ref, rstd_ref, *, eps, has_res):
    x = x_ref[...].astype(jnp.float32)
    if has_res:
        x = x + res_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)
    # (bq, 1) lane-1 blocks: TPU tiling wants the last dim equal to the
    # array dim, same trick as the flash kernel's lse carry
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, res_ref, scale_ref, mean_ref, rstd_ref, g_ref,
                dx_ref, dscale_ref, dbias_ref, *, has_res):
    x = x_ref[...].astype(jnp.float32)
    if has_res:
        x = x + res_ref[...].astype(jnp.float32)
    mean = mean_ref[...]  # (bq, 1)
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    g = g_ref[...].astype(jnp.float32)
    scale = scale_ref[...].astype(jnp.float32)
    n = x.shape[-1]
    gs = g * scale
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

    dscale_ref[...] += jnp.sum(g * xhat, axis=tuple(range(g.ndim - 1)))
    dbias_ref[...] += jnp.sum(g, axis=tuple(range(g.ndim - 1)))


# ---------------------------------------------------------------------------
# Entry + VJP
# ---------------------------------------------------------------------------


def _run_fwd(x2, res2, scale, bias, eps):
    rows, n = x2.shape
    bq = _row_block(rows)
    has_res = res2 is not None
    args = (x2,) + ((res2,) if has_res else (jnp.zeros((1, n), x2.dtype),)) + (scale, bias)
    in_specs = [
        pl.BlockSpec((bq, n), lambda i: (i, 0)),
        pl.BlockSpec((bq, n), lambda i: (i, 0)) if has_res else pl.BlockSpec((1, n), lambda i: (0, 0)),
        pl.BlockSpec((n,), lambda i: (0,)),
        pl.BlockSpec((n,), lambda i: (0,)),
    ]
    out_shapes = (
        jax.ShapeDtypeStruct((rows, n), x2.dtype),
        jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        jax.ShapeDtypeStruct((rows, 1), jnp.float32),
    )
    out_specs = (
        pl.BlockSpec((bq, n), lambda i: (i, 0)),
        pl.BlockSpec((bq, 1), lambda i: (i, 0)),
        pl.BlockSpec((bq, 1), lambda i: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, has_res=has_res),
        grid=(rows // bq,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=_device.pallas_interpret(),
        name="pfx_ln_fwd",
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_ln(x2, res2, scale, bias, eps, has_res):
    y, _, _ = _run_fwd(x2, res2 if has_res else None, scale, bias, eps)
    return y


def _fused_ln_fwd(x2, res2, scale, bias, eps, has_res):
    y, mean, rstd = _run_fwd(x2, res2 if has_res else None, scale, bias, eps)
    return y, (x2, res2, scale, mean, rstd)


def _fused_ln_bwd(eps, has_res, saved, g):
    x2, res2, scale, mean, rstd = saved
    rows, n = x2.shape
    bq = _row_block(rows)
    args = (
        x2,
        res2 if has_res else jnp.zeros((1, n), x2.dtype),
        scale, mean, rstd, g,
    )
    in_specs = [
        pl.BlockSpec((bq, n), lambda i: (i, 0)),
        pl.BlockSpec((bq, n), lambda i: (i, 0)) if has_res else pl.BlockSpec((1, n), lambda i: (0, 0)),
        pl.BlockSpec((n,), lambda i: (0,)),
        pl.BlockSpec((bq, 1), lambda i: (i, 0)),
        pl.BlockSpec((bq, 1), lambda i: (i, 0)),
        pl.BlockSpec((bq, n), lambda i: (i, 0)),
    ]
    out_shapes = (
        jax.ShapeDtypeStruct((rows, n), x2.dtype),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
    )
    out_specs = (
        pl.BlockSpec((bq, n), lambda i: (i, 0)),
        pl.BlockSpec((n,), lambda i: (0,)),
        pl.BlockSpec((n,), lambda i: (0,)),
    )
    dx, dscale_p, dbias_p = pl.pallas_call(
        functools.partial(_bwd_kernel, has_res=has_res),
        grid=(rows // bq,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=_device.pallas_interpret(),
        name="pfx_ln_bwd",
    )(*args)
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
