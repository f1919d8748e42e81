"""A residual stream of several copies, mixed by maps computed from the stream
itself: manifold-constrained hyper-connections (docs/xing4.md has the
equations; ``GPTConfig.hc_mult`` = n copies of width C a token).

A sub-block ``F`` (attention or feed-forward, norm included) no longer reads
``x`` and adds to it.  From the stream ``X`` [n, C] of a token it takes

    v      = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)            [nC], float32
    z      = Phi v                                                [n + n + n^2]
    h_pre  = sigmoid(a_pre z[:n] + b[:n])                         in (0, 1)
    h_post = 2 sigmoid(a_post z[n:2n] + b[n:2n])                  in (0, 2)
    M      = exp(clip(a_res mat(z[2n:]) + mat(b[2n:]), -c, c))    [n, n]
    iters times:  M <- M / (column sums + eps);  M <- M / (row sums + eps)
    u      = sum_i h_pre[i] X_i                                   what F reads
    X'_i   = sum_j M[i, j] X_j + h_post[i] F(u)                   what it leaves

:func:`hc_pre` gives ``u`` and the maps (``[h_pre | h_post | M row-major]``,
float32, a row a token), :func:`hc_post` the new stream.  The maps' arithmetic
is float32 with the product at HIGHEST precision whatever the stream's dtype:
``M`` goes through ``exp`` and forty normalisations.

Left to XLA a sub-block is several passes over ``[tokens, n, C]`` and forty
dependent n x n normalisations, so each half is ONE Pallas kernel that reads
the stream once, ``pfx_hc_pre`` and ``pfx_hc_post``, beside the plain form it
is held to (``hc_pre_xla`` / ``hc_post_xla``; tests/test_xing4_block.py):

- the stream is handed over as ``[tokens, n * C]`` (a renaming) and a grid
  step takes a tile of whole tokens; copy ``i`` is the lanes ``[i C, (i + 1) C)``;
- the maps are computed with the TOKENS ON THE LANES (``z^T = Phi X^T``, the
  product's natural form; the sum of squares rides the same pass as a product
  with ones), so the Sinkhorn rounds run on ``[n, tile]`` slabs, a vector
  register each, and not on one lane of a register a token; the finished
  maps are turned once (a product with the identity, exact at HIGHEST) into a
  row a token, which is what the two mixes broadcast along C;
- both mixes run on the VPU in float32, a chunk of lanes at a time.

:func:`_schedule` chooses between kernel and plain form from the shapes it is
handed, as ``model._norm_schedule`` and ``flash_attention._block_sizes`` do;
on the CPU the kernels are interpreted.  Forward only: nothing trains through
them (``model._block_stack`` refuses ``hc_mult``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddlefleetx_tpu.utils import device as _device

_HIGHEST = jax.lax.Precision.HIGHEST
# tokens a grid step takes: a tile of [128, 4 x 3584] bfloat16 is 3.7 MB, two
# in flight each way; a decode step's 64 rows are one tile
_TOKEN_TILE = 128
# lanes a mix handles at a time (float32 temporaries of [tile, chunk])
_LANE_CHUNK = 1024
_VMEM_CAP = 100 * 2**20  # of the 128 MiB a v5e core has


def _schedule(tokens: int, n: int, width: int) -> Tuple[str, int]:
    """(what runs a sub-block's two mixes over ``tokens`` x ``n`` x ``width``,
    the tokens a grid step takes) from those static values: the one place it
    is chosen.  ``kernel`` where a copy is whole lane tiles and the tokens are
    whole tiles (one tile of all of them under 128, in whole sublane tiles of
    bfloat16); ``composite`` for everything else (toy widths)."""
    if width % 128 or n < 2:
        return "composite", 0
    if tokens % _TOKEN_TILE == 0:
        return "kernel", _TOKEN_TILE
    if tokens < _TOKEN_TILE and tokens % 16 == 0:
        return "kernel", tokens
    return "composite", 0


def _lane_chunk(width: int) -> int:
    """The widest chunk of whole lane tiles under ``_LANE_CHUNK`` that divides
    ``width`` (3,584 -> 896)."""
    tiles = width // 128
    best = max(d for d in range(1, tiles + 1) if tiles % d == 0 and d * 128 <= _LANE_CHUNK)
    return best * 128


def _gates(p: Dict[str, Any], n: int) -> jax.Array:
    """``alpha`` [3] and ``bias`` [maps] as one [maps, 2] float32 operand: a
    number's gate beside its bias."""
    alpha = jnp.concatenate([jnp.broadcast_to(p["alpha"][k].astype(jnp.float32), (m,))
                             for k, m in enumerate((n, n, n * n))])
    return jnp.stack([alpha, p["bias"].astype(jnp.float32)], axis=1)


# ---------------------------------------------------------------------------
# The plain forms
# ---------------------------------------------------------------------------


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` rounds over m [..., n, n]: columns, then rows."""
    def body(_, m):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, body, m)


def hc_maps_xla(x: jax.Array, p: Dict[str, Any], cfg) -> jax.Array:
    """x [tokens, n, C] -> the maps [tokens, n (2 + n)] float32 in plain XLA."""
    n, t = cfg.hc_mult, x.shape[0]
    v = x.reshape(t, -1).astype(jnp.float32)
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + cfg.norm_eps)
    ab = _gates(p, n)
    z = jnp.einsum("tk,mk->tm", v, p["phi"].astype(jnp.float32), precision=_HIGHEST)
    lin = z * ab[:, 0] + ab[:, 1]
    pre, post = jax.nn.sigmoid(lin[:, :n]), 2.0 * jax.nn.sigmoid(lin[:, n:2 * n])
    m = jnp.exp(jnp.clip(lin[:, 2 * n:], -cfg.hc_res_clamp, cfg.hc_res_clamp))
    m = sinkhorn(m.reshape(t, n, n), cfg.hc_sinkhorn_iters, cfg.hc_eps).reshape(t, n * n)
    return jnp.concatenate([pre, post, m], axis=-1)


def hc_pre_xla(x: jax.Array, p: Dict[str, Any], cfg) -> Tuple[jax.Array, jax.Array]:
    """x [tokens, n, C] -> (u [tokens, C] in x's dtype, maps)."""
    maps = hc_maps_xla(x, p, cfg)
    u = jnp.einsum("tn,tnc->tc", maps[:, :cfg.hc_mult], x.astype(jnp.float32))
    return u.astype(x.dtype), maps


def hc_post_xla(x: jax.Array, f: jax.Array, maps: jax.Array, cfg) -> jax.Array:
    """x [tokens, n, C], f [tokens, C], maps -> the new stream, x's dtype."""
    n, t = cfg.hc_mult, x.shape[0]
    post, res = maps[:, n:2 * n], maps[:, 2 * n:].reshape(t, n, n)
    out = (jnp.einsum("tij,tjc->tic", res, x.astype(jnp.float32))
           + post[:, :, None] * f.astype(jnp.float32)[:, None, :])
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------


def _nt(a, b):
    """a [m, k] . b [n, k]^T -> [m, n] float32, both contracted on their lanes."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _pre_kernel(x_ref, phi_ref, ab_ref, u_ref, maps_ref, *, n, width, iters, eps, clamp,
                norm_eps, chunk):
    tile, maps = x_ref.shape[0], n * (2 + n)
    # tokens on the lanes: z^T [maps, tile] and the sum of squares, one pass
    zt = jnp.zeros((maps, tile), jnp.float32)
    ss = jnp.zeros((8, tile), jnp.float32)
    ones = jnp.ones((8, chunk), jnp.float32)
    for lo in range(0, n * width, chunk):
        xc = x_ref[:, lo:lo + chunk].astype(jnp.float32)
        zt = zt + _nt(phi_ref[:, lo:lo + chunk], xc)
        ss = ss + _nt(ones, xc * xc)
    r = jax.lax.rsqrt(ss[0:1] / (n * width) + norm_eps)
    lin = zt * r * ab_ref[:, 0:1] + ab_ref[:, 1:2]
    pre = jax.nn.sigmoid(lin[0:n])
    post = 2.0 * jax.nn.sigmoid(lin[n:2 * n])
    # row i of M as a slab [n (its columns j), tile]
    rows = tuple(jnp.exp(jnp.clip(lin[2 * n + i * n:2 * n + (i + 1) * n], -clamp, clamp))
                 for i in range(n))

    def round_(_, rows):
        cols = rows[0]
        for row in rows[1:]:
            cols = cols + row
        cols = cols + eps
        rows = tuple(row / cols for row in rows)
        return tuple(row / (jnp.sum(row, axis=0, keepdims=True) + eps) for row in rows)

    rows = jax.lax.fori_loop(0, iters, round_, rows)
    mt = jnp.concatenate((pre, post) + rows, axis=0)  # [maps, tile]
    # a row a token: I [tile, tile] . mt^T, exact (each sum has one term)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)).astype(jnp.float32)
    by_token = _nt(eye, mt)  # [tile, maps]
    maps_ref[...] = by_token
    for lo in range(0, width, chunk):
        acc = by_token[:, 0:1] * x_ref[:, lo:lo + chunk].astype(jnp.float32)
        for i in range(1, n):
            at = i * width + lo
            acc = acc + by_token[:, i:i + 1] * x_ref[:, at:at + chunk].astype(jnp.float32)
        u_ref[:, lo:lo + chunk] = acc.astype(u_ref.dtype)


def _post_kernel(x_ref, f_ref, maps_ref, out_ref, *, n, width, chunk):
    maps = maps_ref[...]
    for lo in range(0, width, chunk):
        f = f_ref[:, lo:lo + chunk].astype(jnp.float32)
        xs = [x_ref[:, j * width + lo:j * width + lo + chunk].astype(jnp.float32)
              for j in range(n)]
        for i in range(n):
            acc = maps[:, n + i:n + i + 1] * f
            for j in range(n):
                k = 2 * n + i * n + j
                acc = acc + maps[:, k:k + 1] * xs[j]
            out_ref[:, i * width + lo:i * width + lo + chunk] = acc.astype(out_ref.dtype)


def _compiler_params(block_bytes: int):
    from jax.experimental.pallas import tpu as pltpu

    # every block twice (in flight and in use) and the float32 temporaries
    need = 2 * block_bytes + 24 * 2**20
    return pltpu.CompilerParams(dimension_semantics=("parallel",),
                                vmem_limit_bytes=int(min(need, _VMEM_CAP)))


# jitted: a program's 12 call sites of one shape are traced and lowered once
@functools.partial(jax.jit, static_argnames=("n", "tile", "iters", "eps", "clamp", "norm_eps",
                                             "interpret"))
def _pre_pallas(x2, phi, ab, *, n, tile, iters, eps, clamp, norm_eps, interpret):
    tokens, flat = x2.shape
    width, maps = flat // n, n * (2 + n)
    row = lambda w: pl.BlockSpec((tile, w), lambda t: (t, 0))  # noqa: E731
    whole = lambda shape: pl.BlockSpec(shape, lambda t: (0, 0))  # noqa: E731
    block_bytes = (tile * (flat + width) * x2.dtype.itemsize + maps * flat * 4 + tile * maps * 4)
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, width=width, iters=iters, eps=eps, clamp=clamp,
                          norm_eps=norm_eps, chunk=_lane_chunk(width)),
        grid=(tokens // tile,),
        in_specs=[row(flat), whole((maps, flat)), whole((maps, 2))],
        out_specs=[row(width), row(maps)],
        out_shape=[jax.ShapeDtypeStruct((tokens, width), x2.dtype),
                   jax.ShapeDtypeStruct((tokens, maps), jnp.float32)],
        compiler_params=_compiler_params(block_bytes),
        interpret=interpret,
        name="pfx_hc_pre",
    )(x2, phi, ab)


@functools.partial(jax.jit, static_argnames=("n", "tile", "interpret"))
def _post_pallas(x2, f2, maps, *, n, tile, interpret):
    tokens, flat = x2.shape
    width = flat // n
    row = lambda w: pl.BlockSpec((tile, w), lambda t: (t, 0))  # noqa: E731
    block_bytes = tile * (2 * flat + width) * x2.dtype.itemsize + tile * maps.shape[1] * 4
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, width=width, chunk=_lane_chunk(width)),
        grid=(tokens // tile,),
        in_specs=[row(flat), row(width), row(maps.shape[1])],
        out_specs=row(flat),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        # a tile is read, then written where it was read: the stream is mixed in place
        input_output_aliases={0: 0},
        compiler_params=_compiler_params(block_bytes),
        interpret=interpret,
        name="pfx_hc_post",
    )(x2, f2, maps)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def _impl(impl: str, tokens: int, n: int, width: int) -> Tuple[bool, int]:
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"hyper_connection impl {impl!r}; valid: auto, pallas, xla")
    kind, tile = _schedule(tokens, n, width)
    if impl == "pallas" and kind != "kernel":
        raise ValueError(f"pfx_hc_*: no tile for {tokens} tokens x {n} x {width}")
    return impl != "xla" and kind == "kernel", tile


def hc_pre(x: jax.Array, p: Dict[str, Any], cfg, *, impl: str = "auto"):
    """x [..., n, C], a sub-block's maps' parameters -> (u [..., C] in x's
    dtype: what the sub-block reads; maps [tokens, n (2 + n)] float32, a row
    a token in the order of x's leading axes: what :func:`hc_post` takes)."""
    *lead, n, width = x.shape
    if n != cfg.hc_mult:
        raise ValueError(f"hc_pre: a stream of {n} copies under hc_mult {cfg.hc_mult}")
    tokens = math.prod(lead)
    kernel, tile = _impl(impl, tokens, n, width)
    with jax.named_scope("pfx.hc.pre"):
        if kernel:
            u, maps = _pre_pallas(
                x.reshape(tokens, n * width), p["phi"].astype(jnp.float32), _gates(p, n),
                n=n, tile=tile, iters=int(cfg.hc_sinkhorn_iters), eps=float(cfg.hc_eps),
                clamp=float(cfg.hc_res_clamp), norm_eps=float(cfg.norm_eps),
                interpret=_device.pallas_interpret())
        else:
            u, maps = hc_pre_xla(x.reshape(tokens, n, width), p, cfg)
    return u.reshape(*lead, width), maps


def hc_post(x: jax.Array, f: jax.Array, maps: jax.Array, cfg, *, impl: str = "auto") -> jax.Array:
    """x [..., n, C], the sub-block's result f [..., C], the maps of
    :func:`hc_pre` -> the new stream, x's shape and dtype."""
    *lead, n, width = x.shape
    tokens = maps.shape[0]
    kernel, tile = _impl(impl, tokens, n, width)
    with jax.named_scope("pfx.hc.post"):
        if kernel:
            out = _post_pallas(x.reshape(tokens, n * width), f.reshape(tokens, width).astype(x.dtype),
                               maps, n=n, tile=tile, interpret=_device.pallas_interpret())
        else:
            out = hc_post_xla(x.reshape(tokens, n, width), f.reshape(tokens, width), maps, cfg)
    return out.reshape(x.shape)


def hc_in(x: jax.Array, cfg) -> jax.Array:
    """The way in: x [..., C] -> the stream [..., n, C], every copy x."""
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (cfg.hc_mult, x.shape[-1]))


def hc_out(x: jax.Array) -> jax.Array:
    """The way out: the stream [..., n, C] -> the sum of its copies [..., C]
    (float32 accumulation, x's dtype)."""
    with jax.named_scope("pfx.hc.out"):
        return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)
