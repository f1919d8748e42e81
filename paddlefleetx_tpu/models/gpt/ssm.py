"""The Mamba-2 mixer of a ``layer_pattern`` block (``M`` layers), as it is
SERVED (docs/nemotron_h.md has the equations beside the published code's):

    [z | xBC | dt] = W_in u                 widths inner | inner + 2 G N | heads
    xBC = silu(conv1d_causal_depthwise(xBC) + b)          -> x [heads, P], B, C [G, N]
    dt = softplus(dt + dt_bias)     A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t          y_t = S_t C_t + D x_t
    out = W_out RMSNorm_groups(y * silu(z))

A prefill computes the recurrence in its CHUNKED form (:func:`chunked_scan`:
inside a chunk the masked products of the state-space dual, between chunks
the state carried) and keeps what the row's decode steps start from: ``S``
after the last REAL token and the last ``ssm_conv - 1`` real ``xBC`` columns
(the prompt is right-padded to its bucket: at the padding ``dt`` and ``x``
are 0, so ``S`` passes through).  A decode step (:func:`mixer_step`) is the
recurrence once, over every LIVE slot of the batch (``ops/ssm.py``).  No backward
pass is written: the block is served, not trained (ROADMAP queue 2).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from paddlefleetx_tpu.models.common import ParamSpec, normal_init, ones_init
from paddlefleetx_tpu.ops.ssm import pack_state, ssm_decode_update


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _conv_init(taps: int):
    bound = 1.0 / math.sqrt(taps)  # a depthwise conv's fan-in is its taps

    def f(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return f


def _dt_bias_init(cfg):
    def f(key, shape, dtype):
        lo, hi = math.log(cfg.ssm_dt_min), math.log(cfg.ssm_dt_max)
        dt = jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi))
        dt = jnp.maximum(dt, cfg.ssm_dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)

    return f


def mixer_specs(cfg, w_out) -> Dict[str, Any]:
    """One M layer's parameters.  ``w_out``: the out-projection's draw."""
    h, inner, heads = cfg.hidden_size, cfg.ssm_inner, cfg.ssm_heads
    w = normal_init(cfg.initializer_range)
    taps = cfg.ssm_conv
    return {
        "in_kernel": ParamSpec((h, inner + cfg.ssm_conv_dim + heads), ("embed", "mlp"), w),
        # tap j multiplies the column ssm_conv - 1 - j tokens back
        "conv_kernel": ParamSpec((taps, cfg.ssm_conv_dim), (None, "mlp"), _conv_init(taps)),
        "conv_bias": ParamSpec((cfg.ssm_conv_dim,), ("mlp",), _conv_init(taps)),
        "dt_bias": ParamSpec((heads,), (None,), _dt_bias_init(cfg)),
        "A_log": ParamSpec((heads,), (None,), _a_log_init),
        "D": ParamSpec((heads,), (None,), ones_init()),
        "norm": ParamSpec((inner,), ("mlp",), ones_init()),
        "out_kernel": ParamSpec((inner, h), ("mlp", "embed"), w_out),
    }


def in_projection(p, u: jax.Array, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """u [..., h] -> (z [..., inner], xBC [..., conv_dim], dt [..., heads]
    float32, after its softplus)."""
    with jax.named_scope("pfx.ssm.proj"):
        zxd = u @ p["in_kernel"].astype(u.dtype)
        inner, cd = cfg.ssm_inner, cfg.ssm_conv_dim
        dt = jax.nn.softplus(zxd[..., inner + cd:].astype(jnp.float32) + p["dt_bias"])
        return zxd[..., :inner], zxd[..., inner:inner + cd], dt


def _split_xbc(xbc: jax.Array, cfg):
    """conv output [..., conv_dim] -> (x [..., heads, P], B, C [..., G, N])."""
    inner, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    group = lead + (cfg.ssm_groups, cfg.ssm_state)
    return (xbc[..., :inner].reshape(lead + (cfg.ssm_heads, cfg.ssm_head_dim)),
            xbc[..., inner:inner + gn].reshape(group), xbc[..., inner + gn:].reshape(group))


def _conv_act(window_sum: jax.Array, bias: jax.Array, dtype) -> jax.Array:
    return jax.nn.silu(window_sum + bias).astype(dtype)


def gate_norm_out(p, y: jax.Array, z: jax.Array, cfg) -> jax.Array:
    """RMSNorm over each group of inner / G of ``y * silu(z)`` (the gate
    BEFORE the norm), then the out-projection.  y float32 [..., inner]."""
    dtype = z.dtype
    with jax.named_scope("pfx.ssm.gate_norm"):
        g = y * jax.nn.silu(z.astype(jnp.float32))
        grouped = g.reshape(g.shape[:-1] + (cfg.ssm_groups, -1))
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + cfg.norm_eps)
        g = (grouped.reshape(g.shape) * p["norm"]).astype(dtype)
    with jax.named_scope("pfx.ssm.proj"):
        return g @ p["out_kernel"].astype(dtype)


def chunked_scan(x, dt, a, b, c, cfg):
    """The recurrence over ONE sequence from a zero state, chunked.

    x [T, heads, P], dt [T, heads] float32 (0 where the token is padding),
    a [heads] < 0, b / c [T, G, N]; a T that is not whole chunks is padded
    the same way.  -> (y [T, heads, P] float32 WITHOUT the D x term, the
    state after the last token [heads, P, N] float32).

    Inside a chunk, position i reads position j <= i with the decay
    exp(sum_{j < k <= i} dt_k A): masked [Q, Q] products.  Between chunks
    only the state travels: each chunk's own contribution to the state at
    its end, decayed and summed by a scan over chunks."""
    T, heads, _ = x.shape
    q = min(cfg.ssm_chunk, T)
    if T % q:
        pad = q - T % q
        y, last = chunked_scan(*(jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                                 for v in (x, dt)), a,
                               *(jnp.pad(v, ((0, pad), (0, 0), (0, 0))) for v in (b, c)), cfg)
        return y[:T], last
    n_chunks, per_group = T // q, heads // cfg.ssm_groups
    dtype = x.dtype
    f32 = jnp.float32

    def chunks(v):
        return v.reshape((n_chunks, q) + v.shape[1:])

    xdt = chunks((x.astype(f32) * dt[:, :, None]).astype(dtype))  # [c, Q, H, P]
    bc, cc = chunks(b), chunks(c)
    acs = jnp.cumsum(chunks(dt * a), axis=1)  # [c, Q, H] float32, <= 0, falling
    # inside a chunk
    cb = jnp.einsum("cign,cjgn->cgij", cc, bc, preferred_element_type=f32)
    seg = acs[:, :, None, :] - acs[:, None, :, :]  # [c, i, j, H]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    decay = jnp.where(causal[None, :, :, None], jnp.exp(jnp.minimum(seg, 0.0)), 0.0)
    scores = jnp.repeat(cb, per_group, axis=1) * decay.transpose(0, 3, 1, 2)  # [c, H, i, j]
    y = jnp.einsum("chij,cjhp->cihp", scores.astype(dtype), xdt, preferred_element_type=f32)
    # each chunk's own state at its end
    to_end = jnp.exp(acs[:, -1:, :] - acs)  # [c, Q, H]
    b_h = jnp.repeat(bc, per_group, axis=2)  # [c, Q, H, N]
    own = jnp.einsum("cjhp,cjhn->chpn", (xdt.astype(f32) * to_end[..., None]).astype(dtype),
                     b_h, preferred_element_type=f32)
    # between chunks: the state entering each chunk
    whole = jnp.exp(acs[:, -1, :])  # [c, H]

    def carry(state, inp):
        own_c, whole_c = inp
        return state * whole_c[:, None, None] + own_c, state

    last, entering = jax.lax.scan(
        carry, jnp.zeros(own.shape[1:], f32), (own, whole))
    c_h = jnp.repeat(cc, per_group, axis=2)  # [c, Q, H, N]
    y = y + jnp.einsum("cihn,chpn->cihp", c_h, entering.astype(dtype),
                       preferred_element_type=f32) * jnp.exp(acs)[..., None]
    return y.reshape(x.shape), last


def mixer_prefill(p, u: jax.Array, prompt_len, cfg):
    """The mixer over ONE right-padded prompt u [1, T, h] of ``prompt_len``
    real tokens -> (out [1, T, h], the row's state after its last real
    token, packed as ``ops/ssm.py`` keeps it [R, N, W], its last
    ``ssm_conv - 1`` real conv columns [(taps - 1) * conv_dim])."""
    dtype = u.dtype
    T, taps = u.shape[1], cfg.ssm_conv
    z, xbc, dt = in_projection(p, u[0], cfg)
    valid = jax.lax.iota(jnp.int32, T) < prompt_len
    with jax.named_scope("pfx.ssm.conv"):
        padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
        kernel = p["conv_kernel"].astype(jnp.float32)
        acc = sum(padded[j:j + T].astype(jnp.float32) * kernel[j] for j in range(taps))
        x, b, c = _split_xbc(_conv_act(acc, p["conv_bias"], dtype), cfg)
        # real columns prompt_len - taps + 1 .. prompt_len - 1, zeros before the first
        columns = jax.lax.dynamic_slice_in_dim(padded, prompt_len, taps - 1, axis=0)
    with jax.named_scope("pfx.ssm.scan"):
        x = jnp.where(valid[:, None, None], x, 0)
        dt = jnp.where(valid[:, None], dt, 0.0)
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        y, state = chunked_scan(x, dt, a, b, c, cfg)
        y = y + x.astype(jnp.float32) * p["D"][None, :, None]
    out = gate_norm_out(p, y.reshape(T, -1), z, cfg)
    return out[None], pack_state(state), columns.reshape(-1)


def mixer_step(p, u: jax.Array, states: jax.Array, conv: jax.Array, active, cfg, *, layer: int,
               live):
    """One decode step of every slot: u [slots, 1, h]; ``states`` [M layers,
    slots, R, N, W] and ``conv`` [M layers, slots, (taps - 1) * conv_dim],
    of which state-space layer ``layer``'s are read and rewritten (both
    arrays come back whole); a slot that is not ``active`` keeps both as
    they are, and the state update does not visit it (``live``: the step's
    ``ops.ssm.live_slots(active)``, made once for all its layers).
    -> (out [slots, 1, h], states, conv)."""
    dtype = u.dtype
    taps, cd = cfg.ssm_conv, cfg.ssm_conv_dim
    z, xbc, dt = in_projection(p, u[:, 0], cfg)
    with jax.named_scope("pfx.ssm.conv"):
        old = conv[layer]
        window = jnp.concatenate([old, xbc.astype(conv.dtype)], axis=-1)  # [slots, taps * cd]
        kernel = p["conv_kernel"].astype(jnp.float32)
        acc = sum(window[:, j * cd:(j + 1) * cd].astype(jnp.float32) * kernel[j]
                  for j in range(taps))
        x, b, c = _split_xbc(_conv_act(acc, p["conv_bias"], dtype), cfg)
        conv = conv.at[layer].set(jnp.where(active[:, None], window[:, cd:], old))
    with jax.named_scope("pfx.ssm.step"):
        y, states = ssm_decode_update(
            states, x, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), b, c, p["D"],
            active=active, layer=layer, live=live)
    out = gate_norm_out(p, y.reshape(y.shape[0], -1), z, cfg)
    return out[:, None], states, conv
