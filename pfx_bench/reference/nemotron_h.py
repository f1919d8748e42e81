"""Plain reference of the Nemotron-H block (configuration nemotron-3-nano):
the forward pass in straightforward jax.numpy, float32, matmuls at "highest"
precision; the state-space recurrence as a SEQUENTIAL ``lax.scan`` over
tokens (not the chunked form the program's prefill uses), plain softmax
attention with the KV heads repeated, the router and every expert as loops
and einsums; no cache, no kernel, no batching, no sort.  Independent of
``paddlefleetx_tpu.models``: it reads the served parameter tree by its key
names (``blocks``: one dict a layer) and the sizes from a plain dict (the
``model`` group of ``configs/nemotron-3-nano.json``, or a test's toy sizes).

The equations (docs/nemotron_h.md), following the family's published
modelling code (``model_type: nemotron_h``).  Every layer is ONE sub-block,
``x' = x + mix(rms(x; ln_1))`` with eps ``norm_eps``, no bias anywhere but the
conv's; the kind of layer l is character l of ``layer_pattern``; final
RMSNorm, untied head.  Over one causal sequence at positions 0..s-1:

``M`` (Mamba-2), u = rms(x):
    [z | xBC | dt] = u W_in                 widths inner | inner + 2 G N | heads,  inner = heads x P
    xBC_t = silu(sum_j w_j xBC_{t-(k-1)+j} + b)     depthwise, causal, k taps (zeros before token 0)
    x_t [heads, P], B_t, C_t [G, N] = split(xBC_t)  head h reads group h // (heads / G)
    dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)      one scalar a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t           S [heads, P, N], S_{-1} = 0
    y_t = S_t C_t + D x_t
    out = (rms_groups(y * silu(z)) * norm) W_out           the gate BEFORE the norm; G groups of inner / G

``*`` (attention), u = rms(x):
    q = u W_q [n, d], k = u W_k, v = u W_v [kv, d]; NO rotation (the published code applies none
    in these layers; the state-space layers carry the order); query head h reads KV head h // (n / kv)
    o_h = softmax_{j<=i}(q_h,i . k_j / sqrt(d)) v_j;  out = concat_h(o_h) W_o

``E`` (experts), m = rms(x):
    sc = sigmoid(m W_g) in float32; choice = sc + b (e_score_correction_bias, in the choice only);
    idx = the top_k highest choice scores (n_group 1: no group step);
    w = sc[idx] / (sum sc[idx] + 1e-20) * route_scale
    out = relu2_shared(m) + sum_j w_j relu2_{idx_j}(m),   relu2_e(m) = relu(m W1_e)^2 W2_e  (non-gated)
    only pairs whose expert is HELD here (ids offset .. offset + held - 1) add anything
``-`` (dense): out = relu(m W1)^2 W2.

Departures from the published model, all in the configuration's file: one
chip's share of the experts and of the vocabulary, as the program holds
them; seeded weights drawn by the published initialisation; the recurrent
state float32.  ``group_step`` is accepted and ignored (the runner's control
for a group-limited router; this router has one group).  One departure from
plainness: attention runs in blocks of ``QUERY_BLOCK`` queries (``lax.map``),
so that the [heads, s, s] float32 scores never exist; no value changes."""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _relu2(m, p):
    return jnp.square(jax.nn.relu(m @ p["w1"])) @ p["w2"]


def mamba_mixer(u, p, cfg):
    """u [b, s, h] -> ([b, s, h], the state S after the last token [b, heads,
    P, N]): the recurrence token by token."""
    heads, hd = int(cfg["ssm_heads"]), int(cfg["ssm_head_dim"])
    n, groups, taps = int(cfg["ssm_state"]), int(cfg["ssm_groups"]), int(cfg["ssm_conv"])
    inner, gn = heads * hd, groups * n
    b, s, _ = u.shape
    zxd = u @ p["in_kernel"]
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:inner + inner + 2 * gn], zxd[..., 2 * inner + 2 * gn:]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + s] * p["conv_kernel"][j] for j in range(taps))
                      + p["conv_bias"])
    x = xbc[..., :inner].reshape(b, s, heads, hd)
    bmat = jnp.repeat(xbc[..., inner:inner + gn].reshape(b, s, groups, n), heads // groups, axis=2)
    cmat = jnp.repeat(xbc[..., inner + gn:].reshape(b, s, groups, n), heads // groups, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [b, s, heads]
    a = -jnp.exp(p["A_log"])

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp  # [b, heads, hd], [b, heads, n] x 2, [b, heads]
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    first = jnp.zeros((b, heads, hd, n), jnp.float32)
    last, y = jax.lax.scan(token, first, tuple(jnp.moveaxis(v, 1, 0) for v in (x, bmat, cmat, dt)))
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * x  # [b, s, heads, hd]
    g = y.reshape(b, s, inner) * jax.nn.silu(z)
    g = g.reshape(b, s, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + float(cfg["norm_eps"]))
    return (g.reshape(b, s, inner) * p["norm"]) @ p["out_kernel"], last


def attention(u, p, cfg, rotate=False):
    """Causal grouped-query attention, KV heads repeated.  ``rotate`` is a
    control (rotate-half over all head dims at ``rope_theta``): the
    published code rotates nothing here."""
    q = jnp.einsum("bsh,hnd->bsnd", u, p["q_kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", u, p["k_kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", u, p["v_kernel"])
    if rotate:
        q, k = (_rope(t, float(cfg.get("rope_theta", 10000.0))) for t in (q, k))
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    b, s, n, d = q.shape
    blk = min(QUERY_BLOCK, s)
    pad = -s % blk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    cols = jnp.arange(s)

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(qp, i * blk, blk, axis=1)
        scores = jnp.einsum("bqnd,bjnd->bnqj", qs, k) * d ** -0.5
        rows = i * blk + jnp.arange(blk)
        scores = jnp.where((cols[None, :] <= rows[:, None])[None, None], scores, -jnp.inf)
        return jnp.einsum("bnqj,bjnd->bqnd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, jnp.arange((s + pad) // blk))  # [blocks, b, blk, n, d]
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, s + pad, n, d)[:, :s]
    return jnp.einsum("bsnd,ndh->bsh", out, p["out_kernel"])


def _rope(x, theta):
    s, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * theta ** (
        -2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def route(m, router_kernel, bias, cfg):
    """-> (idx [.., k] expert ids over ALL experts, w [.., k] weights)."""
    sc = jax.nn.sigmoid(m.astype(jnp.float32) @ router_kernel.astype(jnp.float32))
    _, idx = jax.lax.top_k(sc + bias, int(cfg["moe_top_k"]))
    w = jnp.take_along_axis(sc, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, float(cfg["moe_route_scale"]) * w


def routed_experts(m, p, cfg, offset=None):
    """The part of an expert layer's result that the experts in ``p`` give
    (ids ``offset`` .. ``offset`` + held - 1): a loop over them, each
    applied to every token and weighted by what the router gave it."""
    held = p["experts"]["w1"].shape[0]
    offset = int(cfg.get("moe_expert_offset", 0)) if offset is None else offset
    idx, w = route(m, p["router_kernel"], p["e_score_correction_bias"], cfg)

    def one(out, inp):
        e, pe = inp
        weight = jnp.sum(jnp.where(idx == offset + e, w, 0.0), axis=-1)
        return out + weight[..., None] * _relu2(m, pe), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (jnp.arange(held), p["experts"]))
    return out


def expert_layer(m, p, cfg):
    out = routed_experts(m, p, cfg)
    return out + _relu2(m, p["shared"]) if "shared" in p else out


def _layer(x, p, kind, cfg, rotate=False):
    u = _rms(x, p["ln_1"]["scale"], float(cfg["norm_eps"]))
    if kind == "M":
        return x + mamba_mixer(u, p["ssm"], cfg)[0]
    if kind == "*":
        return x + attention(u, p["attn"], cfg, rotate)
    if kind == "E":
        return x + expert_layer(u, p["mlp"], cfg)
    return x + _relu2(u, p["mlp"])


def round_through(a, dtype):
    """``a`` rounded to the values ``dtype`` holds (to nearest, ties to even,
    subnormals and the largest finite value as ``dtype`` has them), in
    float32 ARITHMETIC: what ``a.astype(dtype).astype(float32)`` gives on the
    CPU.  Compiled for the v5e, which has no FP8 unit, that cast left every
    2-D matrix of this tree as it was and rounded the others to three
    mantissa bits without the format's subnormals (my chip run, PR 33: the
    ``fp8_reference`` control moved no Mamba layer at all)."""
    info = jnp.finfo(dtype)
    a = a.astype(jnp.float32)
    _, exponent = jnp.frexp(a)  # |a| = m 2^exponent, m in [0.5, 1)
    step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(exponent, info.minexp + 1) - (info.nmant + 1))
    return jnp.clip(jnp.round(a / step) * step, -float(info.max), float(info.max))


def _f32(tree, weight_dtype=None):
    """A layer's leaves in float32; ``weight_dtype`` (a control: the nearest
    precision below the configuration's) rounds every matrix through it
    first."""
    def up(a):
        if weight_dtype is not None and a.ndim >= 2:
            return round_through(a, weight_dtype)
        return a.astype(jnp.float32)

    return jax.tree.map(up, tree)


def hidden(params, tokens, cfg, weight_dtype=None, rotate=False):
    """tokens [b, s] int -> final-normed hidden [b, s, h] float32.  Each
    layer's weights are upcast when the layer runs and dropped after it."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embeddings"]["word"][tokens])
        for kind, lp in zip(cfg["layer_pattern"], params["blocks"]):
            x = _layer(x, _f32(lp, weight_dtype), kind, cfg, rotate)
        return _rms(x, params["final_ln"]["scale"].astype(jnp.float32), float(cfg["norm_eps"]))


def first_state(params, tokens, cfg):
    """tokens [b, s] int -> the recurrent state of the FIRST state-space
    layer after the last token, [b, heads, P, N] float32: what a served row
    that has read these tokens keeps there.  Of every state in the stack
    this one alone is computed from the same input on both sides (when no
    layer comes before it: the embedding's rows), so it differs from the
    program's by the projections' rounding and by the precision the state
    itself is kept in, and not by what the layers before it have drifted."""
    first = cfg["layer_pattern"].index("M")
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embeddings"]["word"][tokens])
        for kind, lp in zip(cfg["layer_pattern"][:first], params["blocks"]):
            x = _layer(x, _f32(lp), kind, cfg)
        lp = _f32(params["blocks"][first])
        return mamba_mixer(_rms(x, lp["ln_1"]["scale"], float(cfg["norm_eps"])), lp["ssm"], cfg)[1]


def logits(params, tokens, cfg, at=None, weight_dtype=None, group_step=True, rotate=False):
    """tokens [b, s] int -> logits [b, s, vocab] float32 through the untied
    head; with ``at`` [n] only at those positions ([b, n, vocab])."""
    del group_step  # one group: nothing to switch off
    x = hidden(params, tokens, cfg, weight_dtype, rotate)
    if at is not None:
        x = x[:, at]
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bsh,vh->bsv", x, _f32(params["head"]["kernel"], weight_dtype))
