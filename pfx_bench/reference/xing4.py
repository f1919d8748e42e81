"""Plain reference of the Xing4.0 block (configuration xing4.0-29b-a4b): the
whole forward in straightforward jax.numpy, float32, matmuls at "highest"
precision; EXPANDED attention only, no cache, no kernel, no sort.  Independent
of ``paddlefleetx_tpu.models``: it reads the served parameter tree by its key
names and the sizes from a plain dict (the ``model`` group of
``configs/xing4.0-29b-a4b.json``, or a test's toy sizes).

The equations (docs/xing4.md).  The residual stream of a token is ``X`` in
R^{n x C} (n = ``hc_mult`` copies of the hidden width C).  Way in: every copy
is the token's embedding row.  Every SUB-BLOCK k (each layer's attention, each
layer's feed-forward) has its own ``Phi`` [n + n + n^2, nC], ``alpha`` [3] and
``bias`` [n + n + n^2] and does

    v      = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)             [nC]
    z      = Phi v                                                 [n + n + n^2]
    h_pre  = sigmoid(alpha[0] z[:n] + bias[:n])                    in (0, 1)^n
    h_post = 2 sigmoid(alpha[1] z[n:2n] + bias[n:2n])              in (0, 2)^n
    M      = exp(clip(alpha[2] mat(z[2n:]) + mat(bias[2n:]), -c, c))   [n, n], row-major
    hc_sinkhorn_iters times:  M <- M / (column sums + hc_eps);  M <- M / (row sums + hc_eps)
    u      = sum_i h_pre[i] X_i                                    [C]
    f      = F_k(u)
    X'_i   = sum_j M[i, j] X_j + h_post[i] f

``F_k`` is the DeepSeek-V3 layer's sub-block at this model's numbers
(docs/deepseek_v3.md): attention(rms(u; ln_1)) with latent attention,

    c_q  = rms(a W_qa; q_a_norm);  q = c_q W_qb                    [s, n_h x (nope + rope)]
    [c, k_r] = a W_kva;  c = rms(c; kv_a_norm);  k_r = rope(k_r);  q_r = rope(q[.., nope:])
    k_nope = c W_kb;  v = c W_vb
    score_h(i, j<=i) = (q_nope_h,i . k_nope_h,j + q_r_h,i . k_r,j) * scale
    scale = (nope + rope)^-0.5 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    F = concat_h(softmax_j(score_h) v_h) W_o

or mlp(rms(u; ln_2)): SwiGLU in the leading dense layers, and in the expert
layers swiglu_shared(m) + sum_j w_j swiglu_{idx_j}(m) with sc = sigmoid(m W_g)
in float32, idx the top_k of sc + e_score_correction_bias (one group), w =
route_scale sc[idx] / (sum sc[idx] + 1e-20).  rope rotates ADJACENT pairs by
position x f_i, the frequencies YaRN's.  Way out: y = sum_i X_i, the final
RMSNorm, the untied head.

Controls (each has to move the logits past the check's limits; by keyword
of :func:`logits` or as names in ``cfg["control"]``, comma-separated):
``hc_off`` (ONE stream and x + f, the maps unread), ``sinkhorn_1`` (one round
for twenty), ``maps_bf16`` (the maps' norm, product, gates and rounds in
bfloat16), ``yarn_off`` (plain frequencies, m = 1).

One departure from plainness: attention runs in blocks of ``QUERY_BLOCK``
queries (``lax.map``), so that the [heads, s, s] float32 scores never exist;
no value changes.  The served tree is upcast a LAYER at a time, so that the
reference fits beside it."""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
CONTROLS = ("hc_off", "sinkhorn_1", "maps_bf16", "yarn_off")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def yarn_frequencies(cfg, yarn=True):
    d = int(cfg["qk_rope_head_dim"])
    theta, factor = float(cfg["rope_theta"]), float(cfg.get("rope_scaling_factor", 1.0))
    freq = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    if factor <= 1.0 or not yarn:
        return jnp.asarray(freq, jnp.float32)
    orig = float(cfg["rope_original_max_position"])

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(cfg["rope_beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(cfg["rope_beta_slow"]))), d - 1)
    out = []
    for i, f in enumerate(freq):
        ramp = min(1.0, max(0.0, (i - low) / max(high - low, 0.001)))
        out.append(f * (1.0 - ramp) + f / factor * ramp)
    return jnp.asarray(out, jnp.float32)


def yarn_m(cfg, key="rope_mscale_all_dim", yarn=True):
    factor = float(cfg.get("rope_scaling_factor", 1.0))
    return 1.0 if factor <= 1.0 or not yarn else 0.1 * float(cfg[key]) * math.log(factor) + 1.0


def softmax_scale(cfg, yarn=True):
    d = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    return d ** -0.5 * yarn_m(cfg, yarn=yarn) ** 2


def _rope(x, cfg, yarn=True):
    """x [b, s, ..., d]: adjacent pairs rotated, positions 0..s-1."""
    s, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_frequencies(cfg, yarn)[None, :]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (d // 2,))
    factor = yarn_m(cfg, "rope_mscale", yarn) / yarn_m(cfg, yarn=yarn)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _attention(q, k, v, scale):
    """q, k [b, s, n, dk], v [b, s, n, dv] -> [b, s, n, dv]; causal."""
    b, s, n, _ = q.shape
    blk = min(QUERY_BLOCK, s)
    pad = -s % blk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    cols = jnp.arange(s)

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(qp, i * blk, blk, axis=1)
        scores = jnp.einsum("bqnd,bjnd->bnqj", qs, k) * scale
        rows = i * blk + jnp.arange(blk)
        scores = jnp.where((cols[None, :] <= rows[:, None])[None, None], scores, -jnp.inf)
        return jnp.einsum("bnqj,bjnd->bqnd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, jnp.arange((s + pad) // blk))  # [blocks, b, blk, n, dv]
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s + pad, n, v.shape[-1])[:, :s]


def latent_attention(a, at, cfg, yarn=True):
    """The attention sub-block over its normed input a [b, s, C] -> [b, s, C]."""
    eps, nope, kl = float(cfg["norm_eps"]), int(cfg["qk_nope_head_dim"]), int(cfg["kv_lora_rank"])
    c_q = _rms(a @ at["q_a_kernel"], at["q_a_norm"], eps)
    q = (c_q @ at["q_b_kernel"]).reshape(c_q.shape[:2] + (at["k_b_kernel"].shape[1], -1))
    kv = a @ at["kv_a_kernel"]
    c = _rms(kv[..., :kl], at["kv_a_norm"], eps)
    k_r, q_r = _rope(kv[..., kl:], cfg, yarn), _rope(q[..., nope:], cfg, yarn)
    k_nope = jnp.einsum("bsc,cnd->bsnd", c, at["k_b_kernel"])
    v = jnp.einsum("bsc,cnd->bsnd", c, at["v_b_kernel"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None], k_nope.shape[:-1] + k_r.shape[-1:])], -1)
    o = _attention(jnp.concatenate([q[..., :nope], q_r], -1), k, v, softmax_scale(cfg, yarn))
    return jnp.einsum("bsnd,ndh->bsh", o, at["out_kernel"])


def _swiglu(m, p):
    return (jax.nn.silu(m @ p["w1"]) * (m @ p["w3"])) @ p["w2"]


def route(m, router_kernel, bias, cfg):
    """-> (idx [.., k] expert ids, w [.., k] weights): one group, the top_k of
    the bias-corrected sigmoid scores, the weights the plain scores' share."""
    sc = jax.nn.sigmoid(m.astype(jnp.float32) @ router_kernel.astype(jnp.float32))
    _, idx = jax.lax.top_k(sc + bias, int(cfg["moe_top_k"]))
    w = jnp.take_along_axis(sc, idx, axis=-1)
    return idx, float(cfg["moe_route_scale"]) * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)


def feed_forward(m, p, cfg):
    """The feed-forward sub-block over its normed input: SwiGLU, or the
    experts in ``p`` (all of them here), each applied to every token and
    weighted by what the router gave it, plus the shared one."""
    if "router_kernel" not in p:
        return _swiglu(m, p)
    idx, w = route(m, p["router_kernel"], p["e_score_correction_bias"], cfg)
    offset = int(cfg.get("moe_expert_offset", 0))

    def one(out, inp):
        e, pe = inp
        weight = jnp.sum(jnp.where(idx == offset + e, w, 0.0), axis=-1)
        return out + weight[..., None] * _swiglu(m, pe), None

    held = p["experts"]["w1"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (jnp.arange(held), p["experts"]))
    return out + _swiglu(m, p["shared"]) if "shared" in p else out


def maps(x, p, cfg, rounds=None, dtype=jnp.float32):
    """The stream x [..., n, C] -> (h_pre [..., n], h_post [..., n], H_res
    [..., n, n]) of the sub-block whose maps' parameters are ``p``.
    ``rounds`` (a control): Sinkhorn rounds, else the configuration's;
    ``dtype`` (a control): the dtype every step below runs in."""
    n = x.shape[-2]
    eps, clamp = float(cfg["hc_eps"]), float(cfg["hc_res_clamp"])
    v = x.reshape(x.shape[:-2] + (-1,)).astype(dtype)
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True)
                          + jnp.asarray(float(cfg["norm_eps"]), dtype))
    z = jnp.einsum("...k,mk->...m", v, p["phi"].astype(dtype), preferred_element_type=dtype)
    alpha, bias = p["alpha"].astype(dtype), p["bias"].astype(dtype)
    h_pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
    h_post = 2 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * z[..., 2 * n:] + bias[2 * n:], -clamp, clamp))
    m = m.reshape(m.shape[:-1] + (n, n))
    for _ in range(int(cfg["hc_sinkhorn_iters"]) if rounds is None else rounds):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + jnp.asarray(eps, dtype))
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + jnp.asarray(eps, dtype))
    f32 = jnp.float32
    return h_pre.astype(f32), h_post.astype(f32), m.astype(f32)


def round_through(a, dtype):
    """``a`` rounded to the values ``dtype`` holds, in float32 ARITHMETIC
    (on the v5e a cast through float8 inside jit is not the format's
    rounding: reference/nemotron_h.py says what it was)."""
    info = jnp.finfo(dtype)
    a = a.astype(jnp.float32)
    _, exponent = jnp.frexp(a)  # |a| = m 2^exponent, m in [0.5, 1)
    step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(exponent, info.minexp + 1) - (info.nmant + 1))
    return jnp.clip(jnp.round(a / step) * step, -float(info.max), float(info.max))


def _f32(tree, weight_dtype=None):
    """A layer's leaves in float32; ``weight_dtype`` (a control: the nearest
    precision below the configuration's) rounds every matrix through it first."""
    def up(a):
        if weight_dtype is not None and a.ndim >= 2:
            return round_through(a, weight_dtype)
        return a.astype(jnp.float32)

    return jax.tree.map(up, tree)


def _controls(cfg, given):
    """The controls asked for by keyword or named in ``cfg["control"]``."""
    named = {c for c in str(cfg.get("control", "")).split(",") if c}
    unknown = named - set(CONTROLS)
    if unknown:
        raise ValueError(f"unknown control {sorted(unknown)}; known: {CONTROLS}")
    return {c: bool(given.get(c)) or c in named for c in CONTROLS}


def _sub_block(x, lp, name, cfg, on):
    """One sub-block (``name``: attn | mlp) over the stream x [b, s, n, C]
    (or, under ``hc_off``, over x [b, s, C])."""
    eps = float(cfg["norm_eps"])

    def f(u):
        if name == "attn":
            return latent_attention(_rms(u, lp["ln_1"]["scale"], eps), lp["attn"], cfg,
                                    yarn=not on["yarn_off"])
        return feed_forward(_rms(u, lp["ln_2"]["scale"], eps), lp["mlp"], cfg)

    if on["hc_off"]:
        return x + f(x)
    h_pre, h_post, h_res = maps(x, lp["hc_" + name], cfg, rounds=1 if on["sinkhorn_1"] else None,
                                dtype=jnp.bfloat16 if on["maps_bf16"] else jnp.float32)
    u = jnp.einsum("...n,...nc->...c", h_pre, x)
    return jnp.einsum("...ij,...jc->...ic", h_res, x) + h_post[..., None] * f(u)[..., None, :]


def _way_in(params, tokens, cfg, on):
    x = _f32(params["embeddings"]["word"][tokens])
    if on["hc_off"]:
        return x
    n = int(cfg["hc_mult"])
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (n, x.shape[-1]))


def hidden(params, tokens, cfg, weight_dtype=None, **controls):
    """tokens [b, s] int -> final-normed hidden [b, s, C] float32.  Each
    layer's weights are upcast when the layer runs and dropped after it."""
    on = _controls(cfg, controls)
    with jax.default_matmul_precision("highest"):
        x = _way_in(params, tokens, cfg, on)
        for lp in params["blocks"]:
            lp = _f32(lp, weight_dtype)
            x = _sub_block(x, lp, "attn", cfg, on)
            x = _sub_block(x, lp, "mlp", cfg, on)
        y = x if on["hc_off"] else jnp.sum(x, axis=-2)  # the way out
        return _rms(y, params["final_ln"]["scale"].astype(jnp.float32), float(cfg["norm_eps"]))


def stream_at(params, tokens, cfg, layer=1, **controls):
    """tokens [b, s] int -> (X [b, s, n, C] float32, the stream at the INPUT
    of layer ``layer``, and what this reference's maps make of it for that
    layer's attention sub-block: [h_pre | h_post | H_res row-major], [b, s,
    n (2 + n)]).  What a check reads where served tokens cannot see the
    precision the maps run in: the same X through the program's maps has to
    give these numbers (``maps_bf16`` and ``sinkhorn_1`` are its controls)."""
    on = _controls(cfg, controls)
    with jax.default_matmul_precision("highest"):
        x = _way_in(params, tokens, cfg, dict(on, hc_off=False))
        for lp in params["blocks"][:layer]:
            lp = _f32(lp)
            x = _sub_block(_sub_block(x, lp, "attn", cfg, dict(on, hc_off=False)), lp, "mlp", cfg,
                           dict(on, hc_off=False))
        h_pre, h_post, h_res = maps(
            x, _f32(params["blocks"][layer]["hc_attn"]), cfg,
            rounds=1 if on["sinkhorn_1"] else None,
            dtype=jnp.bfloat16 if on["maps_bf16"] else jnp.float32)
        flat = h_res.reshape(h_res.shape[:-2] + (-1,))
        return x, jnp.concatenate([h_pre, h_post, flat], axis=-1)


def logits(params, tokens, cfg, at=None, weight_dtype=None, group_step=True, **controls):
    """tokens [b, s] int -> logits [b, s, vocab] float32 through the untied
    head; with ``at`` [n] only at those positions ([b, n, vocab])."""
    del group_step  # the runner's control for a group-limited router: one group here
    x = hidden(params, tokens, cfg, weight_dtype, **controls)
    if at is not None:
        x = x[:, at]
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bsh,vh->bsv", x, _f32(params["head"]["kernel"], weight_dtype))
