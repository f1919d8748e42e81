"""Plain reference of the DeepSeek-V3 block (configuration deepseek-v3): the
forward pass in straightforward jax.numpy, float32, matmuls at "highest"
precision; EXPANDED attention only, no cache, no kernel, no sort.
Independent of ``paddlefleetx_tpu.models``: it reads the served parameter
tree by its key names and the sizes from a plain dict (the ``model`` group of
``configs/deepseek-v3.json``, or a test's toy sizes).  The served tree holds
one dict a layer (``blocks``); a tree with stacked layers is read too.

The equations (docs/deepseek_v3.md).  Pre-norm, RMSNorm with learned scale,
no biases, untied head, final RMSNorm.  Every layer, over one causal
sequence at positions 0..s-1:

    a    = rms(x; ln_1)
    c_q  = rms(a W_qa; q_a_norm)                     [s, q_lora]
    q    = c_q W_qb                                  [s, n x (nope + rope)], head-major
    [c, k_r] = a W_kva;  c = rms(c; kv_a_norm)       [s, kv_lora], [s, rope]
    k_r  = rope(k_r);  q_r = rope(q[.., nope:])      one rotated key for all heads
    k_nope = c W_kb;  v = c W_vb                     [s, n, nope], [s, n, v]  (W_kvb's two halves)
    score_h(i, j<=i) = (q_nope_h,i . k_nope_h,j + q_r_h,i . k_r,j) * scale
    scale = (nope + rope)^-0.5 * m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    o_h  = softmax_j(score_h) v_h;  h = x + concat_h(o_h) W_o
    m    = rms(h; ln_2)
    f    = (silu(m W1) * (m W3)) W2                            leading dense layers
    f    = swiglu_shared(m) + sum_j w_j swiglu_{idx_j}(m)      expert layers, where
           sc = sigmoid(m W_g) in float32; choice = sc + b (e_score_correction_bias);
           the experts are n_group groups of equal size; a group scores the sum
           of its two highest choice scores; the topk_group best groups stay;
           idx = the top_k highest choice scores among their experts;
           w = sc[idx] / (sum sc[idx] + 1e-20) * route_scale
           only pairs whose expert is HELD here (ids offset .. offset + held - 1) add anything
    x'   = h + f

rope rotates ADJACENT pairs (2i, 2i+1) of the rope dims by position x f_i;
f_i = theta^(-2i/rope), under YaRN blended with f_i / factor by the linear
ramp between the correction dims of beta_fast and beta_slow at the original
context; the cos/sin factor is mscale / mscale_all_dim's ratio (1 here).

Departures from the published model, all in the configuration's file:
weights are bfloat16 where the checkpoint is block-scaled FP8 (the reference
upcasts the served tree a LAYER at a time, so that it fits beside it); the
multi-token-prediction module is not loaded; no redundant experts; one
chip's share of the experts and of the vocabulary, as the program holds
them.  One departure from plainness: attention runs in blocks of
``QUERY_BLOCK`` queries (``lax.map``), so that the [heads, s, s] float32
scores never exist; no value changes."""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def yarn_frequencies(cfg):
    d = int(cfg["qk_rope_head_dim"])
    theta, factor = float(cfg["rope_theta"]), float(cfg.get("rope_scaling_factor", 1.0))
    freq = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    if factor <= 1.0:
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


def yarn_m(cfg, key="rope_mscale_all_dim"):
    factor = float(cfg.get("rope_scaling_factor", 1.0))
    return 1.0 if factor <= 1.0 else 0.1 * float(cfg[key]) * math.log(factor) + 1.0


def softmax_scale(cfg):
    d = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    return d ** -0.5 * yarn_m(cfg) ** 2


def _rope(x, cfg):
    """x [b, s, ..., d]: adjacent pairs rotated, positions 0..s-1."""
    s, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_frequencies(cfg)[None, :]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (d // 2,))
    factor = yarn_m(cfg, "rope_mscale") / yarn_m(cfg)
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


def _swiglu(m, p):
    return (jax.nn.silu(m @ p["w1"]) * (m @ p["w3"])) @ p["w2"]


def route(m, router_kernel, bias, cfg, group_step=True):
    """-> (idx [.., k] expert ids over ALL experts, w [.., k] weights).
    ``group_step`` False is a control: the plain top-k over all experts."""
    sc = jax.nn.sigmoid(m.astype(jnp.float32) @ router_kernel.astype(jnp.float32))
    choice = sc + bias
    groups = int(cfg.get("moe_n_group", 1))
    if groups > 1 and group_step:
        grouped = choice.reshape(choice.shape[:-1] + (groups, -1))
        score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(score, int(cfg["moe_topk_group"]))
        kept = jnp.any(keep[..., None] == jnp.arange(groups), axis=-2)
        choice = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(choice.shape)
    _, idx = jax.lax.top_k(choice, int(cfg["moe_top_k"]))
    w = jnp.take_along_axis(sc, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, float(cfg["moe_route_scale"]) * w


def routed_experts(m, p, cfg, offset=None, group_step=True):
    """The part of an expert layer's result that the experts in ``p`` give
    (ids ``offset`` .. ``offset`` + held - 1): a loop over them, each
    applied to every token and weighted by what the router gave it."""
    held = p["experts"]["w1"].shape[0]
    offset = int(cfg.get("moe_expert_offset", 0)) if offset is None else offset
    idx, w = route(m, p["router_kernel"], p["e_score_correction_bias"], cfg, group_step)

    def one(out, inp):
        e, pe = inp
        weight = jnp.sum(jnp.where(idx == offset + e, w, 0.0), axis=-1)
        return out + weight[..., None] * _swiglu(m, pe), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (jnp.arange(held), p["experts"]))
    return out


def expert_layer(m, p, cfg, group_step=True):
    out = routed_experts(m, p, cfg, group_step=group_step)
    return out + _swiglu(m, p["shared"]) if "shared" in p else out


def _layer(x, p, cfg, group_step=True):
    eps = float(cfg["norm_eps"])
    nope = int(cfg["qk_nope_head_dim"])
    kl = int(cfg["kv_lora_rank"])
    a = _rms(x, p["ln_1"]["scale"], eps)
    at = p["attn"]
    c_q = _rms(a @ at["q_a_kernel"], at["q_a_norm"], eps)
    q = (c_q @ at["q_b_kernel"]).reshape(c_q.shape[:2] + (at["k_b_kernel"].shape[1], -1))
    kv = a @ at["kv_a_kernel"]
    c = _rms(kv[..., :kl], at["kv_a_norm"], eps)
    k_r = _rope(kv[..., kl:], cfg)
    q_r = _rope(q[..., nope:], cfg)
    k_nope = jnp.einsum("bsc,cnd->bsnd", c, at["k_b_kernel"])
    v = jnp.einsum("bsc,cnd->bsnd", c, at["v_b_kernel"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None], k_nope.shape[:-1] + k_r.shape[-1:])], -1)
    o = _attention(jnp.concatenate([q[..., :nope], q_r], -1), k, v, softmax_scale(cfg))
    h = x + jnp.einsum("bsnd,ndh->bsh", o, at["out_kernel"])
    m = _rms(h, p["ln_2"]["scale"], eps)
    if "router_kernel" in p["mlp"]:
        return h + expert_layer(m, p["mlp"], cfg, group_step)
    return h + _swiglu(m, p["mlp"])


def _f32(tree, weight_dtype=None):
    """A layer's leaves in float32; ``weight_dtype`` (a control: the nearest
    precision below the configuration's) rounds every matrix through it
    first."""
    def up(a):
        if weight_dtype is not None and a.ndim >= 2:
            a = a.astype(weight_dtype)
        return a.astype(jnp.float32)

    return jax.tree.map(up, tree)


def layers_of(params):
    """One dict a layer, leading dense layers first: the served tree's
    ``blocks``, or the slices of a tree whose layers are stacked on a
    leading axis (``dense_layers`` then ``layers``; an expert layer then
    reads its bias from ``e_score_correction_bias`` [layers, experts])."""
    if "blocks" in params:
        return list(params["blocks"])
    out = []
    for name in ("dense_layers", "layers"):
        if name in params:
            n = jax.tree.leaves(params[name])[0].shape[0]
            out += [jax.tree.map(lambda a, i=i: a[i], params[name]) for i in range(n)]
    return out


def hidden(params, tokens, cfg, weight_dtype=None, group_step=True):
    """tokens [b, s] int -> final-normed hidden [b, s, h] float32.  Each
    layer's weights are upcast when the layer runs and dropped after it."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embeddings"]["word"][tokens])
        for lp in layers_of(params):
            x = _layer(x, _f32(lp, weight_dtype), cfg, group_step)
        return _rms(x, params["final_ln"]["scale"].astype(jnp.float32), float(cfg["norm_eps"]))


def logits(params, tokens, cfg, at=None, weight_dtype=None, group_step=True):
    """tokens [b, s] int -> logits [b, s, vocab] float32 through the untied
    head; with ``at`` [n] only at those positions ([b, n, vocab])."""
    x = hidden(params, tokens, cfg, weight_dtype, group_step)
    if at is not None:
        x = x[:, at]
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bsh,vh->bsv", x, _f32(params["head"]["kernel"], weight_dtype))
