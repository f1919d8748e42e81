"""Plain reference of the AFMoE block family (configuration trinity-mini):
the forward pass in straightforward jax.numpy, float32, matmuls at "highest"
precision, no kernel, no sort, no cache, no sharding rule.  Independent of
``paddlefleetx_tpu.models``: it reads the program's parameter tree by its
key names and the sizes from a plain dict (the ``model`` group of
``configs/trinity-mini.json``, or a test's toy sizes).

The equations (docs/trinity_mini.md has them with the ``assumed`` list).
``x0 = E[tokens] * sqrt(hidden)``.  For layer ``l``: window and rotation on
where ``(l + 1) % global_attn_every != 0``, else neither:

    a  = rms(x; ln_1)
    q, k, v, g = a Wq, a Wk, a Wv, a Wg        q [s, nq, d]; k, v [s, nkv, d]
    q, k = rms(q; q_norm), rms(k; k_norm)      over d, one scale per layer
    q, k = rope(q, k)                          rotate-half, all d dims, if on
    o  = softmax(q k^T / sqrt(d) + mask) v     KV head h serves query heads g*h..g*h+g-1
    h  = x + rms((o * sigmoid(g)) Wo; post_attn_norm)
    m  = rms(h; ln_2)
    f  = (silu(m W1) * (m W3)) W2                              leading dense layers
    f  = swiglu_shared(m) + sum_j w_j swiglu_{idx_j}(m)        expert layers, where
         s = sigmoid(m Wr) in float32; idx = top_k(s + bias); w = s[idx];
         w = route_scale * w / (sum(w) + 1e-20); only pairs whose expert is
         HELD here (ids offset .. offset + held - 1) add anything
    x' = h + rms(f; post_mlp_norm)

Final rms, logits through the untied head.  The sizes' ``qk_norm``,
``attn_gate``, ``post_norms`` and ``embed_scale_sqrt_hidden`` (all true in the
configuration) switch those four pieces, so that a test can hold the
program's options to this file one at a time; a layer without a router
in its parameters is a dense one.  One departure from plainness:
attention runs in blocks of 512 queries (``lax.map``), each recomputed in
the backward pass, so that the [heads, s, s] float32 scores (8.6 GB at
32 x 8192 x 8192) never exist; no value changes."""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [b, s, n, d]: rotate-half over all d dims, positions 0..s-1."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def _attention(q, k, v, window):
    """q [b, s, nq, d], k, v [b, s, nkv, d] -> [b, s, nq, d]; causal, and
    ``i - j < window`` where a window is given."""
    b, s, nq, d = q.shape
    nkv = k.shape[2]
    q = q.reshape(b, s, nkv, nq // nkv, d)
    blk = min(QUERY_BLOCK, s)
    assert s % blk == 0, (s, blk)
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        scores = jnp.einsum("bqkgd,bjkd->bkgqj", qs, k) / math.sqrt(d)
        rows = i * blk + jnp.arange(blk)
        mask = cols[None, :] <= rows[:, None]
        if window:
            mask = mask & (rows[:, None] - cols[None, :] < window)
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        return jnp.einsum("bkgqj,bjkd->bqkgd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(s // blk))  # [blocks, b, blk, nkv, g, d]
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, nq, d)


def _swiglu(m, p):
    return (jax.nn.silu(m @ p["w1"]) * (m @ p["w3"])) @ p["w2"]


def route(m, router_kernel, bias, cfg):
    """-> (idx [.., k] expert ids over ALL experts, w [.., k] weights)."""
    s = jax.nn.sigmoid(m.astype(jnp.float32) @ router_kernel.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias, int(cfg["moe_top_k"]))
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, float(cfg["moe_route_scale"]) * w


def routed_experts(m, p, bias, cfg):
    """The part of an expert layer's result that the experts held here
    give: a loop over them, each applied to every token and weighted by
    what the router gave it (0 where the token was not routed to it)."""
    held = p["experts"]["w1"].shape[0]
    offset = int(cfg.get("moe_expert_offset", 0))
    idx, w = route(m, p["router_kernel"], bias, cfg)

    @jax.checkpoint
    def one(out, inp):
        e, pe = inp
        weight = jnp.sum(jnp.where(idx == offset + e, w, 0.0), axis=-1)
        return out + weight[..., None] * _swiglu(m, pe), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (jnp.arange(held), p["experts"]))
    return out


def expert_layer(m, p, bias, cfg):
    out = routed_experts(m, p, bias, cfg)
    return out + _swiglu(m, p["shared"]) if "shared" in p else out


def _layer(x, p, bias, cfg, window, rope):
    eps = float(cfg["norm_eps"])
    a = _rms(x, p["ln_1"]["scale"], eps)
    at = p["attn"]
    q = jnp.einsum("bsh,hnd->bsnd", a, at["q_kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", a, at["k_kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", a, at["v_kernel"])
    if cfg["qk_norm"]:
        q, k = _rms(q, at["q_norm"], eps), _rms(k, at["k_norm"], eps)
    if rope:
        q, k = _rope(q, float(cfg["rope_theta"])), _rope(k, float(cfg["rope_theta"]))
    o = _attention(q, k, v, window)
    if cfg["attn_gate"]:
        o = o * jax.nn.sigmoid(jnp.einsum("bsh,hnd->bsnd", a, at["gate_kernel"]))
    o = jnp.einsum("bsnd,ndh->bsh", o, at["out_kernel"])
    h = x + (_rms(o, p["post_attn_norm"]["scale"], eps) if cfg["post_norms"] else o)
    m = _rms(h, p["ln_2"]["scale"], eps)
    if "router_kernel" in p["mlp"]:
        f = expert_layer(m, p["mlp"], bias, cfg)
    else:
        f = _swiglu(m, p["mlp"])
    return h + (_rms(f, p["post_mlp_norm"]["scale"], eps) if cfg["post_norms"] else f)


def layer_kinds(cfg):
    """[(window or 0, rope on)] for every layer, leading dense ones first."""
    every = int(cfg["global_attn_every"])
    out = []
    for l in range(int(cfg["num_layers"])):
        is_global = every > 0 and (l + 1) % every == 0
        out.append((0 if is_global else int(cfg["sliding_window"]), not is_global))
    return out


def logits(params, tokens, cfg, expert_bias=None):
    """tokens [b, s] int -> logits [b, s, vocab] float32.  ``expert_bias``
    [expert layers, experts] (the program's buffer); None = zeros."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        h = p["embeddings"]["word"].shape[1]
        x = p["embeddings"]["word"][tokens]
        if cfg["embed_scale_sqrt_hidden"]:
            x = x * math.sqrt(h)
        kinds = layer_kinds(cfg)
        n_dense = len(kinds) - jax.tree.leaves(p["layers"])[0].shape[0]
        if expert_bias is None:
            expert_bias = jnp.zeros((len(kinds) - n_dense, int(cfg["num_experts"])))
        for l, (window, rope) in enumerate(kinds):
            if l < n_dense:
                lp, bias = jax.tree.map(lambda a: a[l], p["dense_layers"]), None
            else:
                lp = jax.tree.map(lambda a: a[l - n_dense], p["layers"])
                bias = expert_bias[l - n_dense].astype(jnp.float32)
            # jax.checkpoint changes no value: a differentiated reference
            # recomputes each layer instead of keeping its float32 maps
            x = jax.checkpoint(
                lambda x, lp, bias, w=window, r=rope: _layer(x, lp, bias, cfg, w, r))(x, lp, bias)
        x = _rms(x, p["final_ln"]["scale"], float(cfg["norm_eps"]))
        return jnp.einsum("bsh,vh->bsv", x, p["head"]["kernel"])


def loss_from_logits(lg, labels, loss_mask):
    """Masked-mean token cross-entropy."""
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)


def loss(params, tokens, labels, loss_mask, cfg, expert_bias=None):
    return loss_from_logits(logits(params, tokens, cfg, expert_bias), labels, loss_mask)
