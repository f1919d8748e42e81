"""Plain reference of the Mellum 2 block (configuration mellum2-12b-a2.5b):
the forward pass in straightforward jax.numpy, float32, matmuls at "highest"
precision; plain softmax attention with the KV heads repeated and the window
as a mask, the rotation from tables made here, the router and every expert as
a loop; no cache, no ring, no kernel, no batching, no sort.  Independent of
``paddlefleetx_tpu.models``: it reads the served parameter tree by its key
names (``blocks``: one dict a SUB-block, so a published layer is two of them:
its attention, then its experts) and the sizes from a plain dict (the
``model`` group of ``configs/mellum2-12b-a2.5b.json``, or a test's toy sizes).

The equations (docs/mellum2.md), as the published ``config.json``
(``model_type: mellum``) gives them.  For layer l of 28, h of width 2304:

    a = rms(h; eps 1e-6);  q = a W_q [32 heads of 128],  k = a W_k,  v = a W_v [4 heads of 128]
    (no bias; query heads 8g .. 8g+7 read KV head g)
    rotation, rotate-half over the whole head (pairs i, i + 64), by the layer's KIND
    (``layer_types[l]``: (l + 1) % 4 == 0 is full_attention, else sliding_attention):
      a WINDOW layer:  inv_freq_i = 500000^(-2i/128)
      a FULL layer, YaRN: inv_freq_i blended with inv_freq_i / 16 by the linear ramp between the
        correction dims of beta_fast 32 and beta_slow 1 at the original 8,192 positions (floored
        and ceiled, the published default), and cos and sin BOTH times attention_factor
        1.2772588722239782 (= 0.1 ln 16 + 1), so the scores gain its square
    scores q k^T / sqrt(128), causal; a window layer lets position i see j only where
    0 <= i - j < 1024; softmax in float32;  h = h + (P v) W_o
    m = rms(h);  p = softmax_float32(m W_r) over all 64 experts;  the 8 largest;
    w_e = p_e / (sum of the 8);  h = h + sum_e w_e W_down_e (silu(W_gate_e m) * (W_up_e m))
    only experts HELD here (ids offset .. offset + held - 1) add anything
    final RMSNorm, untied head over the vocabulary.

In the served tree W_gate is ``w1``, W_up ``w3``, W_down ``w2``; the kind of
sub-block j is character j of ``layer_pattern`` (``W`` window attention, ``*``
full attention, ``E`` experts).

Departures from the published model, all in the configuration's file: one
chip's share of the experts (16 of 64: the renormalisation is over the 8
CHOSEN of all 64, held or not), a served context cap, seeded weights; no
QK-norm, output gate, shared expert or routing bias (the config has no key for
any); the multi-token-prediction head is not loaded.  One departure from
plainness: attention runs in blocks of ``QUERY_BLOCK`` queries (``lax.map``),
so that the [heads, s, s] float32 scores never exist; no value changes.

Controls (each has to FAIL the comparison that decides ``correct``; keyword
arguments of :func:`logits`, or names in ``cfg["control"]``, comma-separated:
the chip runner's way in): ``window_off`` (window layers attend everything),
``yarn_off`` (full layers rotate plainly, factor 1), ``no_renorm`` (the chosen
weights are not renormalised), ``router_bf16`` (the router's product and
softmax in bfloat16); ``weight_dtype`` rounds every matrix through a lower
precision first (float8: the nearest below the configuration's bfloat16)."""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
CONTROLS = ("window_off", "yarn_off", "no_renorm", "router_bf16")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def inv_frequencies(d, cfg, yarn):
    """The d / 2 rotation frequencies: theta^(-2i/d); with ``yarn`` each
    blended with itself / factor by the linear ramp between the correction
    dims of beta_fast and beta_slow at the original context, floored and
    ceiled.  -> (inv_freq [d / 2], the factor on cos and sin)."""
    theta = float(cfg["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * i / d)
    factor = float(cfg.get("rope_scaling_factor", 1.0))
    if not yarn or factor <= 1.0:
        return freq, 1.0
    original = float(cfg["rope_original_max_position"])

    def correction_dim(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(cfg["rope_beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(cfg["rope_beta_slow"]))), d - 1)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / factor * ramp, 0.1 * math.log(factor) + 1.0


def _rotate(x, inv_freq, factor):
    """Rotate-half over all dims of x [b, s, n, d] at positions 0..s-1."""
    s, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return (x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)) * factor


def attention(u, p, cfg, window, yarn):
    """Causal grouped-query attention over one sequence, KV heads repeated;
    ``window`` > 0: position i sees j only where 0 <= i - j < window."""
    q = jnp.einsum("bsh,hnd->bsnd", u, p["q_kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", u, p["k_kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", u, p["v_kernel"])
    inv_freq, factor = inv_frequencies(q.shape[-1], cfg, yarn)
    q, k = _rotate(q, inv_freq, factor), _rotate(k, inv_freq, factor)
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
        seen = cols[None, :] <= rows[:, None]
        if window:
            seen = seen & (rows[:, None] - cols[None, :] < window)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bnqj,bjnd->bqnd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, jnp.arange((s + pad) // blk))  # [blocks, b, blk, n, d]
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, s + pad, n, d)[:, :s]
    return jnp.einsum("bsnd,ndh->bsh", out, p["out_kernel"])


def route(m, router_kernel, cfg, renormalise=True, router_dtype=None):
    """-> (idx [.., k] expert ids over ALL experts, w [.., k] weights): the
    softmax over all experts in float32, the k largest, renormalised over
    the k.  ``router_dtype`` (a control): the product and the softmax in it."""
    dtype = router_dtype or jnp.float32
    p = jax.nn.softmax(m.astype(dtype) @ router_kernel.astype(dtype), axis=-1)
    w, idx = jax.lax.top_k(p.astype(jnp.float32), int(cfg["moe_top_k"]))
    return idx, w / jnp.sum(w, axis=-1, keepdims=True) if renormalise else w


def routed_experts(m, p, cfg, offset=None, renormalise=True, router_dtype=None):
    """The part of an expert layer's result that the experts in ``p`` give
    (ids ``offset`` .. ``offset`` + held - 1): a loop over them, each
    applied to every token and weighted by what the router gave it."""
    held = p["experts"]["w1"].shape[0]
    offset = int(cfg.get("moe_expert_offset", 0)) if offset is None else offset
    idx, w = route(m, p["router_kernel"], cfg, renormalise, router_dtype)

    def one(out, inp):
        e, pe = inp
        weight = jnp.sum(jnp.where(idx == offset + e, w, 0.0), axis=-1)
        y = (jax.nn.silu(m @ pe["w1"]) * (m @ pe["w3"])) @ pe["w2"]
        return out + weight[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (jnp.arange(held), p["experts"]))
    return out


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
    """A sub-block's leaves in float32; ``weight_dtype`` (a control) rounds
    every matrix through it first."""
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


def hidden(params, tokens, cfg, weight_dtype=None, **controls):
    """tokens [b, s] int -> final-normed hidden [b, s, h] float32.  Each
    sub-block's weights are upcast when it runs and dropped after it."""
    on = _controls(cfg, controls)
    eps = float(cfg["norm_eps"])
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embeddings"]["word"][tokens])
        for kind, lp in zip(cfg["layer_pattern"], params["blocks"]):
            lp = _f32(lp, weight_dtype)
            u = _rms(x, lp["ln_1"]["scale"], eps)
            if kind == "W":
                window = 0 if on["window_off"] else int(cfg["sliding_window"])
                x = x + attention(u, lp["attn"], cfg, window, yarn=False)
            elif kind == "*":
                x = x + attention(u, lp["attn"], cfg, 0, yarn=not on["yarn_off"])
            elif kind == "E":
                x = x + routed_experts(
                    u, lp["mlp"], cfg, renormalise=not on["no_renorm"],
                    router_dtype=jnp.bfloat16 if on["router_bf16"] else None)
            else:
                raise ValueError(f"layer_pattern {cfg['layer_pattern']!r}: W, * and E only")
        return _rms(x, params["final_ln"]["scale"].astype(jnp.float32), eps)


def first_router(params, tokens, cfg, **controls):
    """tokens [b, s] int -> (m [b, s, h], idx [b, s, k], w [b, s, k]): the
    float32 normed input of the FIRST expert layer, over the float32 forward
    of the layers before it, and what this reference's router makes of it.
    What a check reads where served tokens cannot see the precision a router
    runs in: the same ``m`` through the program's routing rule has to give
    these weights (``router_bf16`` is its control)."""
    on = _controls(cfg, controls)
    eps = float(cfg["norm_eps"])
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embeddings"]["word"][tokens])
        for kind, lp in zip(cfg["layer_pattern"], params["blocks"]):
            lp = _f32(lp)
            u = _rms(x, lp["ln_1"]["scale"], eps)
            if kind == "E":
                return (u,) + route(u, lp["mlp"]["router_kernel"], cfg, not on["no_renorm"],
                                    jnp.bfloat16 if on["router_bf16"] else None)
            window = int(cfg["sliding_window"]) if kind == "W" and not on["window_off"] else 0
            x = x + attention(u, lp["attn"], cfg, window, yarn=kind == "*" and not on["yarn_off"])
    raise ValueError("no expert layer")


def logits(params, tokens, cfg, at=None, weight_dtype=None, group_step=True, **controls):
    """tokens [b, s] int -> logits [b, s, vocab] float32 through the untied
    head; with ``at`` [n] only at those positions ([b, n, vocab])."""
    del group_step  # the runner's control for a group-limited router: none here
    x = hidden(params, tokens, cfg, weight_dtype, **controls)
    if at is not None:
        x = x[:, at]
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bsh,vh->bsv", x, _f32(params["head"]["kernel"], weight_dtype))
