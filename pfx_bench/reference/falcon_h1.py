"""Plain reference of the Falcon-H1 block (configuration falcon-h1-34b): the
forward pass in straightforward jax.numpy, float32, matmuls at "highest"
precision; the state-space recurrence as a SEQUENTIAL ``lax.scan`` over tokens
(not the chunked form the program's prefill uses), plain softmax attention with
the KV heads repeated; no cache, no kernel, no batching.  Independent of
``paddlefleetx_tpu.models``: it reads a parameter tree by its key names
(``blocks``: one dict a layer) and the sizes from a plain dict (the ``model``
group of ``configs/falcon-h1-34b.json``, or a test's toy sizes).

The equations (docs/falcon_h1.md), following the family's published modelling
code (``model_type: falcon_h1``) as the configuration's ``assumed`` reads it.
RMSNorm with a plain learned scale, eps ``norm_eps``, no bias anywhere but the
conv's, untied head.  A published layer is two of this tree's layers, ``P``
then ``-``.  Every constant of ``mup_multipliers`` is applied HERE, to
activations, where the published forward applies it:

    x0 = embedding_multiplier E[token]
``P``, h = rms(x; ln_1):
    x' = x + ssm_out_multiplier Mixer(ssm_in_multiplier h)
           + attention_out_multiplier Attn(attention_in_multiplier h)
  Mixer(u) (Mamba-2):
    [z | x | B | C | dt] = (u W_in) * ssm_multipliers, one constant a segment, BEFORE the conv
                                       widths inner | inner | G N | G N | heads, inner = heads x P
    xBC_t = silu(sum_j w_j xBC_{t-(k-1)+j} + b)     depthwise, causal, k taps (zeros before token 0)
    dt_t = softplus(dt_t + dt_bias) (no clamp);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t    S [heads, P, N] float32, head h reads group h // (heads / G)
    y_t = S_t C_t + D x_t
    out = (rms_groups(y * silu(z)) * norm) W_out    the gate BEFORE the norm (mamba_norm_before_gate false)
  Attn(u):
    q = u W_q [n, d];  k = key_multiplier u W_k,  v = u W_v [kv, d]
    q, k rotated (rotate-half over all d dims, rope_theta, no scaling) at positions 0..s-1
    o_h = softmax_{j<=i}(q_h,i . k_j / sqrt(d)) v_j, query head h reads KV head h // (n / kv);  out = concat_h(o_h) W_o
``-``, m = rms(x; ln_1):
    x' = x + mlp_multipliers[1] (silu(mlp_multipliers[0] m W_gate) * m W_up) W_down       (w1, w3, w2)
logits = lm_head_multiplier rms(x; final_ln) W_head^T

**Which tree.**  The constants belong to a checkpoint's UNFOLDED weights, and
``folded=False`` takes such a tree (``model.init``'s): tier-1 holds the
program's fold to that (tests/test_falcon_h1_block.py).  The benchmark's child
hands over the tree the server SERVED from, whose constants are inside its
matrices; with ``folded=True`` (the default, for that caller) each layer's
leaves are divided by their constants first, by :func:`places`, this file's own
reading of where each constant sits.  What that proves on the chip is the
programs (kernels, pages, state, precision) on the served weights, NOT the
fold: a constant the fold forgot is divided out of a matrix that never held it,
and both sides agree.  The fold is tier-1's to hold.

One departure from plainness: attention runs in blocks of ``QUERY_BLOCK``
queries and the head in slices of the vocabulary (``lax.map``), so that
neither the [heads, s, s] scores nor a float32 copy of the 2.67 GB head ever
exists; no value changes.  ``group_step`` is accepted and ignored (the
runner's control for a router; this block has none)."""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
HEAD_SLICE = 16384  # rows of the head upcast at a time, at most

ONES = {"embedding_multiplier": 1.0, "lm_head_multiplier": 1.0, "ssm_in_multiplier": 1.0,
        "ssm_out_multiplier": 1.0, "ssm_multipliers": (1.0,) * 5, "attention_in_multiplier": 1.0,
        "attention_out_multiplier": 1.0, "key_multiplier": 1.0, "mlp_multipliers": (1.0, 1.0)}


def constants(cfg, override=None) -> dict:
    """The published constants by name; ``override`` (a control) replaces some
    in what is APPLIED, and :func:`places` never sees it."""
    return {**ONES, **dict(cfg.get("mup_multipliers") or {}), **dict(override or {})}


def _segments(cfg, c):
    """The in-projection's column constants: ssm_multipliers by segment."""
    inner = int(cfg["ssm_heads"]) * int(cfg["ssm_head_dim"])
    gn = int(cfg["ssm_groups"]) * int(cfg["ssm_state"])
    widths = (inner, inner, gn, gn, int(cfg["ssm_heads"]))
    return jnp.concatenate([jnp.full((w,), float(m), jnp.float32)
                            for w, m in zip(widths, c["ssm_multipliers"])])


def places(cfg, c) -> dict:
    """(group, leaf) -> what a FOLDED tree's leaf holds beside the
    checkpoint's matrix: this file's reading of the published forward."""
    return {
        ("ssm", "in_kernel"): c["ssm_in_multiplier"] * _segments(cfg, c),
        ("ssm", "out_kernel"): c["ssm_out_multiplier"],
        ("attn", "q_kernel"): c["attention_in_multiplier"],
        ("attn", "k_kernel"): c["attention_in_multiplier"] * c["key_multiplier"],
        ("attn", "v_kernel"): c["attention_in_multiplier"],
        ("attn", "out_kernel"): c["attention_out_multiplier"],
        ("mlp", "w1"): c["mlp_multipliers"][0],
        ("mlp", "w2"): c["mlp_multipliers"][1],
    }


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def mamba_mixer(u, p, cfg, c, gate_after_norm=False):
    """u [b, s, h] -> ([b, s, h], the state S after the last token [b, heads,
    P, N]): the recurrence token by token.  ``gate_after_norm`` is a control."""
    heads, hd = int(cfg["ssm_heads"]), int(cfg["ssm_head_dim"])
    n, groups, taps = int(cfg["ssm_state"]), int(cfg["ssm_groups"]), int(cfg["ssm_conv"])
    inner, gn = heads * hd, groups * n
    b, s, _ = u.shape
    zxd = (u @ p["in_kernel"]) * _segments(cfg, c)
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:2 * inner + 2 * gn], zxd[..., 2 * inner + 2 * gn:]
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
    y = (jnp.moveaxis(y, 0, 1) + p["D"][:, None] * x).reshape(b, s, inner)

    def norm(g):
        g = g.reshape(b, s, groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + float(cfg["norm_eps"]))
        return g.reshape(b, s, inner) * p["norm"]

    g = norm(y) * jax.nn.silu(z) if gate_after_norm else norm(y * jax.nn.silu(z))
    return g @ p["out_kernel"], last


def _rope(x, theta):
    s, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * theta ** (
        -2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def attention(u, p, cfg, c, rotate=True):
    """Causal grouped-query attention, KV heads repeated, q and k rotated.
    ``rotate=False`` is a control."""
    q = jnp.einsum("bsh,hnd->bsnd", u, p["q_kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", u, p["k_kernel"]) * c["key_multiplier"]
    v = jnp.einsum("bsh,hnd->bsnd", u, p["v_kernel"])
    if rotate:
        q, k = (_rope(t, float(cfg["rope_theta"])) for t in (q, k))
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


def swiglu(m, p, c):
    gate, down = c["mlp_multipliers"]
    return down * ((jax.nn.silu(gate * (m @ p["w1"])) * (m @ p["w3"])) @ p["w2"])


def _layer(x, p, kind, cfg, c, rotate=True, gate_after_norm=False):
    """-> (x', the mixer's last state or None)."""
    u = _rms(x, p["ln_1"]["scale"], float(cfg["norm_eps"]))
    state = None
    if kind in "MP":
        mixed, state = mamba_mixer(c["ssm_in_multiplier"] * u, p["ssm"], cfg, c, gate_after_norm)
        x = x + c["ssm_out_multiplier"] * mixed
    if kind in "*P":
        x = x + c["attention_out_multiplier"] * attention(
            c["attention_in_multiplier"] * u, p["attn"], cfg, c, rotate)
    if kind == "-":
        x = x + swiglu(u, p["mlp"], c)
    return x, state


def round_through(a, dtype):
    """``a`` rounded to the values ``dtype`` holds, in float32 ARITHMETIC (on
    the v5e a cast through float8 inside jit is not the format's rounding:
    ``reference/nemotron_h.py`` says what it is)."""
    info = jnp.finfo(dtype)
    a = a.astype(jnp.float32)
    _, exponent = jnp.frexp(a)  # |a| = m 2^exponent, m in [0.5, 1)
    step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(exponent, info.minexp + 1) - (info.nmant + 1))
    return jnp.clip(jnp.round(a / step) * step, -float(info.max), float(info.max))


def _checkpoint(leaf, held=1.0, weight_dtype=None):
    """A leaf in float32 as the CHECKPOINT holds it: a folded leaf divided by
    what it holds beside the checkpoint's matrix.  ``weight_dtype`` (a
    control: the nearest precision below the configuration's) rounds every
    matrix through it, at the checkpoint's scale (a folded head, 1/128 of it,
    would fall under float8's smallest numbers whole)."""
    a = leaf.astype(jnp.float32) / held
    return round_through(a, weight_dtype) if weight_dtype is not None and a.ndim >= 2 else a


def _checkpoint_layer(lp, cfg, folded, weight_dtype=None):
    """One layer's leaves as the checkpoint holds them (:func:`places`)."""
    held = places(cfg, constants(cfg)) if folded else {}
    return {group: {name: _checkpoint(leaf, held.get((group, name), 1.0), weight_dtype)
                    for name, leaf in leaves.items()}
            for group, leaves in lp.items()}


def _embed(params, tokens, cfg, c, folded, weight_dtype=None):
    held = constants(cfg)["embedding_multiplier"] if folded else 1.0
    return c["embedding_multiplier"] * _checkpoint(params["embeddings"]["word"][tokens], held, weight_dtype)


def hidden(params, tokens, cfg, weight_dtype=None, folded=True, rotate=True, gate_after_norm=False,
           override=None):
    """tokens [b, s] int -> final-normed hidden [b, s, h] float32.  Each
    layer's weights are upcast when the layer runs and dropped after it.
    ``rotate``, ``gate_after_norm`` and ``override`` (constants applied as
    other than published) are controls: each has to fail the check."""
    c = constants(cfg, override)
    with jax.default_matmul_precision("highest"):
        x = _embed(params, tokens, cfg, c, folded, weight_dtype)
        for kind, lp in zip(cfg["layer_pattern"], params["blocks"]):
            x, _ = _layer(x, _checkpoint_layer(lp, cfg, folded, weight_dtype), kind, cfg, c,
                          rotate, gate_after_norm)
        return _rms(x, params["final_ln"]["scale"].astype(jnp.float32), float(cfg["norm_eps"]))


def first_state(params, tokens, cfg, folded=True):
    """tokens [b, s] int -> the recurrent state of the FIRST layer with one
    (a ``P`` layer, here the first of the stack) after the last token, [b,
    heads, P, N] float32: what a served row that has read these tokens keeps
    there.  Its input is the embedding's rows on both sides, so it differs
    from the program's by the projections' rounding and by the precision the
    state itself is kept in, not by what earlier layers have drifted."""
    c = constants(cfg)
    first = min(cfg["layer_pattern"].index(k) for k in "MP" if k in cfg["layer_pattern"])
    with jax.default_matmul_precision("highest"):
        x = _embed(params, tokens, cfg, c, folded)
        for kind, lp in zip(cfg["layer_pattern"][:first + 1], params["blocks"]):
            x, state = _layer(x, _checkpoint_layer(lp, cfg, folded), kind, cfg, c)
        return state


def logits(params, tokens, cfg, at=None, weight_dtype=None, group_step=True, folded=True,
           rotate=True, gate_after_norm=False, override=None):
    """tokens [b, s] int -> logits [b, s, vocab] float32 through the untied
    head; with ``at`` [n] only at those positions ([b, n, vocab])."""
    del group_step  # no router: nothing to switch off
    c = constants(cfg)
    x = hidden(params, tokens, cfg, weight_dtype, folded, rotate, gate_after_norm, override)
    if at is not None:
        x = x[:, at]
    head = params["head"]["kernel"]
    held = constants(cfg)["lm_head_multiplier"] if folded else 1.0
    vocab = head.shape[0]
    rows = max(d for d in range(1, min(vocab, HEAD_SLICE) + 1) if vocab % d == 0)
    with jax.default_matmul_precision("highest"):
        def some(i):  # a slice of the head, read where it lies
            w = jax.lax.dynamic_slice_in_dim(head, i * rows, rows, axis=0)
            return jnp.einsum("bsh,vh->bsv", x, _checkpoint(w, held, weight_dtype))

        out = jax.lax.map(some, jnp.arange(vocab // rows))  # [slices, b, s, rows]
    out = jnp.moveaxis(out, 0, 2).reshape(x.shape[0], x.shape[1], vocab)
    return c["lm_head_multiplier"] * out
