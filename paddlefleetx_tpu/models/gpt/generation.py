"""GPT autoregressive generation: KV-cache decode + logits processors.

Reference: ``GPTForGeneration`` (single_model.py:898-1320 — prepare inputs,
logits processors, per-token sample loop with incremental KV-cache decode)
and ``processor.py`` (LogitsProcessorList etc.).

TPU-native shape discipline: the reference's dynamic Python while-loop
becomes a bounded ``lax.while_loop`` over ``max_dec_len`` slots with an
``unfinished`` flag (padded static shapes; XLA traces one step) that exits
as soon as every row has emitted EOS (beam search keeps a fixed-trip
``lax.scan``).
The KV cache is a preallocated [layers, b, heads, max_len, head_dim] pair
(heads-major so the flash-decode kernel's block tiling keeps (seq, dim)
minor — ``ops/decode_attention.py``) updated with ``dynamic_update_slice``;
prefill packs the prompt in one forward.  The decode step attends only
over cache blocks ``< ceil((pos+t)/block)``, not the whole buffer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.models.gpt.model import (
    ShardingCtx,
    _constrain,
    latent_attention_expanded,
    latent_projections,
    latent_softmax_scale,
    layer_norm,
    layer_rope_at,
    rms_norm,
)
from paddlefleetx_tpu.ops.decode_attention import (
    decode_attention,
    kv_cache_dtype,
    kv_cache_len,
    latent_page_write,
    live_slots,
    mla_paged_decode_attention,
    mla_work_list,
    paged_decode_attention,
    quantize_kv,
    window_view,
)
from paddlefleetx_tpu.ops.sampling import filtered_logits, sample_logits
from paddlefleetx_tpu.ops.speculative import (
    SpecConfig,
    ngram_propose,
    speculative_verify,
)


class KVCache(NamedTuple):
    """Contiguous decode cache.  ``k``/``v`` are [layers, b, heads,
    max_len, head_dim] in the model dtype — or int8 under
    ``kv_dtype`` "int8", in which case ``k_scale``/``v_scale`` [layers, b,
    heads, max_len] carry the per-(slot, head) quantization scales
    written alongside every cache update (quantize-on-write,
    dequantize-in-kernel — ``ops/decode_attention``)."""

    k: jax.Array  # [layers, b, heads, max_len, head_dim]
    v: jax.Array
    k_scale: Optional[jax.Array] = None  # [layers, b, heads, max_len]
    v_scale: Optional[jax.Array] = None


def init_cache(
    cfg: GPTConfig, batch: int, max_len: int, dtype=None, kv_dtype: str = ""
) -> KVCache:
    """``kv_dtype`` (the serving path passes ``--kv-dtype`` / the
    ``Generation.speculative.kv_dtype`` config value through): "" or "bf16"
    keeps the cache in the model dtype, "int8" allocates the quantized
    pair plus its scale planes (HBM bytes per slot halve vs bf16).

    ``max_len`` is the number of slots the caller needs; the buffer is
    :func:`~paddlefleetx_tpu.ops.decode_attention.kv_cache_len` of it —
    rounded up to the alignment the decode kernel's block loads must be
    provably on.  The slack is never visited."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    quant = kv_cache_dtype(kv_dtype) == "int8"
    shape = (cfg.num_layers, batch, cfg.num_attention_heads,
             kv_cache_len(max_len, quant), cfg.head_dim)
    if quant:
        sshape = shape[:-1]
        return KVCache(
            jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
            jnp.zeros(sshape, jnp.float32), jnp.zeros(sshape, jnp.float32),
        )
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


# ---------------------------------------------------------------------------
# Which leaves the serving forwards compute on in ``cfg.dtype``
# ---------------------------------------------------------------------------

# group of the tree -> the leaves below consumed in the compute dtype:
# matmul operands, the biases added to their results, the embedding
# tables.  Every other leaf (the LayerNorm scales and biases) enters
# ``layer_norm``'s float32 arithmetic as stored; rounding it would be a
# different result.  ONE list: the forwards cast through ``_in_dtype`` at
# the point of use, ``serving_params`` once for a server that keeps the
# tree — a weight added to a forward and not here shows as a per-step
# convert (tests/test_serving.py holds the decode step to none).
COMPUTE_DTYPE_LEAVES = {
    "embeddings": ("word", "position"),
    "attn": ("qkv_kernel", "qkv_bias", "out_kernel", "out_bias",
             # latent attention (the norms' scales stay float32)
             "q_a_kernel", "q_b_kernel", "kv_a_kernel", "k_b_kernel", "v_b_kernel",
             # a layer_pattern block's grouped-query attention
             "q_kernel", "k_kernel", "v_kernel"),
    # a state-space mixer: its conv's bias, dt's bias, A, D and the gated
    # norm's scale stay float32
    "ssm": ("in_kernel", "conv_kernel", "out_kernel"),
    "mlp": ("fc_in_kernel", "fc_in_bias", "fc_out_kernel", "fc_out_bias",
            "w1", "w3", "w2"),
    # an expert layer: the router's kernel and its correction bias stay
    # float32 (the choice is a discontinuity, moe.sigmoid_route)
    "experts": ("w1", "w3", "w2"),
    "shared": ("w1", "w3", "w2"),
    "head": ("kernel",),
}


def _in_dtype(group: str, p: Dict[str, Any], dtype) -> Dict[str, Any]:
    """``p`` (the ``group`` sub-dict of a parameter tree) with the group's
    COMPUTE_DTYPE_LEAVES as ``dtype``.  On a leaf already in ``dtype``
    (a server's tree) the cast traces to nothing."""
    names = COMPUTE_DTYPE_LEAVES[group]
    return {k: v.astype(dtype) if k in names else v for k, v in p.items()}


def serving_params(params: Dict[str, Any], cfg: GPTConfig) -> Dict[str, Any]:
    """The tree a server should HOLD: every leaf the serving forwards cast
    (COMPUTE_DTYPE_LEAVES) already in ``cfg.dtype``, every other leaf as
    it is.  Training keeps float32 masters and casts once a step among
    thousands of tokens of work; a decode step is its own dispatch with
    the tree as an argument, so a float32 tree is converted again for a
    handful of tokens, every step, to the same bits.  Each matmul consumed
    ``round(w)`` before and consumes it after: logits do not change.

    A ``float32`` configuration keeps its leaves.  Otherwise the casts run
    leaf by leaf, each waited for, and this function keeps no reference to
    a leaf it has replaced: a caller that hands over its only reference to
    the tree (``GenerationServer``) never holds two whole trees.  Leaves
    keep their sharding: each device converts its shard.

    The described block's tree comes back with its layers UNSTACKED
    (:func:`unstack_layers`): ``blocks``, a tuple of one dict a layer.

    A muP checkpoint's constants (``cfg.mup_multipliers``) are NOT applied
    here: this function runs again on the tree a server already holds, and a
    fold is no cast.  The tree that comes in has them inside its weights
    (``models/gpt/convert.py`` ``fold_mup``, once, where the checkpoint is
    converted)."""
    dtype = jnp.dtype(cfg.dtype)
    # a layer_pattern tree is born with ``blocks``; any other has them once served
    if dtype == jnp.float32 or ("blocks" in params and not cfg.layer_pattern):
        return params if cfg.classic_block else unstack_layers(params, cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    del params
    flat.reverse()
    out = []
    while flat:
        path, x = flat.pop()
        if _compute_dtype_leaf(path, x.dtype, dtype):
            x = jax.block_until_ready(x.astype(dtype))
        out.append(x)
    params = treedef.unflatten(out)
    return params if cfg.classic_block else unstack_layers(params, cfg)


def unstack_layers(params: Dict[str, Any], cfg: GPTConfig) -> Dict[str, Any]:
    """The described block's tree as it is SERVED: ``dense_layers`` and
    ``layers`` (stacked on a leading axis for the training scan) become
    ``blocks``, a tuple of one dict a layer, leading dense layers first.
    A layer loop over a stack slices each layer's weights out of it, and
    what feeds a Mosaic call (the grouped products' expert weights) is
    then COPIED out first: 0.7 GB a layer, every decode step (compiled for
    the v5e).  Unstacked, every weight is read where it lies.  An expert
    layer gains its routing bias ``e_score_correction_bias`` [experts]
    (float32 zeros unless the tree brings one): what a served checkpoint
    carries trained."""
    if "blocks" in params:
        return _with_routing_bias(params, cfg)  # a layer_pattern tree is born unstacked
    stacks = [params[k] for k in ("dense_layers", "layers") if k in params]
    blocks = []
    for stack in stacks:
        n = jax.tree.leaves(stack)[0].shape[0]
        for l in range(n):
            blocks.append(jax.tree.map(lambda a: jax.block_until_ready(a[l]), stack))
    del stacks
    out = {k: v for k, v in params.items() if k not in ("dense_layers", "layers")}
    out["blocks"] = tuple(blocks)
    return _with_routing_bias(out, cfg)


def _compute_dtype_leaf(path, dtype_of_leaf, dtype) -> bool:
    group, name = (getattr(k, "key", None) for k in path[-2:])
    return name in COMPUTE_DTYPE_LEAVES.get(group, ()) and dtype_of_leaf != dtype


def init_serving_params(cfg: GPTConfig, key: jax.Array, shardings=None) -> Dict[str, Any]:
    """The tree ``serving_params(init(cfg, key), cfg)`` gives, made ONE LEAF
    AT A TIME: a leaf's float32 original is cast and freed before the next
    leaf is made, so the lifetime peak is the tree at rest plus one leaf in
    float32.  (A float32 tree of a model whose bfloat16 weights fill half
    the chip cannot exist on it at all.)  The same keys and the same
    operations as ``models.common.init_params`` then ``serving_params``, so
    the same values to the bit; the described block's layers are made unstacked
    (each layer's leaf from the key its slice of the stack would get).  With
    ``mup_multipliers`` each float32 leaf is folded before its cast
    (``convert.fold_mup``, which ``serving_params`` leaves to the converter):
    the tree is ``serving_params(fold_mup(init(cfg, key), cfg), cfg)``.
    ``shardings``: a tree of shardings matching ``gpt_specs`` (a mesh;
    the GPT-2 block only), or None."""
    from paddlefleetx_tpu.models.common import ParamSpec
    from paddlefleetx_tpu.models.gpt.convert import fold_mup_leaf
    from paddlefleetx_tpu.models.gpt.model import _block_layer_specs, gpt_specs

    dtype = jnp.dtype(cfg.dtype)
    is_spec = lambda x: isinstance(x, ParamSpec)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(gpt_specs(cfg), is_leaf=is_spec)
    keys = jax.random.split(key, len(flat))
    places = [None] * len(flat) if shardings is None else treedef.flatten_up_to(shardings)

    def finish(path, x):
        # seeded weights stand for a checkpoint: its muP constants go into them here
        x = fold_mup_leaf(path, x, cfg)
        if _compute_dtype_leaf(path, x.dtype, dtype):
            x = x.astype(dtype)  # the float32 original is freed as this returns
        return x

    def make(path, spec, k, place=None):
        init, n = getattr(spec.init, "slabs", (spec.init, 1))
        if n == 1:
            x = finish(path, init(k, spec.shape, spec.dtype))
            return jax.block_until_ready(x if place is None else jax.device_put(x, place))
        # a leaf too large to exist in float32 beside the tree (``common.slab_init``):
        # each slab of rows is drawn, finished and written into the leaf before the next
        rows = spec.shape[0] // n
        x = None
        for i, k_i in enumerate(jax.random.split(k, n)):
            slab = finish(path, init(k_i, (rows,) + tuple(spec.shape[1:]), spec.dtype))
            if x is None:
                x = jnp.zeros(spec.shape, slab.dtype)
            x = jax.block_until_ready(_write_rows(x, slab, i * rows))
        return x

    if cfg.classic_block:
        return treedef.unflatten(
            [make(path, spec, k, place) for (path, spec), k, place in zip(flat, keys, places)])
    if shardings is not None:
        raise ValueError("tensor parallelism: the described block is served on one device")
    if cfg.layer_pattern:  # its specs are unstacked already
        return _with_routing_bias(treedef.unflatten(
            [make(path, spec, k) for (path, spec), k in zip(flat, keys)]), cfg)
    # the stacked leaf of n layers draws layer l from split(leaf key, n)[l]
    n_dense = cfg.leading_dense_layers
    stacks = {"dense_layers": (n_dense, _block_layer_specs(cfg, False), 0),
              "layers": (cfg.num_layers - n_dense,
                         _block_layer_specs(cfg, cfg.moe_dropless), n_dense)}
    blocks = [{} for _ in range(cfg.num_layers)]
    out: Dict[str, Any] = {}

    def put(tree, names, leaf):
        for name in names[:-1]:
            tree = tree.setdefault(name, {})
        tree[names[-1]] = leaf

    for (path, spec), k in zip(flat, keys):
        names = [p.key for p in path]
        if names[0] not in stacks:
            put(out, names, make(path, spec, k))
            continue
        n, inner, first = stacks[names[0]]
        for name in names[1:]:
            inner = inner[name]
        for l, k_l in enumerate(jax.random.split(k, n)):
            put(blocks[first + l], names[1:], make(path, inner, k_l))
    out["blocks"] = tuple(blocks)
    return _with_routing_bias(out, cfg)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows(leaf, slab, at):
    return jax.lax.dynamic_update_slice_in_dim(leaf, slab, at, axis=0)


def _with_routing_bias(params, cfg: GPTConfig):
    for blk in params["blocks"]:
        if "router_kernel" in blk["mlp"]:
            blk["mlp"].setdefault(
                "e_score_correction_bias", jnp.zeros((cfg.num_experts,), jnp.float32))
    return params


def check_servable(cfg: GPTConfig) -> None:
    """Which blocks the serving forwards know (docs/serving.md "What a block
    must provide"): the GPT-2 block, the described block with latent
    attention (a dense SwiGLU or a dropless expert MLP; one residual stream or,
    under ``hc_mult``, a stream of several copies mixed by maps), and a
    ``layer_pattern`` block (state-space, grouped-query attention, full or
    over a window, and expert layers, one sub-block a layer).  Anything else
    raises, naming the option that is in the way."""
    if cfg.classic_block:
        if cfg.num_experts > 1:
            raise ValueError("serving knows no capacity-factor expert layer (num_experts)")
        return
    if cfg.layer_pattern:
        return  # GPTConfig refuses what such a block does not take
    for option in ("num_kv_heads", "attn_head_dim", "qk_norm", "attn_gate", "post_norms",
                   "sliding_window", "global_attn_every", "embed_scale_sqrt_hidden"):
        if getattr(cfg, option):
            raise ValueError(
                f"serving the described block: {option} is not served yet (per-head pools "
                "of shared KV heads and a window-aware allocator are ROADMAP queue 2)")
    if not cfg.latent_attention:
        raise ValueError("serving the described block needs latent attention (kv_lora_rank): "
                         "a per-head rope block has no serving forward yet")


# ---------------------------------------------------------------------------
# Cache-aware forward (shares weights with model.gpt_specs; the training
# forward in model.py stays cache-free)
# ---------------------------------------------------------------------------


def _decoder_layer(
    p: Dict[str, Any], x: jax.Array, ctx: Optional[ShardingCtx], attend: Callable
):
    """One GPT-2 decoder layer over x [b, t, h], in the serving forwards'
    spelling.  ``attend(q, k, v)`` is the caller's half: it writes the
    chunk's k/v [b, t, heads, head_dim] into its cache, attends q over
    that cache and returns ``(attn_out [b, t, heads, head_dim], cache
    state)``; the state comes back beside the layer's output untouched.
    Under TP serving (reference GPTForGenerationHybrid
    hybrid_model.py:1209) q and the caches stay ``heads``-sharded over
    the model axis and GSPMD inserts the output projection's row-psum."""
    dtype = x.dtype

    y = layer_norm(x, p["ln_1"]["scale"], p["ln_1"]["bias"], ctx=ctx)
    attn = _in_dtype("attn", p["attn"], dtype)
    qkv = jnp.einsum("bsh,htnd->bstnd", y, attn["qkv_kernel"])
    qkv = qkv + attn["qkv_bias"][None, None]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    q = _constrain(ctx, q, ("batch", None, "heads", "kv"))

    attn_out, kv_state = attend(q, k, v)
    attn_out = jnp.einsum(
        "bsnd,ndh->bsh", attn_out, attn["out_kernel"]
    ) + attn["out_bias"]
    x = x + attn_out

    y = layer_norm(x, p["ln_2"]["scale"], p["ln_2"]["bias"], ctx=ctx)
    mp = _in_dtype("mlp", p["mlp"], dtype)
    y = y @ mp["fc_in_kernel"] + mp["fc_in_bias"]
    y = jax.nn.gelu(y, approximate=True)
    y = y @ mp["fc_out_kernel"] + mp["fc_out_bias"]
    return x + y, kv_state


def _layer_with_cache(
    p: Dict[str, Any],
    x: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx] = None,
    kv_valid_from: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
):
    """One decoder layer over x [b, t, h] writing K/V at offset ``pos``.

    Attends over cache[:pos+t] via the length-aware blocked kernel
    (``ops/decode_attention``): only cache blocks up to ceil((pos+t)/block)
    are visited, with the causal + ``kv_valid_from`` left-pad masks folded
    into per-block masking.  The sharded path uses the lax spelling of the
    blocked loop (GSPMD partitions it freely, a pallas_call would need
    shard_map).
    """
    def attend(q, k, v):
        # cache layout [b, heads, max_len, head_dim]: transpose the (small)
        # step chunk, never the cache.  Under int8 the chunk quantizes HERE
        # (quantize-on-write) and the scale planes update alongside — the
        # kernels below dequantize in-kernel, so the cache only ever streams
        # as int8.
        kc = k.transpose(0, 2, 1, 3)
        vc = v.transpose(0, 2, 1, 3)
        if k_scale is not None:
            kq, ks = quantize_kv(kc)
            vq, vs = quantize_kv(vc)
            k_new = jax.lax.dynamic_update_slice(k_cache, kq, (0, 0, pos, 0))
            v_new = jax.lax.dynamic_update_slice(v_cache, vq, (0, 0, pos, 0))
            ks_new = jax.lax.dynamic_update_slice(k_scale, ks, (0, 0, pos))
            vs_new = jax.lax.dynamic_update_slice(v_scale, vs, (0, 0, pos))
            ks_new = _constrain(ctx, ks_new, ("batch", "heads", None))
            vs_new = _constrain(ctx, vs_new, ("batch", "heads", None))
        else:
            k_new = jax.lax.dynamic_update_slice(k_cache, kc, (0, 0, pos, 0))
            v_new = jax.lax.dynamic_update_slice(v_cache, vc, (0, 0, pos, 0))
            ks_new = vs_new = None
        k_new = _constrain(ctx, k_new, ("batch", "heads", None, "kv"))
        v_new = _constrain(ctx, v_new, ("batch", "heads", None, "kv"))
        attn_out = decode_attention(
            q, k_new, v_new, pos, kv_valid_from=kv_valid_from,
            impl="lax" if ctx is not None else "auto",
            k_scale=ks_new, v_scale=vs_new,
        )
        return attn_out, (k_new, v_new, ks_new, vs_new)

    x, kv_state = _decoder_layer(p, x, ctx, attend)
    return (x, *kv_state)


def forward_cached(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: KVCache,
    pos: jax.Array,
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx] = None,
    position_ids: Optional[jax.Array] = None,
    kv_valid_from: Optional[jax.Array] = None,
) -> Tuple[jax.Array, KVCache]:
    """tokens [b, t] at positions [pos, pos+t) -> (logits [b, t, v], cache).

    ``position_ids`` [b, t] overrides the default pos+arange(t) position
    embedding indices and ``kv_valid_from`` [b] masks cache keys before a
    row's first real token — together they implement left-padded serving
    buckets (each row's real prompt right-aligned at the same width)."""
    dtype = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    emb = _in_dtype("embeddings", params["embeddings"], dtype)
    word, pe = emb["word"], emb["position"]
    if position_ids is None:
        x = word[tokens] + pe[pos + jnp.arange(t)][None, :, :]
    else:
        x = word[tokens] + pe[position_ids]
    x = _constrain(ctx, x, ("batch", None, "embed"))

    quant = cache.k_scale is not None
    if quant:
        def body(x, inp):
            p_l, kc, vc, ksl, vsl = inp
            x, kc, vc, ksl, vsl = _layer_with_cache(
                p_l, x, kc, vc, pos, cfg, ctx, kv_valid_from, ksl, vsl
            )
            return x, (kc, vc, ksl, vsl)

        xs = (params["layers"], cache.k, cache.v, cache.k_scale, cache.v_scale)
        x, (ks, vs, kss, vss) = jax.lax.scan(body, x, xs)
        out_cache = KVCache(ks, vs, kss, vss)
    else:
        def body(x, inp):
            p_l, kc, vc = inp
            x, kc, vc, _, _ = _layer_with_cache(
                p_l, x, kc, vc, pos, cfg, ctx, kv_valid_from
            )
            return x, (kc, vc)

        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache.k, cache.v))
        out_cache = KVCache(ks, vs)
    x = layer_norm(x, params["final_ln"]["scale"], params["final_ln"]["bias"], ctx=ctx)
    logits = jnp.einsum("bsh,vh->bsv", x, word)
    return _constrain(ctx, logits, ("batch", None, "vocab")), out_cache


# ---------------------------------------------------------------------------
# Logits processors (reference processor.py)
# ---------------------------------------------------------------------------


def apply_repetition_penalty(logits, generated_mask_counts, penalty: float):
    """Divide positive / multiply negative logits of already-generated tokens
    (reference RepetitionPenaltyLogitsProcessor)."""
    if penalty == 1.0:
        return logits
    seen = generated_mask_counts > 0
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, penalized, logits)


def apply_min_length(logits, cur_len, min_len: int, eos_token_id: int):
    """Suppress EOS before min_length (reference MinLengthLogitsProcessor)."""
    if min_len <= 0:
        return logits
    return jnp.where(
        (cur_len < min_len)[..., None]
        & (jnp.arange(logits.shape[-1]) == eos_token_id)[None, :],
        -1e10,
        logits,
    )


def apply_forced_token(logits, step, force_at_step: int, token_id: int):
    """Force a specific token at a given decode step (reference
    ForcedBOSTokenLogitsProcessor / ForcedEOSTokenLogitsProcessor)."""
    if token_id < 0:
        return logits
    forced = jnp.full_like(logits, -1e10).at[..., token_id].set(0.0)
    return jnp.where(step == force_at_step, forced, logits)


def apply_hamming_diversity(logits, current_tokens, group_start: int, penalty: float):
    """Penalize tokens already chosen by EARLIER beam groups at this step
    (reference HammingDiversityLogitsProcessor): logits [gb, v];
    current_tokens [gb] holds this step's choices for groups processed so
    far (entries >= group_start are not yet decided and are masked off)."""
    if penalty == 0.0:
        return logits
    vocab = logits.shape[-1]
    decided = jnp.arange(current_tokens.shape[0]) < group_start
    counts = jnp.zeros((vocab,), logits.dtype).at[current_tokens].add(
        decided.astype(logits.dtype)
    )
    return logits - penalty * counts[None, :]


# ---------------------------------------------------------------------------
# Generation loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Reference GPTForGeneration config surface (single_model.py:898-960)."""

    max_dec_len: int = 64
    min_dec_len: int = 1
    decode_strategy: str = "sampling"  # sampling | greedy_search | beam_search
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    eos_token_id: int = 50256
    pad_token_id: int = 0
    # beam search (reference BeamSearchScorer + processor.py)
    num_beams: int = 4
    length_penalty: float = 1.0
    # diverse (group) beam search: HammingDiversityLogitsProcessor
    num_beam_groups: int = 1
    diversity_penalty: float = 0.0
    # ForcedBOS/ForcedEOS processors (-1 = disabled)
    forced_bos_token_id: int = -1
    forced_eos_token_id: int = -1

    def __post_init__(self):
        if self.decode_strategy not in ("sampling", "greedy_search", "beam_search"):
            raise ValueError(
                f"bad decode_strategy {self.decode_strategy!r}; "
                "valid: sampling, greedy_search, beam_search"
            )


def _left_pad_prefill(prompt_len: int, prompt_lens: Optional[jax.Array]):
    """(pad_len [b], prefill position ids [b, P]) for left-padded buckets;
    (None, None) on the unpadded path."""
    if prompt_lens is None:
        return None, None
    pad_len = jnp.int32(prompt_len) - prompt_lens
    pos_ids = jnp.maximum(jnp.arange(prompt_len)[None, :] - pad_len[:, None], 0)
    return pad_len, pos_ids


def bucket_len(longest: int, multiple: int) -> int:
    """THE prompt-bucket formula (next multiple of ``multiple``).

    Single-sourced on purpose: ``pad_prompts`` (the padding itself),
    ``GenerationServer.warmup`` (bucket validation), and the serve-layer
    coalesce key (tools/serve.py ``plan_request``) must all agree on the
    padded width — a drifted copy would silently key fresh compiles for
    coalesced traffic."""
    return ((int(longest) + int(multiple) - 1) // int(multiple)) * int(multiple)


def pad_prompts(prompts, pad_token_id: int, multiple: int = 64):
    """Left-pad a list of variable-length prompts to a shared bucketed
    width (``bucket_len``): serving compiles once per BUCKET, not once
    per prompt length (VERDICT r1 weak #4).

    Returns (padded [b, P] int32 array, prompt_lens [b])."""
    import numpy as np

    P = bucket_len(max(len(p) for p in prompts), multiple)
    out = np.full((len(prompts), P), pad_token_id, np.int32)
    lens = np.zeros((len(prompts),), np.int32)
    for i, p in enumerate(prompts):
        out[i, P - len(p):] = p
        lens[i] = len(p)
    return jnp.asarray(out), jnp.asarray(lens)


def generate(
    params: Dict[str, Any],
    input_ids: jax.Array,
    cfg: GPTConfig,
    gen: GenerationConfig,
    key: Optional[jax.Array] = None,
    ctx: Optional[ShardingCtx] = None,
    prompt_lens: Optional[jax.Array] = None,
    cache: Optional[KVCache] = None,
    return_cache: bool = False,
    spec: Optional[SpecConfig] = None,
    return_spec_stats: bool = False,
) -> jax.Array:
    """input_ids [b, prompt_len] -> generated ids [b, max_dec_len]
    (eos/pad-filled after finish).

    Without ``prompt_lens`` the prompts are taken as right-aligned and
    unpadded.  With ``prompt_lens`` [b], rows are LEFT-padded to a shared
    width (see :func:`pad_prompts`): padded key slots are masked out of
    attention and position ids start at the first real token — the shape
    (and therefore the compiled artifact) depends only on the bucket.

    Pass ``ctx`` to serve on a mesh: the KV cache and attention stay
    heads-sharded over the model axis (TP serving parity with the
    reference's GPTForGenerationHybrid, hybrid_model.py:1209).

    ``cache``: optionally pass a preallocated ``init_cache(cfg, b,
    prompt_len + max_dec_len)`` buffer instead of allocating inside the
    trace — a caller jitting generate can then DONATE it
    (``donate_argnums``) so the per-step ``dynamic_update_slice`` writes
    in place instead of copying the pair each step.  A donated cache is
    CONSUMED: the caller must not touch it after the call.  Donation only
    aliases an input to an OUTPUT buffer, so pair it with
    ``return_cache=True`` — the returned final cache occupies the donated
    buffer and can be donated straight back on the next same-shape call
    (``core/serving.py`` keeps a per-bucket pool doing exactly that;
    stale tail slots are safe because the blocked kernel never visits
    blocks beyond ``pos + t``).

    ``return_cache``: return ``(tokens, final KVCache)`` instead of
    tokens (sampling/greedy only).

    ``spec``: a :class:`~paddlefleetx_tpu.ops.speculative.SpecConfig`
    routes sampling/greedy decode through the speculative while-loop
    (:func:`_generate_speculative`): draft k tokens per iteration,
    verify them in ONE t=k+1 forward, commit the accepted prefix —
    greedy output is token-identical to the plain loop by construction.
    The cache needs ``spec.draft_k`` slack slots past ``prompt_len +
    max_dec_len`` (the verify chunk's rejected tail overruns before the
    rewind); a caller-provided cache must include them.
    ``return_spec_stats`` appends an ``(proposed, accepted)`` int32 pair
    to the return tuple (acceptance telemetry)."""
    if cfg.num_experts > 1 or not cfg.classic_block:
        raise ValueError(
            "generate()'s contiguous cache knows the GPT-2 block without expert layers "
            "only; the described block is served through the paged pools "
            "(--scheduler continuous)")
    if return_spec_stats and spec is None:
        raise ValueError("return_spec_stats needs a SpecConfig")
    b, prompt_len = input_ids.shape
    max_len = prompt_len + gen.max_dec_len
    cache_len = max_len + (spec.draft_k if spec is not None else 0)
    if max_len > cfg.max_position_embeddings:
        # with prompt_lens, position ids are bounded by the REAL lengths,
        # not the bucket width: only reject when the real positions
        # overflow (or the bound cannot be known, i.e. traced lengths)
        real_bound = None
        if prompt_lens is not None:
            try:
                real_bound = int(jax.numpy.max(prompt_lens)) + gen.max_dec_len
            except jax.errors.ConcretizationTypeError:
                real_bound = None  # traced lengths: bucket-width bound applies
        if real_bound is None or real_bound > cfg.max_position_embeddings:
            raise ValueError(
                f"prompt_len {prompt_len} + max_dec_len {gen.max_dec_len} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}"
            )
    if key is None:
        key = jax.random.key(0)
    if gen.decode_strategy == "beam_search":
        if cache is not None or return_cache:
            raise ValueError(
                "cache donation/return is not supported for beam_search (the "
                "beam loop reorders the cache by parent each step)"
            )
        if spec is not None:
            raise ValueError(
                "speculative decoding is not supported for beam_search "
                "(the beam loop reorders the cache by parent each step)"
            )
        return beam_search(params, input_ids, cfg, gen, ctx=ctx, prompt_lens=prompt_lens)
    pad_len, prefill_pos_ids = _left_pad_prefill(prompt_len, prompt_lens)
    if cache is None:
        cache = init_cache(cfg, b, cache_len)
    else:
        want = (cfg.num_layers, b, cfg.num_attention_heads,
                kv_cache_len(cache_len, cache.k_scale is not None),
                cfg.head_dim)
        if cache.k.shape != want:
            raise ValueError(
                f"provided cache shape {cache.k.shape} != required {want} "
                f"(prompt {prompt_len} + max_dec_len {gen.max_dec_len}"
                + (f" + draft_k {spec.draft_k}" if spec is not None else "")
                + ")"
            )
    if spec is not None:
        return _generate_speculative(
            params, input_ids, cfg, gen, spec, key, ctx, prompt_lens,
            pad_len, prefill_pos_ids, cache, return_cache, return_spec_stats,
        )
    vocab = cfg.vocab_size
    valid = (
        jnp.ones((b, prompt_len), jnp.int32)
        if pad_len is None
        else (jnp.arange(prompt_len)[None, :] >= pad_len[:, None]).astype(jnp.int32)
    )
    token_counts0 = jnp.zeros((b, vocab), jnp.int32).at[
        jnp.arange(b)[:, None], input_ids
    ].add(valid)

    # prefill: cache K/V for the prompt; its last-row logits seed the loop
    logits, cache = forward_cached(
        params, input_ids, cache, jnp.int32(0), cfg, ctx,
        position_ids=prefill_pos_ids, kv_valid_from=pad_len,
    )
    last_logits = logits[:, -1, :].astype(jnp.float32)

    class Carry(NamedTuple):
        cache: KVCache
        logits: jax.Array  # [b, vocab] — logits of the position to sample
        pos: jax.Array
        unfinished: jax.Array  # [b] bool
        token_counts: jax.Array
        key: jax.Array

    def step(carry: Carry, i):
        logits = apply_min_length(
            carry.logits, jnp.full((b,), i), gen.min_dec_len, gen.eos_token_id
        )
        logits = apply_repetition_penalty(
            logits, carry.token_counts, gen.repetition_penalty
        )
        logits = apply_forced_token(logits, i, 0, gen.forced_bos_token_id)
        logits = apply_forced_token(
            logits, i, gen.max_dec_len - 1, gen.forced_eos_token_id
        )
        key, sub = jax.random.split(carry.key)
        if gen.decode_strategy == "greedy_search":
            nxt = jnp.argmax(logits, axis=-1)
        else:
            nxt = sample_logits(
                sub, logits, temperature=gen.temperature, top_k=gen.top_k, top_p=gen.top_p
            )
        nxt = jnp.where(carry.unfinished, nxt, gen.pad_token_id)
        unfinished = carry.unfinished & (nxt != gen.eos_token_id)
        counts = carry.token_counts.at[jnp.arange(b), nxt].add(1)
        step_pos_ids = (
            (prompt_lens + i)[:, None] if prompt_lens is not None else None
        )
        new_logits, cache = forward_cached(
            params, nxt[:, None], carry.cache, carry.pos, cfg, ctx,
            position_ids=step_pos_ids, kv_valid_from=pad_len,
        )
        new_carry = Carry(
            cache=cache,
            logits=new_logits[:, -1, :].astype(jnp.float32),
            pos=carry.pos + 1,
            unfinished=unfinished,
            token_counts=counts,
            key=key,
        )
        return new_carry, nxt

    carry0 = Carry(
        cache=cache,
        logits=last_logits,
        pos=jnp.int32(prompt_len),
        unfinished=jnp.ones((b,), bool),
        token_counts=token_counts0,
        key=key,
    )
    # early-exit while_loop: stops as soon as nothing is unfinished (a
    # fixed-trip loop would run a full forward over the batch for every
    # remaining slot).  The buffer starts pad-filled, which is what a
    # finished row emits (nxt is forced to pad_token_id once unfinished
    # is False), so the skipped slots read as if they had run.
    tokens0 = jnp.full((b, gen.max_dec_len), gen.pad_token_id, jnp.int32)

    def loop_cond(st):
        carry, i, _ = st
        return (i < gen.max_dec_len) & jnp.any(carry.unfinished)

    def loop_body(st):
        carry, i, tokens = st
        new_carry, nxt = step(carry, i)
        tokens = jax.lax.dynamic_update_slice(tokens, nxt[:, None], (0, i))
        return new_carry, i + 1, tokens

    carry, _, tokens = jax.lax.while_loop(
        loop_cond, loop_body, (carry0, jnp.int32(0), tokens0)
    )
    return (tokens, carry.cache) if return_cache else tokens  # [b, max_dec_len]


# ---------------------------------------------------------------------------
# Speculative decode loop (contiguous path).  Leviathan et al. 2023 via
# ops/speculative.py: each iteration forwards a [pending, draft_0..k-1]
# chunk (t = k+1) through the SAME cached forward the plain loop uses,
# verifies the drafts against the target's own processed logits, and
# commits the batch-min accepted prefix + the pending token — between 1
# and k+1 tokens per forward instead of exactly 1.
# ---------------------------------------------------------------------------


def _generate_speculative(
    params, input_ids, cfg, gen, spec: SpecConfig, key, ctx, prompt_lens,
    pad_len, prefill_pos_ids, cache, return_cache, return_spec_stats,
):
    """The speculative spelling of generate()'s early-exit while loop.

    Commit discipline: per iteration every row verifies its own k drafts,
    but the batch commits the MINIMUM accepted length m across unfinished
    rows (the contiguous cache writes one shared [b, t] chunk at a
    scalar position, so rows cannot advance independently — the paged
    path's :func:`decode_step_spec` does true per-row commit).  Each
    row's committed tokens are a verified prefix of its own acceptance,
    so greedy output stays token-identical to the plain loop; rows that
    accepted beyond m simply re-verify the surplus next iteration.  Rows
    that hit EOS inside their accepted prefix stop constraining the
    minimum (they are done — pad-substitution covers their tail).

    Cache rewind: the chunk writes K/V at [pos, pos+k]; only [pos,
    pos+m] are committed.  The next iteration's chunk starts at
    pos+m+1 and spans k+1 slots, so every stale slot is rewritten
    BEFORE any attention visits it — the same stale-tail argument as
    the donated serving pool (docs/decode_path.md).  The cache carries
    ``draft_k`` slack slots past prompt+max_dec_len for the final
    iteration's overrun; overrun position ids clamp to the embedding
    table (those slots are never committed)."""
    b, prompt_len = input_ids.shape
    k = spec.draft_k
    K = k + 1
    DEC = gen.max_dec_len
    vocab = cfg.vocab_size
    use_counts = gen.repetition_penalty != 1.0
    greedy = gen.decode_strategy == "greedy_search"
    if key is None:
        key = jax.random.key(0)

    valid = (
        jnp.ones((b, prompt_len), jnp.int32)
        if pad_len is None
        else (jnp.arange(prompt_len)[None, :] >= pad_len[:, None]).astype(jnp.int32)
    )
    token_counts0 = jnp.zeros((b, vocab), jnp.int32).at[
        jnp.arange(b)[:, None], input_ids
    ].add(valid)

    logits, cache = forward_cached(
        params, input_ids, cache, jnp.int32(0), cfg, ctx,
        position_ids=prefill_pos_ids, kv_valid_from=pad_len,
    )
    last_logits = logits[:, -1, :].astype(jnp.float32)

    # pending_0 = the baseline loop's step-0 token, sampled through the
    # identical (single-sourced) processor chain
    p0 = process_step_logits(
        last_logits, jnp.zeros((b,), jnp.int32), token_counts0,
        jnp.full((b,), DEC - 1, jnp.int32), gen,
    )
    key, sub0 = jax.random.split(key)
    if greedy:
        pending0 = jnp.argmax(p0, axis=-1).astype(jnp.int32)
    else:
        pending0 = sample_logits(
            sub0, p0, temperature=gen.temperature, top_k=gen.top_k,
            top_p=gen.top_p,
        ).astype(jnp.int32)

    class SpecCarry(NamedTuple):
        cache: KVCache
        pending: jax.Array    # [b] token for step `emitted`
        pos: jax.Array        # cache slot where pending will be written
        emitted: jax.Array    # committed tokens so far (shared)
        unfinished: jax.Array
        token_counts: jax.Array
        key: jax.Array
        tokens: jax.Array     # [b, DEC + k + 1] (k+1 write slack)
        proposed: jax.Array   # drafted tokens (acceptance telemetry)
        accepted: jax.Array   # committed drafted tokens

    tokens0 = jnp.full((b, DEC + K), gen.pad_token_id, jnp.int32)

    def loop_cond(st: SpecCarry):
        return (st.emitted < DEC) & jnp.any(st.unfinished)

    def loop_body(st: SpecCarry):
        emitted = st.emitted
        # self-draft from the row's own prompt + committed output
        ctx_buf = jnp.concatenate([input_ids, st.tokens], axis=1)
        draft = ngram_propose(
            ctx_buf, prompt_len + emitted, st.pending, k, n=spec.ngram
        )
        chunk = jnp.concatenate([st.pending[:, None], draft], axis=1)

        # ONE t=k+1 forward verifies the whole chunk; overrun position
        # ids clamp to the embedding table (never committed)
        base = (
            prompt_lens if prompt_lens is not None
            else jnp.full((b,), prompt_len, jnp.int32)
        )
        pos_ids = jnp.clip(
            base[:, None] + emitted + jnp.arange(K)[None, :],
            0, cfg.max_position_embeddings - 1,
        )
        logits_all, cache = forward_cached(
            params, chunk, st.cache, st.pos, cfg, ctx,
            position_ids=pos_ids, kv_valid_from=pad_len,
        )

        key, sub = jax.random.split(st.key)
        sv = speculative_verify(
            sub, logits_all.astype(jnp.float32), chunk,
            st.token_counts if use_counts else None,
            st.unfinished, emitted, gen,
        )

        # batch-min commit: rows finished before the window, or finished
        # BY it (EOS inside their accepted prefix), stop constraining
        constraint = jnp.where(
            ~st.unfinished | sv.eos_hit.any(axis=1), k, sv.accepted
        )
        m = jnp.minimum(jnp.min(constraint), DEC - 1 - emitted)

        jmask = jnp.arange(K) <= m  # [K]
        window = jnp.where(jmask[None, :], sv.w, gen.pad_token_id)
        tokens = jax.lax.dynamic_update_slice(st.tokens, window, (0, emitted))
        counts = st.token_counts.at[jnp.arange(b)[:, None], sv.w].add(
            jmask[None, :].astype(jnp.int32)
        )
        unfinished = st.unfinished & ~(sv.eos_hit & jmask[None, :]).any(axis=1)

        # next pending = the token for step emitted + m + 1: the already-
        # accepted surplus draft when the row out-accepted the batch, else
        # the verify candidate (correction / residual / bonus)
        m_col = jnp.full((b, 1), m, jnp.int32)
        beyond = sv.accepted > m
        from_chunk = jnp.take_along_axis(
            chunk, jnp.minimum(m_col + 1, k), axis=1
        )[:, 0]
        from_pend = jnp.take_along_axis(sv.pend, m_col, axis=1)[:, 0]
        pending = jnp.where(
            unfinished,
            jnp.where(beyond, from_chunk, from_pend),
            gen.pad_token_id,
        ).astype(jnp.int32)

        n_alive = st.unfinished.sum().astype(jnp.int32)
        return SpecCarry(
            cache=cache,
            pending=pending,
            pos=st.pos + m + 1,
            emitted=emitted + m + 1,
            unfinished=unfinished,
            token_counts=counts,
            key=key,
            tokens=tokens,
            proposed=st.proposed + k * n_alive,
            accepted=st.accepted + m * n_alive,
        )

    st0 = SpecCarry(
        cache=cache,
        pending=pending0,
        pos=jnp.int32(prompt_len),
        emitted=jnp.int32(0),
        unfinished=jnp.ones((b,), bool),
        token_counts=token_counts0,
        key=key,
        tokens=tokens0,
        proposed=jnp.int32(0),
        accepted=jnp.int32(0),
    )
    st = jax.lax.while_loop(loop_cond, loop_body, st0)
    tokens = st.tokens[:, :DEC]
    out = (tokens,)
    if return_cache:
        out = out + (st.cache,)
    if return_spec_stats:
        out = out + ((st.proposed, st.accepted),)
    return out if len(out) > 1 else tokens


# ---------------------------------------------------------------------------
# Paged decode: block-pool KV cache + the step-wise entry the
# continuous-batching scheduler drives (core/continuous_batching.py).
# The contiguous generate() above runs ONE request set to completion
# inside a fused loop; these functions instead expose ONE decode step
# over a batch of INDEPENDENT rows (own positions, own budgets, own
# block tables into a shared arena), so the host scheduler can admit and
# evict rows at every step boundary.
# ---------------------------------------------------------------------------


class PagedPools(NamedTuple):
    """The paged KV arena: [layers, num_blocks, heads, block, head_dim]
    (heads-major within a block, matching KVCache's tiling rationale).
    Block 0 is the NULL block — never allocated to a sequence; inactive
    batch rows route their writes there (core/paged_cache.py).  Under
    ``kv_dtype`` "int8" the arrays are int8 and ``k_scale``/``v_scale``
    [layers, num_blocks, heads, block] carry per-(slot, head) scale
    tiles stored alongside the arena — each pool block owns its
    [heads, block] scale tile, DMA'd with it by the pallas kernel's
    clamped index map.  The arena is donated into every dispatch, carried
    through the step's layer loop and written in place: a handle handed
    to a dispatch is dead, the returned one is the arena
    (docs/decode_path.md, "The arena's contract").

    What a pool holds of a token comes from the model
    (``GPTConfig.cached_token``): under latent attention ``k`` is the ONE
    pool [layers, num_blocks, 1, kv_lora + rope, block] (a token is one
    COLUMN of its page: the normalised latent, then the rotated shared
    key; tokens minor, ``ops/decode_attention.py`` says why) and there is
    no ``v``: the values are the first kv_lora entries of each column.

    A ``layer_pattern`` block's pools hold pages for its ATTENTION layers
    only ([attention layers, num_blocks, kv_heads, block, head_dim]) and,
    beside them, what a row keeps in its state-space layers whatever its
    length (``GPTConfig.row_state``), per batch SLOT and not per page:
    ``ssm`` [state-space layers, slots, R, state, W] (the recurrent state,
    packed as ``ops/ssm.py`` says) and ``conv`` [state-space layers, slots,
    (taps - 1) * conv_dim] (the last columns the conv saw, oldest first).
    They ride every dispatch with the arena, under the same contract.

    A pattern with WINDOW layers (``W``; docs/mellum2.md) keeps a SECOND
    class of pages in the same arena: ``wk`` / ``wv`` [window layers,
    ring blocks, kv_heads, block, head_dim], with block ids of their own
    (0 the null block again).  A row holds ``GPTConfig.ring_pages`` of them
    for its whole life, as a ring: token t of a window layer lives in ring
    slot ``(t // block) % ring_pages``, so the pages a query's window needs
    never share a slot, whatever the row's length.  ``k`` / ``v`` are then
    the FULL layers' pages alone, which grow with the row."""

    k: jax.Array
    v: Optional[jax.Array] = None
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    ssm: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    wk: Optional[jax.Array] = None
    wv: Optional[jax.Array] = None

    def fields(self) -> Tuple[str, ...]:
        """Names of the arrays these pools hold, in order."""
        return tuple(n for n, x in zip(self._fields, self) if x is not None)

    @classmethod
    def of(cls, fields: Tuple[str, ...], leaves) -> "PagedPools":
        """Pools from their arrays alone (``tuple(x for x in pools if x is
        not None)``: what a compiled entry point takes and gives back)."""
        return cls(**dict(zip(fields, leaves)))


def init_paged_pools(
    cfg: GPTConfig, num_blocks: int, block: int, dtype=None,
    kv_dtype: str = "", slots: int = 0, ring_blocks: int = 0,
) -> PagedPools:
    """``slots`` (a block with ``row_state`` only): the batch's capacity.
    ``ring_blocks`` (a pattern with ``W`` layers only): the blocks of the
    window layers' class, the null block among them."""
    check_servable(cfg)
    quant = kv_cache_dtype(kv_dtype) == "int8"
    if cfg.layer_pattern:
        from paddlefleetx_tpu.ops.ssm import packed_shape

        if quant:
            raise ValueError("kv_dtype int8: pools of shared KV heads are not quantized yet")
        dtype = dtype or jnp.dtype(cfg.dtype)
        (heads, width), _ = cfg.cached_token
        shape = (cfg.kv_layers, num_blocks, heads, block, width)
        pools = PagedPools(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        if cfg.window_layers:
            if ring_blocks < 2:
                raise ValueError("a pattern with W layers needs its ring blocks")
            ring = (cfg.window_layers, ring_blocks, heads, block, width)
            pools = pools._replace(wk=jnp.zeros(ring, dtype), wv=jnp.zeros(ring, dtype))
        if cfg.row_state:
            if slots < 1:
                raise ValueError("a block with row state needs its batch slots")
            (_, ssm, ssm_dtype), (_, conv, conv_dtype) = cfg.row_state
            lead = (cfg.ssm_layers, slots)
            pools = pools._replace(
                ssm=jnp.zeros(lead + packed_shape(*ssm), jnp.dtype(ssm_dtype)),
                conv=jnp.zeros(lead + (conv[0] * conv[1],), jnp.dtype(conv_dtype)))
        return pools
    if cfg.latent_attention:
        if quant:
            raise ValueError("kv_dtype int8: latent pools are not quantized yet")
        (heads, width), = cfg.cached_token
        return PagedPools(jnp.zeros((cfg.num_layers, num_blocks, heads, width, block),
                                    dtype or jnp.dtype(cfg.dtype)))
    shape = (cfg.num_layers, num_blocks, cfg.num_attention_heads, block,
             cfg.head_dim)
    if quant:
        sshape = shape[:-1]
        return PagedPools(
            jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
            jnp.zeros(sshape, jnp.float32), jnp.zeros(sshape, jnp.float32),
        )
    dtype = dtype or jnp.dtype(cfg.dtype)
    return PagedPools(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


class PagedRows(NamedTuple):
    """Per-row decode state the scheduler threads through decode_step.

    ``positions`` is each row's NEXT write slot (= real prompt length +
    tokens generated so far); ``gen_steps`` counts generated tokens;
    ``max_news`` is the per-row decode budget (runtime data, NOT a
    compile key — unlike the contiguous path, a new max_tokens value
    never keys a retrace); ``forced_steps`` is the per-row step index
    where ``forced_eos_token_id`` fires — the CONTIGUOUS path's bucketed
    run end (`core/serving.plan_decode`'s ``run - 1``), not the raw
    budget, so forced-EOS output stays token-identical to the coalesce
    path (whose forced step usually lands beyond the trimmed output);
    ``logits`` are the pending next-token logits the next step samples
    from; ``counts`` back repetition penalty.

    ``reject`` (speculative path only, else None): the draft token id
    the last iteration's verify REJECTED at exactly the carried logits'
    position, or -1.  Sampled decode masks it out of the filtered
    distribution before drawing — the Leviathan residual rule carried
    across the step boundary; greedy ignores it (the argmax already
    differs from a rejected draft)."""

    logits: jax.Array        # [B, vocab] f32
    counts: jax.Array        # [B, vocab] int32
    positions: jax.Array     # [B] int32
    gen_steps: jax.Array     # [B] int32
    max_news: jax.Array      # [B] int32
    active: jax.Array        # [B] bool
    forced_steps: jax.Array  # [B] int32
    reject: Optional[jax.Array] = None  # [B] int32 (-1 = none)
    # expert layers only: what the step just run counted, :func:`_moe_counts`
    moe: Optional[jax.Array] = None


# ---------------------------------------------------------------------------
# The described block on the paged pools (latent attention; SwiGLU or the
# dropless expert layer).  Prefill runs the EXPANDED form over the prompt and
# keeps each token's latent; a decode step runs the ABSORBED form against
# the latent pages.  docs/deepseek_v3.md has both with their equations.
# ---------------------------------------------------------------------------


def _moe_counts(stats) -> jax.Array:
    """Expert layers' load statistics (a list, one dict a layer) -> int32
    [3]: the pairs the valid tokens gave, those on experts held here, the
    fullest held expert's pairs x experts held (over the second: max over
    mean), each summed over the layers."""
    total = jnp.zeros((3,), jnp.int32)
    for st in stats:
        n_held = st["pairs_held"]
        fullest = jnp.round(st["load_max_over_mean"] * n_held).astype(jnp.int32)
        total = total + jnp.stack([jnp.sum(st["load"]), n_held, fullest]).astype(jnp.int32)
    return total


def _step_write_slots(block_tables, positions, active, bs: int):
    """Where a one-token step writes each row's token: (its position, or 0
    for an inactive row; the pool block, the null block for an inactive
    row; the slot inside the block), each [B]."""
    pos = jnp.where(active, positions, 0)
    blk_log = jnp.clip(pos // bs, 0, block_tables.shape[1] - 1)
    blk = jnp.take_along_axis(block_tables, blk_log[:, None], axis=1)[:, 0]
    return pos, jnp.where(active, blk, 0), pos % bs


def _block_mlp(p, m, cfg: GPTConfig, valid):
    """The block's feed-forward over m [b, t, h] -> (result, the expert
    layer's load statistics or None).  An expert layer is one whose
    parameters hold a router.  A decode step (t == 1: a token a row) runs
    every held expert on every row; a prefill sorts its pairs, as training
    does at every size, runs the products over them in the forward-only
    kernel that follows the held pairs (``pfx_grouped_matmul``) and brings
    the results back by the sort's inverse, a gather (``gather_combine``)."""
    from paddlefleetx_tpu.models.gpt.moe import dropless_moe_block, feed_forward
    from paddlefleetx_tpu.ops.grouped_matmul import grouped_matmul

    dtype = m.dtype
    if "router_kernel" not in p:
        return feed_forward(m, _in_dtype("mlp", p, dtype)), None
    q = dict(p, experts=_in_dtype("experts", p["experts"], dtype))
    if "shared" in p:
        q["shared"] = _in_dtype("shared", p["shared"], dtype)
    return dropless_moe_block(q, m, cfg, None, p["e_score_correction_bias"], valid,
                              every_held_expert=m.shape[1] == 1, grouped_product=grouped_matmul,
                              gather_combine=True)  # forward only: no scatter to transpose


def _block_layer_step(p, x, positions, valid, cfg: GPTConfig, attend):
    """One layer of the described block over x [b, t, h] (under ``hc_mult``
    the stream of copies [b, t, n, h]) at ``positions``
    [b, t]; ``valid`` [b, t] marks the tokens that are someone's.
    ``attend(attn params, q_nope, q_r, latent)`` writes the latents to its
    cache and attends -> (attention result [b, t, n, v], cache state)."""
    dtype = x.dtype
    attn = _in_dtype("attn", p["attn"], dtype)
    if cfg.hyper_connections:
        return _block_layer_step_streams(p, attn, x, positions, valid, cfg, attend)
    y = rms_norm(x, p["ln_1"]["scale"], cfg.norm_eps)
    out, state = attend(attn, *latent_projections(attn, y, positions, cfg))
    x = x + jnp.einsum("bsnd,ndh->bsh", out, attn["out_kernel"])
    f, stats = _block_mlp(p["mlp"], rms_norm(x, p["ln_2"]["scale"], cfg.norm_eps), cfg, valid)
    return x + f, state, stats


def _block_layer_step_streams(p, attn, x, positions, valid, cfg: GPTConfig, attend):
    """:func:`_block_layer_step` over a residual stream of ``hc_mult`` copies,
    x [b, t, n, h] (docs/xing4.md): each sub-block reads the copies mixed by
    its ``h_pre`` and leaves ``H_res X + h_post f``, the maps computed from
    the stream (``ops/hyper_connection.py``); the sub-blocks themselves are
    the one-stream layer's."""
    from paddlefleetx_tpu.ops.hyper_connection import hc_post, hc_pre

    u, maps = hc_pre(x, p["hc_attn"], cfg)
    y = rms_norm(u, p["ln_1"]["scale"], cfg.norm_eps)
    out, state = attend(attn, *latent_projections(attn, y, positions, cfg))
    x = hc_post(x, jnp.einsum("bsnd,ndh->bsh", out, attn["out_kernel"]), maps, cfg)
    u, maps = hc_pre(x, p["hc_mlp"], cfg)
    f, stats = _block_mlp(p["mlp"], rms_norm(u, p["ln_2"]["scale"], cfg.norm_eps), cfg, valid)
    return hc_post(x, f, maps, cfg), state, stats


def _block_stack_step(params, x, state, cfg: GPTConfig, layer_fn):
    """The block's stack, layer after layer (no scan: the served tree holds
    each layer's weights as leaves of their own, :func:`unstack_layers`):
    ``layer_fn(p, x, state, layer) -> (x, state, stats or None)`` with
    ``layer`` static and ``state`` the caller's cache (the paged arena,
    written in place from layer to layer).  -> (x, state, the expert
    layers' statistics, a list)."""
    stats = []
    for l, p_l in enumerate(unstack_layers(params, cfg)["blocks"]):
        x, state, st = layer_fn(p_l, x, state, l)
        if st is not None:
            stats.append(st)
    return x, state, stats


def _block_embed(params, tokens, cfg: GPTConfig):
    """The way in of every block but the GPT-2 one: tokens [...] -> x [..., h]
    in the compute dtype, or under ``hc_mult`` the stream [..., n, h] with the
    token's row in every copy."""
    word = _in_dtype("embeddings", params["embeddings"], jnp.dtype(cfg.dtype))["word"]
    x = word[tokens]
    if cfg.hyper_connections:
        from paddlefleetx_tpu.ops.hyper_connection import hc_in

        x = hc_in(x, cfg)
    return x


def _block_logits(params, x, cfg: GPTConfig):
    if cfg.hyper_connections:  # the way out: the copies' sum
        from paddlefleetx_tpu.ops.hyper_connection import hc_out

        x = hc_out(x)
    x = rms_norm(x, params["final_ln"]["scale"], cfg.norm_eps)
    head = _in_dtype("head", params["head"], x.dtype)["kernel"]
    return jnp.einsum("bsh,vh->bsv", x, head).astype(jnp.float32)


def _block_paged_forward_step(params, tokens, pools, block_tables, positions, active,
                              cfg: GPTConfig, ctx):
    """The decode step of the described block: tokens [B] at slots
    ``positions`` -> (logits [B, 1, v] f32, pools, counts).  Latent
    attention in its ABSORBED form: the query goes into the latent space
    (``W_uk^T q_nope``), scores against the row's latent pages, and the
    probabilities' sum over the latents comes back through ``W_uv``.  A
    row that is not ``active`` costs the attention kernel nothing: its grid is
    the step's work list (:func:`mla_work_list`), made once for all layers."""
    if ctx is not None:
        raise ValueError("tensor parallelism: the described block is served on one "
                         "device (its pools and experts have no sharding rules yet)")
    if tokens.ndim == 2 and tokens.shape[1] != 1:
        raise ValueError(
            "the described block's decode step takes one token a row: a verify chunk "
            "(draft_k) or a prompt chunk (prefill_chunk) over latent pools is not written")
    tokens = tokens.reshape(-1)
    x = _block_embed(params, tokens, cfg)[:, None]  # [B, 1, h] (or [B, 1, n, h])
    pos, blk, off = _step_write_slots(block_tables, positions, active, pools.k.shape[4])
    kl = cfg.kv_lora_rank
    scale = latent_softmax_scale(cfg)
    # once a step: every layer's kernel walks the same (live row, page group) pairs
    work = mla_work_list(live_slots(active), pos, pools.k.shape[4], block_tables.shape[1])

    def layer_fn(p, x, pools, layer):
        def attend(attn, q_nope, q_r, latent):
            pool = latent_page_write(pools.k, latent[:, 0], blk, off, layer=layer)
            with jax.named_scope("pfx.attn.mla.absorb"):
                q_lat = jnp.einsum("bnd,cnd->bnc", q_nope[:, 0], attn["k_b_kernel"])
            q = jnp.concatenate([q_lat, q_r[:, 0]], axis=-1)
            with jax.named_scope("pfx.attn.mla.decode"):
                o_lat = mla_paged_decode_attention(
                    q, pool, block_tables, pos, layer=layer, scale=scale, kv_lora=kl,
                    work=work)
            with jax.named_scope("pfx.attn.mla.absorb"):
                out = jnp.einsum("bnc,cnd->bnd", o_lat.astype(x.dtype), attn["v_b_kernel"])
            return out[:, None], PagedPools(pool)

        return _block_layer_step(p, x, pos[:, None], active[:, None], cfg, attend)

    x, pools, stats = _block_stack_step(params, x, pools, cfg, layer_fn)
    return _block_logits(params, x, cfg), pools, _moe_counts(stats) if stats else None


def _block_paged_prefill(params, prompt, prompt_len, pools, table_row, cfg: GPTConfig, ctx):
    """Prefill of the described block: the EXPANDED form over the padded
    prompt [1, P] (causal, so the real rows' arithmetic is the unpadded
    one), each layer's latents written to the row's blocks.  -> (pools,
    the last real token's logits [v], counts)."""
    if ctx is not None:
        raise ValueError("tensor parallelism: the described block is served on one device")
    P = int(prompt.shape[1])
    PB, bs = int(table_row.shape[0]), int(pools.k.shape[4])
    if PB * bs < P:
        raise ValueError(f"table_row covers {PB}x{bs}={PB * bs} slots < prompt bucket {P}")
    x = _block_embed(params, prompt, cfg)
    positions = jax.lax.iota(jnp.int32, P)[None]
    valid = positions < prompt_len

    def layer_fn(p, x, pools, layer):
        def attend(attn, q_nope, q_r, latent):
            with jax.named_scope("pfx.attn.mla.prefill"):
                out = latent_attention_expanded(attn, q_nope, q_r, latent, cfg)
            pages = jnp.pad(latent[0], ((0, PB * bs - P), (0, 0))).reshape(PB, 1, bs, -1)
            pages = pages.transpose(0, 1, 3, 2).astype(pools.k.dtype)  # a token is a column
            return out, PagedPools(pools.k.at[layer, table_row].set(pages))

        return _block_layer_step(p, x, positions, valid, cfg, attend)

    x, pools, stats = _block_stack_step(params, x, pools, cfg, layer_fn)
    last = jax.lax.dynamic_index_in_dim(x[0], prompt_len - 1, axis=0, keepdims=True)
    return (pools, _block_logits(params, last[None], cfg)[0, 0],
            _moe_counts(stats) if stats else None)


# ---------------------------------------------------------------------------
# A ``layer_pattern`` block on the paged pools (docs/nemotron_h.md,
# docs/falcon_h1.md): every layer is ONE sub-block, x + mix(RMSNorm(x)): a
# state-space mixer over the row's recurrent state, grouped-query attention
# over the row's pages (rotated under ``position: rope``), BOTH side by side
# on the same normed input (``P``), or a feed-forward (experts or dense).
# ---------------------------------------------------------------------------


def _pattern_stack(params, x, pools, valid, cfg: GPTConfig, mixer, attend, positions=None):
    """The pattern's layers one after the other.  ``mixer(p, y, pools, m)``
    runs state-space layer number ``m`` and ``attend(q, k, v, pools, a)``
    attention layer number ``a`` over its own cache; each -> (result,
    pools).  ``m`` and ``a`` count the layers of their kind (the pools'
    leading axes), not the layer's place in the stack: a ``P`` layer hands
    the SAME normed input to both mixers, adds their results and advances
    both.  ``positions`` [b, t] (``position: rope``): where the tokens sit; q
    and k are rotated BEFORE ``attend`` sees them, so a cache holds rotated
    keys; each KIND of attention layer rotates its own way
    (``GPTConfig.layer_rotation``).  A window layer (``W``) is
    ``attend(q, k, v, pools, w, window=True)`` with ``w`` counting the
    window layers alone: its cache is the pools' second class.
    -> (x, pools, the expert layers' statistics, a list)."""
    dtype = x.dtype
    stats, m, a, w = [], 0, 0, 0

    def attention(p, y, pools, a, kind):
        attn = _in_dtype("attn", p, dtype)
        q, k, v = (jnp.einsum("bsh,hnd->bsnd", y, attn[f"{n}_kernel"]) for n in "qkv")
        if cfg.position == "rope":
            q, k = (layer_rope_at(t, positions, cfg, kind) for t in (q, k))
        # only a W layer says so: an ``attend`` written for a pattern without them takes none
        out, pools = attend(q, k, v, pools, a, **({"window": True} if kind == "W" else {}))
        return jnp.einsum("bsnd,ndh->bsh", out, attn["out_kernel"]), pools

    for kind, p in zip(cfg.layer_pattern, params["blocks"]):
        y = rms_norm(x, p["ln_1"]["scale"], cfg.norm_eps)
        if kind == "P":
            with jax.named_scope("pfx.parallel"):
                out, pools = mixer(_in_dtype("ssm", p["ssm"], dtype), y, pools, m)
                attended, pools = attention(p["attn"], y, pools, a, kind)
                out = out + attended
            m, a = m + 1, a + 1
        elif kind == "M":
            out, pools = mixer(_in_dtype("ssm", p["ssm"], dtype), y, pools, m)
            m += 1
        elif kind == "*":
            out, pools = attention(p["attn"], y, pools, a, kind)
            a += 1
        elif kind == "W":
            out, pools = attention(p["attn"], y, pools, w, kind)
            w += 1
        else:
            out, st = _block_mlp(p["mlp"], y, cfg, valid)
            if st is not None:
                stats.append(st)
        x = x + out
    return x, pools, stats


def _pattern_prefill_attention(q, k, v, cfg: GPTConfig, window: bool = False):
    """Causal attention over one sequence, KV heads shared by their groups;
    ``window``: each position sees the last ``sliding_window`` only (the
    flash forward the training path runs under ``pfx.attn.window``).  A
    pattern with window layers names the scope by the layer's kind."""
    from paddlefleetx_tpu.ops.attention import attention

    kind = (".window" if window else ".full") if cfg.window_layers else ""
    with jax.named_scope("pfx.attn.gqa.prefill" + kind):
        return attention(q, k, v, impl=cfg.attn_impl, causal=True,
                         window=cfg.sliding_window if window else 0)


def _pattern_paged_forward_step(params, tokens, pools, block_tables, positions, active,
                                cfg: GPTConfig, ctx):
    """The decode step of a ``layer_pattern`` block: tokens [B] at slots
    ``positions`` -> (logits [B, 1, v] f32, pools, counts).  Row i IS batch
    slot i: its recurrent state is ``pools.ssm[:, i]``.  A pattern with
    window layers takes ``block_tables`` as a PAIR, (the full layers' tables
    [B, M], the window layers' rings [B, ring pages])."""
    if ctx is not None:
        raise ValueError("tensor parallelism: a layer_pattern block is served on one "
                         "device (its pools, states and experts have no sharding rules yet)")
    if tokens.ndim == 2 and tokens.shape[1] != 1:
        raise ValueError(
            "a layer_pattern block's decode step takes one token a row: a verify chunk "
            "(draft_k) or a prompt chunk (prefill_chunk) over row state is not written")
    from paddlefleetx_tpu.models.gpt.ssm import mixer_step

    tokens = tokens.reshape(-1)
    dtype = jnp.dtype(cfg.dtype)
    x = _in_dtype("embeddings", params["embeddings"], dtype)["word"][tokens][:, None]
    bs = pools.k.shape[3]
    rings = None
    if cfg.window_layers:
        block_tables, rings = block_tables
    pos, blk, off = _step_write_slots(block_tables, positions, active, bs)
    heads = jax.lax.iota(jnp.int32, cfg.kv_heads)[None, :]
    # once a step: every layer's kernel, state or attention, visits the same slots
    live = live_slots(active)
    if rings is not None:
        # where the window layers write the token (its page's ring slot) and
        # what they read (the ring turned oldest page first): once a step too
        R = rings.shape[1]
        ring_blk = jnp.take_along_axis(rings, ((pos // bs) % R)[:, None], axis=1)[:, 0]
        ring_blk = jnp.where(active, ring_blk, 0)
        ring_tables, ring_pos, ring_start = window_view(rings, pos, cfg.sliding_window, bs)

    def mixer(p, y, pools, m):
        out, ssm, conv = mixer_step(p, y, pools.ssm, pools.conv, active, cfg, layer=m, live=live)
        return out, pools._replace(ssm=ssm, conv=conv)

    def attend(q, k, v, pools, a, window=False):
        if window:
            at = (a, ring_blk[:, None], heads, off[:, None])
            pools = pools._replace(wk=pools.wk.at[at].set(k[:, 0].astype(pools.wk.dtype)),
                                   wv=pools.wv.at[at].set(v[:, 0].astype(pools.wv.dtype)))
            with jax.named_scope("pfx.attn.gqa.decode.window"):
                out = paged_decode_attention(q, pools.wk, pools.wv, ring_tables, ring_pos,
                                             layer=a, starts=ring_start, live=live)
            return out, pools
        at = (a, blk[:, None], heads, off[:, None])  # [B, kv heads] slots of this layer
        pools = pools._replace(k=pools.k.at[at].set(k[:, 0].astype(pools.k.dtype)),
                               v=pools.v.at[at].set(v[:, 0].astype(pools.v.dtype)))
        with jax.named_scope("pfx.attn.gqa.decode" + (".full" if cfg.window_layers else "")):
            out = paged_decode_attention(q, pools.k, pools.v, block_tables, pos, layer=a,
                                         live=live)
        return out, pools

    x, pools, stats = _pattern_stack(params, x, pools, active[:, None], cfg, mixer, attend,
                                     pos[:, None])
    return _block_logits(params, x, cfg), pools, _moe_counts(stats) if stats else None


def _pattern_paged_prefill(params, prompt, prompt_len, pools, table_row, slot, cfg: GPTConfig, ctx):
    """Prefill of a ``layer_pattern`` block over the right-padded prompt
    [1, P]: each attention layer's keys and values go to the row's pages,
    each state-space layer's state after the last REAL token and its last
    conv columns OVERWRITE batch slot ``slot``'s (whatever a finished row
    left there).  A pattern with window layers takes ``table_row`` as a PAIR,
    (the full layers' pages [PB], the row's ring [ring pages]): a window
    layer writes into the ring only the pages that hold the prompt's last
    ``sliding_window`` tokens (what the row's first decode step can see),
    each into the slot its page number names.
    -> (pools, the last real token's logits [v], counts)."""
    if ctx is not None:
        raise ValueError("tensor parallelism: a layer_pattern block is served on one device")
    if cfg.row_state and slot is None:
        raise ValueError("a block with row state prefills INTO a batch slot: pass slot")
    from paddlefleetx_tpu.models.gpt.ssm import mixer_prefill
    from paddlefleetx_tpu.ops.ssm import write_slot_states

    P = int(prompt.shape[1])
    ring_row = None
    if cfg.window_layers:
        table_row, ring_row = table_row
    PB, bs = int(table_row.shape[0]), int(pools.k.shape[3])
    if PB * bs < P:
        raise ValueError(f"table_row covers {PB}x{bs}={PB * bs} slots < prompt bucket {P}")
    dtype = jnp.dtype(cfg.dtype)
    x = _in_dtype("embeddings", params["embeddings"], dtype)["word"][prompt]
    positions = jax.lax.iota(jnp.int32, P)[None]
    valid = positions < prompt_len
    if ring_row is not None:
        # the pages of the prompt a window layer keeps: from the one that holds
        # the first token the row's first decode step (at prompt_len) can see
        R = int(ring_row.shape[0])
        first = jnp.maximum(prompt_len - cfg.sliding_window + 1, 0) // bs
        kept_pages = first + jax.lax.iota(jnp.int32, min(R, PB))  # no two share a ring slot
        ring_at = ring_row[kept_pages % R]
        kept_pages = jnp.minimum(kept_pages, PB - 1)  # past the bucket: unread until overwritten

    kept = []  # each state-space layer's (state, conv columns), written at the end

    def mixer(p, y, pools, m):
        out, state, columns = mixer_prefill(p, y, prompt_len, cfg)
        kept.append((state, columns))
        return out, pools

    def pages(t, pool):  # [1, P, kv heads, d] -> [PB, kv heads, bs, d]
        t = jnp.pad(t[0], ((0, PB * bs - P), (0, 0), (0, 0)))
        return t.reshape(PB, bs, t.shape[1], t.shape[2]).transpose(0, 2, 1, 3).astype(pool.dtype)

    def attend(q, k, v, pools, a, window=False):
        if window:
            return _pattern_prefill_attention(q, k, v, cfg, window=True), pools._replace(
                wk=pools.wk.at[a, ring_at].set(pages(k, pools.wk)[kept_pages]),
                wv=pools.wv.at[a, ring_at].set(pages(v, pools.wv)[kept_pages]))
        return _pattern_prefill_attention(q, k, v, cfg), pools._replace(
            k=pools.k.at[a, table_row].set(pages(k, pools.k)),
            v=pools.v.at[a, table_row].set(pages(v, pools.v)))

    x, pools, stats = _pattern_stack(params, x, pools, valid, cfg, mixer, attend, positions)
    if kept:
        states, columns = (jnp.stack(v) for v in zip(*kept))
        pools = pools._replace(
            ssm=write_slot_states(pools.ssm, states, slot),
            conv=pools.conv.at[:, slot].set(columns.astype(pools.conv.dtype)))
    last = jax.lax.dynamic_index_in_dim(x[0], prompt_len - 1, axis=0, keepdims=True)
    return (pools, _block_logits(params, last[None], cfg)[0, 0],
            _moe_counts(stats) if stats else None)


def expert_load(params, tokens: jax.Array, cfg: GPTConfig) -> jax.Array:
    """tokens [1, s] through the described block's EXPANDED forward, no
    cache -> the pairs each expert of each expert layer received,
    [expert layers, experts] int32 (held or not): what the routing bias's
    balance rule (``moe.next_expert_bias``) reads."""
    x = _block_embed(params, tokens, cfg)
    if cfg.layer_pattern:
        from paddlefleetx_tpu.models.gpt.ssm import mixer_prefill

        _, _, stats = _pattern_stack(
            params, x, None, None, cfg,
            lambda p, y, pools, m: (mixer_prefill(p, y, tokens.shape[1], cfg)[0], pools),
            lambda q, k, v, pools, a, window=False: (
                _pattern_prefill_attention(q, k, v, cfg, window), pools),
            jax.lax.iota(jnp.int32, tokens.shape[1])[None] if cfg.position == "rope" else None)
        return jnp.stack([st["load"] for st in stats])
    positions = jnp.broadcast_to(jax.lax.iota(jnp.int32, tokens.shape[1])[None], tokens.shape)

    def layer_fn(p, x, state, layer):
        def attend(attn, q_nope, q_r, latent):
            return latent_attention_expanded(attn, q_nope, q_r, latent, cfg), state

        return _block_layer_step(p, x, positions, None, cfg, attend)

    _, _, stats = _block_stack_step(params, x, None, cfg, layer_fn)
    return jnp.stack([st["load"] for st in stats])


def _block_forward_step(cfg: GPTConfig):
    """The decode step of a block other than the GPT-2 one."""
    return _pattern_paged_forward_step if cfg.layer_pattern else _block_paged_forward_step


def _paged_layer_step(
    p: Dict[str, Any],
    x: jax.Array,
    pools: PagedPools,
    layer: jax.Array,
    blk: jax.Array,
    off: jax.Array,
    tables: jax.Array,
    positions: jax.Array,
    live,
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx] = None,
):
    """Decoder layer ``layer`` over x [b, t, h]: write each of the t chunk
    tokens' K/V at slot (blk[i, j], off[i, j]) of that layer's blocks, per
    row (t > 1 is the speculative verify chunk), then block-table paged
    attention with per-query causal bounds over the rows ``live`` lists
    (:func:`live_slots` of the step's mask).  ``pools`` is the whole arena
    and comes back whole: the write lands in it and the attention reads
    the layer's pages out of it, so no layer's pool is ever sliced out of
    the stack.  Under int8 the chunk quantizes on write and the per-slot
    scales land in the arena's scale planes."""
    n = cfg.num_attention_heads

    def attend(q, k, v):
        # scatter the [b, t, n, d] chunk into each row's blocks: rows own
        # disjoint blocks and a row's t slots are distinct, so the only
        # index collisions are inactive/overrun rows' null-block writes
        # (garbage-on-garbage, never read)
        at = (layer, blk[:, :, None], jnp.arange(n)[None, None, :],
              off[:, :, None])  # [b, t, n] slots of this layer
        if pools.k_scale is not None:
            (kq, ks), (vq, vs) = quantize_kv(k), quantize_kv(v)
            chunk = (kq, vq, ks, vs)
        else:
            chunk = (k.astype(pools.k.dtype), v.astype(pools.v.dtype))
        new = PagedPools(*(pool.at[at].set(c) for pool, c in zip(pools, chunk)))
        attn_out = paged_decode_attention(
            q, new.k, new.v, tables, positions, layer=layer,
            impl="lax" if ctx is not None else "auto",
            k_scale=new.k_scale, v_scale=new.v_scale, live=live,
        )
        return attn_out, new

    return _decoder_layer(p, x, ctx, attend)


def paged_forward_step(
    params: Dict[str, Any],
    tokens: jax.Array,
    pools: PagedPools,
    block_tables: jax.Array,
    positions: jax.Array,
    active: jax.Array,
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx] = None,
    n_valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, PagedPools]:
    """tokens [B] or [B, t] at per-row slots positions..positions+t-1 ->
    (logits [B, t, v] f32, pools).  t = 1 is the plain decode step;
    t > 1 is the speculative verify chunk (causal within the chunk).
    Inactive rows keep their place in the fixed shape, write to the null
    block and are NOT attended (the paged kernel's grid follows the live
    rows; a finished row's stale position is never read); their logits
    are garbage the caller ignores.  Chunk slots past a
    row's block-table allocation gather the NULL padding entry, so a
    near-budget verify overrun can never alias another row's blocks
    (the engine also reserves draft_k slack — belt and braces).

    ``n_valid`` [B] (chunked prefill) null-routes each row's chunk slots
    >= its real token count: a padded tail chunk's junk positions can
    wrap onto REAL slots of the row's last allocated block after the
    table-width clamp, so pad K/V must never be written anywhere."""
    if not cfg.classic_block:
        if n_valid is not None:
            raise ValueError("prefill_chunk: a prompt chunk over latent pools or row state "
                             "is not written")
        return _block_forward_step(cfg)(
            params, tokens, pools, block_tables, positions, active, cfg, ctx)[:2]
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    B, t = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    emb = _in_dtype("embeddings", params["embeddings"], dtype)
    word, pe = emb["word"], emb["position"]
    # per-slot positions; clamp inactive rows' (stale) and overrun
    # slots' embedding indices into the table
    pos_t = positions[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    pos_emb = jnp.clip(
        jnp.where(active[:, None], pos_t, 0),
        0, cfg.max_position_embeddings - 1,
    )
    x = word[tokens] + pe[pos_emb]  # [B, t, h]
    x = _constrain(ctx, x, ("batch", None, "embed"))

    bs = pools.k.shape[3]
    blk_log = jnp.clip(pos_t // bs, 0, block_tables.shape[1] - 1)
    blk = jnp.take_along_axis(block_tables, blk_log, axis=1)  # [B, t]
    blk = jnp.where(active[:, None], blk, 0)  # inactive rows -> null block
    if n_valid is not None:  # pad chunk slots -> null block
        blk = jnp.where(
            jnp.arange(t, dtype=jnp.int32)[None, :] < n_valid[:, None],
            blk, 0,
        )
    off = pos_t % bs

    # the arena is CARRIED through the layer loop and written in place;
    # as the scan's xs / ys each layer's pool was sliced out of the stack
    # and written back into a second one (1.6 GB twice a step at GPT-1.3B)
    live = live_slots(active)  # once a step: every layer's kernel visits the same rows

    def body(carry, inp):
        x, pools = carry
        p_l, layer = inp
        return _paged_layer_step(
            p_l, x, pools, layer, blk, off, block_tables, positions, live, cfg, ctx
        ), None

    layers = jnp.arange(pools.k.shape[0], dtype=jnp.int32)
    (x, pools), _ = jax.lax.scan(body, (x, pools), (params["layers"], layers))
    x = layer_norm(x, params["final_ln"]["scale"], params["final_ln"]["bias"], ctx=ctx)
    logits = jnp.einsum("bsh,vh->bsv", x, word)
    logits = _constrain(ctx, logits, ("batch", None, "vocab"))
    return logits.astype(jnp.float32), pools


def paged_prefill(
    params: Dict[str, Any],
    prompt: jax.Array,
    prompt_len: jax.Array,
    pools: PagedPools,
    table_row: jax.Array,
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx] = None,
    return_moe: bool = False,
    slot: Optional[jax.Array] = None,
) -> Tuple[PagedPools, jax.Array, jax.Array]:
    """Prefill ONE row's prompt into its pool blocks (prefill-on-admit);
    for a block with row state also into batch slot ``slot``.

    ``prompt`` [1, P] is RIGHT-padded to the bucket (real tokens at
    [0, prompt_len); pad junk after) — unlike the contiguous serving
    path's left padding, paged rows are unpadded in their logical cache,
    so real token i lives at slot i and positions need no offset.  The
    prompt runs through the contiguous ``forward_cached`` prefill (causal
    masking makes the real rows' math exactly the unpadded computation),
    then the temp cache is repacked block-wise into the arena at
    ``table_row`` [PB] (PB * block >= P).  Pad-slot junk K/V land in the
    row's own blocks past ``prompt_len`` and are overwritten by decode
    steps before any attention limit reaches them — the same stale-tail
    argument as the donated contiguous pool.

    Returns (pools, last real token's logits [v] f32, prompt token
    counts [v] for repetition penalty); with ``return_moe`` a fourth, the
    expert layers' counts (:func:`_moe_counts`) or None without any."""
    P = int(prompt.shape[1])
    if not cfg.classic_block:
        if cfg.layer_pattern:
            pools, last, moe = _pattern_paged_prefill(
                params, prompt, prompt_len, pools, table_row, slot, cfg, ctx)
        else:
            pools, last, moe = _block_paged_prefill(
                params, prompt, prompt_len, pools, table_row, cfg, ctx)
        counts = jnp.zeros((cfg.vocab_size,), jnp.int32).at[prompt[0]].add(
            (jnp.arange(P) < prompt_len).astype(jnp.int32))
        return (pools, last, counts, moe) if return_moe else (pools, last, counts)
    layers = cfg.num_layers
    n = cfg.num_attention_heads
    d = cfg.head_dim
    PB = int(table_row.shape[0])
    bs = int(pools.k.shape[3])
    L = PB * bs
    if L < P:
        raise ValueError(
            f"table_row covers {PB}x{bs}={L} slots < prompt bucket {P}"
        )
    # the temp prefill cache is NATIVE dtype even when the arena is int8:
    # the prompt's self-attention runs at full precision and the K/V
    # quantize ONCE on the repack below (decode then reads the same
    # quantized prompt keys whether speculating or not)
    cache = init_cache(cfg, 1, L, kv_dtype="bf16")
    pos_ids = jnp.arange(P, dtype=jnp.int32)[None, :]
    logits, cache = forward_cached(
        params, prompt, cache, jnp.int32(0), cfg, ctx, position_ids=pos_ids
    )
    last = jax.lax.dynamic_index_in_dim(
        logits[0], prompt_len - 1, axis=0, keepdims=False
    ).astype(jnp.float32)
    # repack [layers, 1, n, L(+alignment slack), d] -> per-block
    # [layers, PB, n, bs, d]
    def pack(c):
        return (
            c[:, 0, :, :L].reshape(layers, n, PB, bs, d).transpose(0, 2, 1, 3, 4)
        )

    counts = jnp.zeros((cfg.vocab_size,), jnp.int32).at[prompt[0]].add(
        (jnp.arange(P) < prompt_len).astype(jnp.int32)
    )
    if pools.k_scale is not None:
        kq, ksl = quantize_kv(pack(cache.k))
        vq, vsl = quantize_kv(pack(cache.v))
        new = PagedPools(
            pools.k.at[:, table_row].set(kq),
            pools.v.at[:, table_row].set(vq),
            pools.k_scale.at[:, table_row].set(ksl),
            pools.v_scale.at[:, table_row].set(vsl),
        )
    else:
        new = PagedPools(
            pools.k.at[:, table_row].set(pack(cache.k).astype(pools.k.dtype)),
            pools.v.at[:, table_row].set(pack(cache.v).astype(pools.v.dtype)))
    return (new, last, counts, None) if return_moe else (new, last, counts)


def paged_chunk_prefill(
    params: Dict[str, Any],
    tokens: jax.Array,
    pools: PagedPools,
    table_row: jax.Array,
    position: jax.Array,
    n_valid: jax.Array,
    last_idx: jax.Array,
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx] = None,
) -> Tuple[PagedPools, jax.Array]:
    """Prefill ONE row's next chunk of prompt tokens directly against the
    paged arena: ``tokens`` [1, t] land at slots position..position+t-1
    of the row's ``table_row`` blocks, attending over everything already
    in them — which is exactly what makes this the prefix-reuse and
    chunked-prefill spelling (docs/serving.md): the already-cached
    prefix (shared blocks) and earlier chunks are simply THERE, so only
    the unmatched suffix ever runs through the model.  Rides
    :func:`paged_forward_step`'s multi-token path (the speculative
    verify chunk machinery), so a chunk admission compiles into the same
    bounded (t, table-width) family as decode steps — no monolithic
    full-prompt prefill compile for a prompt that is mostly cached.

    Pad slots past the real chunk (``tokens[0, j]`` for j >= ``n_valid``)
    NULL-ROUTE their K/V writes outright: a near-capacity tail chunk's
    pad positions can alias real slots of the row's last block modulo
    the block size, so unlike `paged_prefill`'s bucket junk they must
    never land in the row's blocks at all.  Returns (pools, logits of
    chunk slot ``last_idx`` [v] f32 — the last REAL prompt token's
    logits on the final chunk)."""
    logits, pools = paged_forward_step(
        params, tokens, pools, table_row[None, :], position[None],
        jnp.ones((1,), bool), cfg, ctx, n_valid=n_valid[None],
    )
    last = jax.lax.dynamic_index_in_dim(
        logits[0], last_idx, axis=0, keepdims=False
    ).astype(jnp.float32)
    return pools, last


def prefix_token_counts(prompt_ids, vocab_size: int) -> "np.ndarray":
    """Host-side repetition-penalty seed counts for a prompt — the exact
    integer bincount `paged_prefill` computes in-graph, computed on host
    for admissions that skip the monolithic prefill (prefix hits /
    chunked prompts)."""
    import numpy as np

    return np.bincount(
        np.asarray(list(prompt_ids), np.int64), minlength=int(vocab_size)
    ).astype(np.int32)


def gather_kv_blocks(pools: PagedPools, table) -> Dict[str, "np.ndarray"]:
    """Copy one row's arena blocks to host for the KV-handoff payload:
    ``{"k", "v"[, "k_scale", "v_scale"]}`` with k/v shaped
    [layers, len(table), heads, block, dim] in the ARENA dtype (int8
    blocks ship with their per-(slot, head) scale planes — the decode
    replica adopts the quantized values bit-exactly instead of paying a
    second quantization error).  Host-side indexing, not a jit: handoff
    happens once per request at the prefill/decode boundary, never on
    the per-token hot path."""
    import numpy as np

    idx = jnp.asarray(table, jnp.int32)
    out = {"k": np.asarray(pools.k[:, idx]), "v": np.asarray(pools.v[:, idx])}
    if pools.k_scale is not None:
        out["k_scale"] = np.asarray(pools.k_scale[:, idx])
        out["v_scale"] = np.asarray(pools.v_scale[:, idx])
    return out


def scatter_kv_blocks(pools: PagedPools, table, blocks) -> PagedPools:
    """Adopt exported blocks into this arena at ``table`` (the adopting
    row's first ``len(table)`` allocated blocks).  The caller validates
    compatibility first (`core/paged_cache.check_handoff_meta`); this
    helper still refuses a dtype or per-block-shape mismatch loudly —
    scattering mistyped bytes would corrupt a live arena."""
    want = {"k", "v"} | (
        {"k_scale", "v_scale"} if pools.k_scale is not None else set()
    )
    if set(blocks) != want:
        raise ValueError(
            f"handoff arrays {sorted(blocks)} != arena arrays {sorted(want)}"
        )
    idx = jnp.asarray(table, jnp.int32)
    new = {}
    for name in sorted(want):
        pool = getattr(pools, name)
        arr = blocks[name]
        if str(arr.dtype) != str(pool.dtype):
            raise ValueError(
                f"handoff {name} dtype {arr.dtype} != arena {pool.dtype}"
            )
        if tuple(arr.shape) != (pool.shape[0], len(table)) + pool.shape[2:]:
            raise ValueError(
                f"handoff {name} shape {tuple(arr.shape)} does not cover "
                f"{len(table)} blocks of arena {tuple(pool.shape)}"
            )
        new[name] = pool.at[:, idx].set(jnp.asarray(arr))
    return PagedPools(
        new["k"], new["v"], new.get("k_scale"), new.get("v_scale")
    )


def process_step_logits(logits, steps, counts, forced_steps, gen):
    """THE per-step logits-processor chain (min-length -> repetition
    penalty -> forced BOS/EOS), shape-agnostic: ``logits`` [..., v] with
    ``steps``/``forced_steps`` matching the leading dims (per-row on the
    paged path, per-slot on the speculative verify chunk).
    Single-sourced on purpose: :func:`decode_step`,
    :func:`decode_step_spec`'s pending-token sampling, the speculative
    prefill seed, and `ops/speculative.speculative_verify` must all stay
    BITWISE identical or the greedy token-identity contract silently
    drifts.  ``counts`` None skips repetition penalty (callers pass None
    exactly when the penalty is 1.0)."""
    logits = apply_min_length(logits, steps, gen.min_dec_len, gen.eos_token_id)
    if counts is not None:
        logits = apply_repetition_penalty(logits, counts, gen.repetition_penalty)
    if gen.forced_bos_token_id >= 0:
        forced = jnp.full_like(logits, -1e10).at[
            ..., gen.forced_bos_token_id].set(0.0)
        logits = jnp.where((steps == 0)[..., None], forced, logits)
    if gen.forced_eos_token_id >= 0:
        forced = jnp.full_like(logits, -1e10).at[
            ..., gen.forced_eos_token_id].set(0.0)
        logits = jnp.where((steps == forced_steps)[..., None], forced, logits)
    return logits


def decode_step(
    params: Dict[str, Any],
    pools: PagedPools,
    block_tables: jax.Array,
    rows: PagedRows,
    cfg: GPTConfig,
    gen: GenerationConfig,
    key: Optional[jax.Array] = None,
    ctx: Optional[ShardingCtx] = None,
) -> Tuple[jax.Array, PagedPools, PagedRows]:
    """ONE iteration-level decode step over the running batch.

    Samples each active row's next token from its pending logits through
    the same processor chain as :func:`generate` (min-length, repetition
    penalty, forced BOS/EOS — all per-row: rows sit at different steps),
    writes the token's K/V at the row's current slot, and returns the
    refreshed pending logits.  Greedy rows are token-identical to the
    contiguous path; sampling rows draw from per-step subkeys (a
    different, but deterministic, stream).  Returns (sampled tokens [B],
    pools, rows')."""
    B, vocab = rows.logits.shape
    i = rows.gen_steps
    logits = process_step_logits(
        rows.logits, i, rows.counts, rows.forced_steps, gen
    )
    if gen.decode_strategy == "greedy_search":
        nxt = jnp.argmax(logits, axis=-1)
    else:
        if key is None:
            raise ValueError("sampling decode_step needs a PRNG key")
        nxt = sample_logits(
            key, logits, temperature=gen.temperature, top_k=gen.top_k,
            top_p=gen.top_p,
        )
    nxt = jnp.where(rows.active, nxt, gen.pad_token_id)
    counts = rows.counts.at[jnp.arange(B), nxt].add(
        rows.active.astype(jnp.int32)
    )
    finished = rows.active & (
        (nxt == gen.eos_token_id) | (i + 1 >= rows.max_news)
    )
    if cfg.classic_block:
        new_logits, pools = paged_forward_step(
            params, nxt, pools, block_tables, rows.positions, rows.active,
            cfg, ctx,
        )
        moe = None
    else:
        new_logits, pools, moe = _block_forward_step(cfg)(
            params, nxt, pools, block_tables, rows.positions, rows.active, cfg, ctx)
    act = rows.active.astype(jnp.int32)
    new_rows = PagedRows(
        logits=new_logits[:, 0],
        counts=counts,
        positions=rows.positions + act,
        gen_steps=i + act,
        max_news=rows.max_news,
        active=rows.active & ~finished,
        forced_steps=rows.forced_steps,
        moe=moe,
    )
    return nxt, pools, new_rows


def decode_step_spec(
    params: Dict[str, Any],
    pools: PagedPools,
    block_tables: jax.Array,
    rows: PagedRows,
    drafts: jax.Array,
    cfg: GPTConfig,
    gen: GenerationConfig,
    key: Optional[jax.Array] = None,
    ctx: Optional[ShardingCtx] = None,
) -> Tuple[jax.Array, jax.Array, PagedPools, PagedRows]:
    """ONE speculative iteration over the running batch — the paged
    spelling of :func:`_generate_speculative`'s body, with TRUE per-row
    commit (each row owns its positions, so accepted lengths never
    constrain each other; accepted length is runtime DATA, not a compile
    key — ``drafts`` [B, k] are host-proposed runtime data too).

    Per row: sample the pending token t0 from ``rows.logits`` through
    exactly :func:`decode_step`'s processor chain (greedy rows are
    bitwise the baseline), forward the [t0, draft_0..k-1] chunk in ONE
    t=k+1 dispatch (writing K/V at positions..positions+k), verify the
    drafts with :func:`~paddlefleetx_tpu.ops.speculative.
    speculative_verify`, and commit t0 plus the accepted prefix —
    truncated by the per-row budget.  Rejected-tail K/V slots are
    rewritten by the next iteration's chunk before any attention visits
    them (positions advance only by the committed count: the per-row
    position REWIND; block tables are untouched — rows reserved their
    full capacity, plus draft_k slack, at admission).

    Returns (window [B, k+1] committed tokens — pad past each row's
    count, ncommit [B] int32 in [0, k+1] (0 only for inactive rows),
    pools, rows').  ``rows'.logits`` carries the RAW target logits at
    each row's last committed position; ``rows'.reject`` the residual
    mask for the next sample (sampling mode; see :class:`PagedRows`)."""
    B, vocab = rows.logits.shape
    k = int(drafts.shape[1])
    K = k + 1
    i = rows.gen_steps
    greedy = gen.decode_strategy == "greedy_search"
    use_counts = gen.repetition_penalty != 1.0
    if not greedy and key is None:
        raise ValueError("sampling decode_step_spec needs a PRNG key")

    # --- t0: the baseline decode_step sampling rule on pending logits
    logits = process_step_logits(
        rows.logits, i, rows.counts, rows.forced_steps, gen
    )
    if greedy:
        t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        key_verify = key
    else:
        key, key_t0, key_verify = (
            jax.random.split(key, 3)
        )
        filt = filtered_logits(
            logits, temperature=gen.temperature, top_k=gen.top_k,
            top_p=gen.top_p,
        )
        if rows.reject is not None:
            # residual rule carried across the step boundary: mask the
            # draft the last verify rejected at THIS position (post-
            # filter, so the renormalized nucleus is the exact residual)
            hit = rows.reject >= 0
            safe = jnp.clip(rows.reject, 0, vocab - 1)
            filt = jnp.where(
                hit[:, None]
                & (jnp.arange(vocab)[None, :] == safe[:, None]),
                -1e10, filt,
            )
        t0 = jax.random.categorical(key_t0, filt, axis=-1).astype(jnp.int32)
    nxt0 = jnp.where(rows.active, t0, gen.pad_token_id)
    chunk = jnp.concatenate([nxt0[:, None], drafts.astype(jnp.int32)], axis=1)

    # --- ONE t=k+1 verify forward
    logits_all, pools = paged_forward_step(
        params, chunk, pools, block_tables, rows.positions, rows.active,
        cfg, ctx,
    )
    sv = speculative_verify(
        key_verify, logits_all, chunk,
        rows.counts if use_counts else None,
        rows.active, i, gen, forced_steps=rows.forced_steps,
    )

    # --- per-row commit: the accepted prefix cut by the decode budget
    budget_ok = (i[:, None] + jnp.arange(K)[None, :]) < rows.max_news[:, None]
    valid = sv.real & budget_ok
    ncommit = valid.sum(axis=1).astype(jnp.int32)
    window = jnp.where(valid, sv.w, gen.pad_token_id)
    jmask = (jnp.arange(K)[None, :] < ncommit[:, None]).astype(jnp.int32)
    counts = rows.counts.at[jnp.arange(B)[:, None], window].add(jmask)

    eos_fin = (sv.eos_hit & valid).any(axis=1)
    budget_fin = (i + ncommit) >= rows.max_news
    finished = rows.active & (eos_fin | budget_fin)

    # --- carry the RAW logits at each row's last committed position
    sel = jnp.clip(ncommit - 1, 0, k)[:, None, None]
    new_logits = jnp.take_along_axis(logits_all, sel, axis=1)[:, 0]
    new_logits = jnp.where(rows.active[:, None], new_logits, rows.logits)

    # --- residual mask: a MISMATCH rejection at exactly the carried slot
    a = sv.accepted
    a_cl = jnp.clip(a, 0, k - 1)
    ok_at_a = jnp.take_along_axis(sv.ok, a_cl[:, None], axis=1)[:, 0]
    real_at_a = jnp.take_along_axis(sv.real, a[:, None], axis=1)[:, 0]
    mism = (a < k) & real_at_a & ~ok_at_a
    rej_draft = jnp.take_along_axis(drafts, a_cl[:, None], axis=1)[:, 0]
    reject = jnp.where(
        mism & (ncommit == a + 1) & rows.active & ~finished,
        rej_draft.astype(jnp.int32), jnp.int32(-1),
    )

    new_rows = PagedRows(
        logits=new_logits,
        counts=counts,
        positions=rows.positions + ncommit,
        gen_steps=i + ncommit,
        max_news=rows.max_news,
        active=rows.active & ~finished,
        forced_steps=rows.forced_steps,
        reject=reject,
    )
    return window, ncommit, pools, new_rows


# ---------------------------------------------------------------------------
# Beam search (reference single_model.py:1190-1320 beam strategy +
# BeamSearchScorer; diverse groups via HammingDiversityLogitsProcessor)
# ---------------------------------------------------------------------------


def _length_penalty(length, alpha: float):
    return jnp.power(length.astype(jnp.float32), alpha)


def beam_search(
    params: Dict[str, Any],
    input_ids: jax.Array,
    cfg: GPTConfig,
    gen: GenerationConfig,
    ctx: Optional[ShardingCtx] = None,
    prompt_lens: Optional[jax.Array] = None,
) -> jax.Array:
    """Static-shape beam search: [b, prompt_len] -> [b, max_dec_len].

    K = num_beams alive beams per prompt plus a K-slot finished pool;
    each step takes the top 2*Kg candidates per beam group (Kg = K /
    num_beam_groups), routes EOS continuations into the finished pool with
    length penalty, keeps the best Kg non-EOS continuations alive, and
    reorders the KV cache by parent beam.  ``diversity_penalty`` applies
    the Hamming penalty against earlier groups' same-step choices.
    Repetition penalty is not applied on the beam path (matching the
    reference beam strategy's processor set)."""
    b, prompt_len = input_ids.shape
    K, G = gen.num_beams, gen.num_beam_groups
    if K % G:
        raise ValueError(f"num_beams {K} not divisible by num_beam_groups {G}")
    Kg = K // G
    vocab = cfg.vocab_size
    # length validated by generate() before dispatch
    max_len = prompt_len + gen.max_dec_len

    # prefill ONCE per prompt, then repeat the cache/logits K-fold (all
    # beams share the prompt; re-running the forward K times would be
    # K x the prefill FLOPs for identical results)
    pad_len, prefill_pos_ids = _left_pad_prefill(prompt_len, prompt_lens)
    # beam reorders the cache by parent each step and rebuilds it here —
    # always native dtype (int8 KV quant covers the sampling/greedy
    # serving paths, not beam)
    cache = init_cache(cfg, b, max_len, kv_dtype="bf16")
    logits, cache = forward_cached(
        params, input_ids, cache, jnp.int32(0), cfg, ctx,
        position_ids=prefill_pos_ids, kv_valid_from=pad_len,
    )
    cache = KVCache(
        jnp.repeat(cache.k, K, axis=1), jnp.repeat(cache.v, K, axis=1)
    )
    logits0 = jnp.repeat(logits[:, -1, :].astype(jnp.float32), K, axis=0)
    pad_len_flat = jnp.repeat(pad_len, K, axis=0) if pad_len is not None else None
    lens_flat = (
        jnp.repeat(prompt_lens, K, axis=0) if prompt_lens is not None else None
    )

    NEG = jnp.float32(-1e9)
    # only each group's first beam is live at step 0 (avoids duplicates)
    init_scores = jnp.where(
        (jnp.arange(K) % Kg) == 0, 0.0, NEG
    )[None].repeat(b, 0)  # [b, K]

    def _pin_beam(x, logical):
        """Pin beam bookkeeping to batch-sharded/replicated-elsewhere.

        jax-0.4.37 GSPMD mis-partitions the beam scan under TP: the scan
        carry's bookkeeping arrays (derived from vocab-sharded logits via
        top_k/gather chains) can leave the loop marked partial-over-`model`
        while each shard actually holds the full value, and the consumer's
        combining all-reduce then multiplies token ids by mp_degree
        (observed: every emitted token exactly 2x under mp=2; the same
        ops unrolled OUTSIDE lax.scan partition correctly).  Explicitly
        constraining the carry each step keeps the sharding the partitioner
        propagates identical to what the values actually are.  These are
        [b, K]-sized arrays — replication is free."""
        if ctx is None:
            return x
        return ctx.constrain(x, logical)

    class Beams(NamedTuple):
        cache: KVCache
        logits: jax.Array  # [b*K, v]
        scores: jax.Array  # [b, K] cumulative alive logprobs
        seqs: jax.Array  # [b, K, max_dec]
        fin_scores: jax.Array  # [b, K]
        fin_seqs: jax.Array  # [b, K, max_dec]
        pos: jax.Array

    def step(st: Beams, i):
        logp = jax.nn.log_softmax(st.logits, axis=-1).reshape(b, K, vocab)
        logp = apply_min_length(
            logp.reshape(b * K, vocab), jnp.full((b * K,), i),
            gen.min_dec_len, gen.eos_token_id,
        ).reshape(b, K, vocab)
        logp = apply_forced_token(
            logp.reshape(b * K, vocab), i, 0, gen.forced_bos_token_id
        ).reshape(b, K, vocab)
        logp = apply_forced_token(
            logp.reshape(b * K, vocab), i, gen.max_dec_len - 1,
            gen.forced_eos_token_id,
        ).reshape(b, K, vocab)

        new_scores = st.scores
        fin_scores, fin_seqs = st.fin_scores, st.fin_seqs
        chosen_tok = jnp.zeros((b, K), jnp.int32)
        chosen_parent = jnp.zeros((b, K), jnp.int32)
        step_tokens = jnp.full((b, K), -1, jnp.int32)  # for Hamming penalty

        for g in range(G):  # static, G small
            sl = slice(g * Kg, (g + 1) * Kg)
            glogp = logp[:, sl]  # [b, Kg, v]
            if gen.diversity_penalty > 0.0 and g > 0:
                glogp = jax.vmap(
                    lambda lg, cur: apply_hamming_diversity(
                        lg, cur, g * Kg, gen.diversity_penalty
                    )
                )(glogp, step_tokens)
            cand = (st.scores[:, sl, None] + glogp).reshape(b, Kg * vocab)
            top_s, top_i = jax.lax.top_k(cand, 2 * Kg)  # [b, 2Kg]
            tok = top_i % vocab
            parent = top_i // vocab + g * Kg  # flat beam index
            is_eos = tok == gen.eos_token_id

            # finished pool: EOS continuations scored with length penalty
            f_cand = jnp.where(is_eos, top_s / _length_penalty(
                jnp.full((b, 2 * Kg), i + 1), gen.length_penalty
            ), NEG)
            # candidate finished sequences = parent's seq + eos at i
            parent_seqs = jnp.take_along_axis(
                st.seqs, parent[..., None], axis=1
            )  # [b, 2Kg, max_dec]
            f_seqs = jax.vmap(
                lambda ps, tk: ps.at[:, i].set(tk)
            )(parent_seqs, tok)
            all_f_scores = jnp.concatenate([fin_scores, f_cand], axis=1)
            all_f_seqs = jnp.concatenate([fin_seqs, f_seqs], axis=1)
            keep_s, keep_i = jax.lax.top_k(all_f_scores, K)
            fin_scores = keep_s
            fin_seqs = jnp.take_along_axis(all_f_seqs, keep_i[..., None], axis=1)

            # alive: best Kg non-EOS continuations
            alive_s = jnp.where(is_eos, NEG, top_s)
            a_s, a_i = jax.lax.top_k(alive_s, Kg)  # indices into 2Kg
            a_tok = jnp.take_along_axis(tok, a_i, axis=1)
            a_parent = jnp.take_along_axis(parent, a_i, axis=1)
            new_scores = new_scores.at[:, sl].set(a_s)
            chosen_tok = chosen_tok.at[:, sl].set(a_tok)
            chosen_parent = chosen_parent.at[:, sl].set(a_parent)
            step_tokens = step_tokens.at[:, sl].set(a_tok)

        # reorder sequences/caches by parent beam, then append tokens
        new_seqs = jnp.take_along_axis(st.seqs, chosen_parent[..., None], axis=1)
        new_seqs = jax.vmap(lambda s, t: s.at[:, i].set(t))(new_seqs, chosen_tok)
        flat_parent = (
            jnp.arange(b)[:, None] * K + chosen_parent
        ).reshape(-1)  # [b*K]
        cache = KVCache(
            jnp.take(st.cache.k, flat_parent, axis=1),
            jnp.take(st.cache.v, flat_parent, axis=1),
        )
        step_pos_ids = (
            (lens_flat + i)[:, None] if lens_flat is not None else None
        )
        new_logits, cache = forward_cached(
            params, chosen_tok.reshape(b * K, 1), cache, st.pos, cfg, ctx,
            position_ids=step_pos_ids, kv_valid_from=pad_len_flat,
        )
        return Beams(
            cache=cache,
            logits=new_logits[:, -1, :].astype(jnp.float32),
            scores=_pin_beam(new_scores, ("batch", None)),
            seqs=_pin_beam(new_seqs, ("batch", None, None)),
            fin_scores=_pin_beam(fin_scores, ("batch", None)),
            fin_seqs=_pin_beam(fin_seqs, ("batch", None, None)),
            pos=st.pos + 1,
        ), None

    st0 = Beams(
        cache=cache,
        logits=logits0,
        scores=init_scores,
        seqs=jnp.full((b, K, gen.max_dec_len), gen.pad_token_id, jnp.int32),
        fin_scores=jnp.full((b, K), NEG),
        fin_seqs=jnp.full((b, K, gen.max_dec_len), gen.pad_token_id, jnp.int32),
        pos=jnp.int32(prompt_len),
    )
    st, _ = jax.lax.scan(step, st0, jnp.arange(gen.max_dec_len))

    # merge still-alive beams (scored at full length) into the pool
    alive_final = st.scores / _length_penalty(
        jnp.full((b, K), gen.max_dec_len), gen.length_penalty
    )
    all_scores = jnp.concatenate([st.fin_scores, alive_final], axis=1)
    all_seqs = jnp.concatenate([st.fin_seqs, st.seqs], axis=1)
    best = jnp.argmax(all_scores, axis=1)
    return jnp.take_along_axis(all_seqs, best[:, None, None], axis=1)[:, 0]
