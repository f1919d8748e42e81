"""GPT decoder-only LM — pure-JAX functional, sharded by annotation.

One model definition covers the reference's four GPT variants (single-device
``GPTModel`` single_model.py:608, TP/SP ``GPTModelHybrid`` hybrid_model.py:739,
pipeline ``GPTForPretrainingPipe`` hybrid_model.py:1055, auto-parallel
``GPTModelAuto`` auto_model.py:514): parallelism comes from the logical-axis
annotations on :func:`gpt_specs` + the active sharding rules, not from
separate classes.

Architecture (matches reference GPTModel): learned word+position embeddings,
pre-LayerNorm transformer decoder blocks (fused-qkv attention, gelu MLP),
final LayerNorm, logits via tied word-embedding matmul
(``parallel_matmul``, hybrid_model.py:66-87), masked-mean token
cross-entropy (``GPTPretrainingCriterion`` single_model.py:819).

Layers are stacked on a leading ``layers`` axis and executed with
``lax.scan`` (compile-time O(1) in depth; the ``layers`` axis is what
pipeline stage-sharding partitions).  Recompute granularities full /
full_attn / core_attn (reference single_model.py:320-405) map to
``jax.checkpoint`` placement.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from paddlefleetx_tpu.models.common import (
    ParamSpec,
    dropout,
    init_params,
    logical_axes,
    normal_init,
    ones_init,
    slab_init,
    stack_spec_tree,
    zeros_init,
)
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.ops.attention import attention
from paddlefleetx_tpu.utils import device as _device


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """Optional activation-sharding context (mesh + logical rules).

    ``pipeline`` switches the transformer stack from plain scan-over-layers
    to the stage-pipelined schedule (parallel/pipeline.py)."""

    mesh: Any
    rules: Tuple[Tuple[str, Any], ...]
    pipeline: Any = None  # Optional[PipelineConfig]
    # global token positions of the (possibly permuted) sequence, [s];
    # consumed by ring attention so balanced layouts (zigzag_permutation)
    # mask causally by TRUE token order.  None = contiguous arange.
    attn_positions: Any = None

    def constrain(self, x: jax.Array, logical: Tuple[Optional[str], ...]) -> jax.Array:
        from paddlefleetx_tpu.parallel.sharding import with_logical_constraint

        return with_logical_constraint(x, logical, self.rules, self.mesh)

    def shard_kernel(self, fn, in_logical, out_logical):
        """``fn`` (a Pallas kernel) under this mesh: inside ``shard_map``
        over the axes its logical dims are sharded along
        (``parallel/sharding.shard_kernel``)."""
        from paddlefleetx_tpu.parallel.sharding import shard_kernel

        return shard_kernel(fn, self.mesh, self.rules, in_logical, out_logical)


def _constrain(ctx: Optional[ShardingCtx], x: jax.Array, logical) -> jax.Array:
    return ctx.constrain(x, logical) if ctx is not None else x


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def _rms_specs(width: int) -> Dict[str, Any]:
    return {"scale": ParamSpec((width,), ("embed",), ones_init())}


def _block_layer_specs(cfg: GPTConfig, experts: bool) -> Dict[str, Any]:
    """One layer of the block the vocabulary of ``GPTConfig`` describes
    (every block but the GPT-2 one, whose tree ``_layer_specs`` keeps):
    RMSNorm, no biases, a SwiGLU or an expert MLP."""
    h, nh, nkv, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    w = normal_init(cfg.initializer_range)
    if cfg.latent_attention:
        ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
        nope, rot, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        # W_kvb is two leaves (its key columns and its value columns): the
        # decode step multiplies by each alone.  W_qb keeps its published
        # 2-D shape: as [q_lora, heads, 192] the TPU pads the 192 to 256
        # and the decode step converts the whole matrix first, every layer
        attn: Dict[str, Any] = {
            "q_a_kernel": ParamSpec((h, ql), ("embed", None), w),
            "q_a_norm": ParamSpec((ql,), (None,), ones_init()),
            "q_b_kernel": ParamSpec((ql, nh * (nope + rot)), (None, "heads"), w),
            "kv_a_kernel": ParamSpec((h, kl + rot), ("embed", None), w),
            "kv_a_norm": ParamSpec((kl,), (None,), ones_init()),
            "k_b_kernel": ParamSpec((kl, nh, nope), (None, "heads", "kv"), w),
            "v_b_kernel": ParamSpec((kl, nh, vd), (None, "heads", "kv"), w),
            "out_kernel": ParamSpec((nh, vd, h), ("heads", "kv", "embed"), w),
        }
    else:
        attn = {
            "q_kernel": ParamSpec((h, nh, hd), ("embed", "heads", "kv"), w),
            "k_kernel": ParamSpec((h, nkv, hd), ("embed", "heads", "kv"), w),
            "v_kernel": ParamSpec((h, nkv, hd), ("embed", "heads", "kv"), w),
            "out_kernel": ParamSpec((nh, hd, h), ("heads", "kv", "embed"), w),
        }
        if cfg.attn_gate:
            attn["gate_kernel"] = ParamSpec((h, nh, hd), ("embed", "heads", "kv"), w)
        if cfg.qk_norm:
            attn["q_norm"] = ParamSpec((hd,), (None,), ones_init())
            attn["k_norm"] = ParamSpec((hd,), (None,), ones_init())
    from paddlefleetx_tpu.models.gpt.moe import dropless_layer_specs, swiglu_specs

    mlp = dropless_layer_specs(cfg) if experts else swiglu_specs(h, cfg.ffn_hidden_size, w)
    specs = {"ln_1": _rms_specs(h), "attn": attn, "ln_2": _rms_specs(h), "mlp": mlp}
    if cfg.post_norms:
        specs["post_attn_norm"] = _rms_specs(h)
        specs["post_mlp_norm"] = _rms_specs(h)
    if cfg.hyper_connections:
        # each sub-block's maps over the stream of hc_mult copies (docs/xing4.md;
        # float32 in the served tree, like the routers): ``phi`` one ROW a
        # number the maps take (h_pre, then h_post, then H_res row-major) over
        # the flattened stream, ``alpha`` the three gates, ``bias`` a number each.
        # Seeded: alpha 1 and a bias of spread 1, so that the maps move with
        # the stream (the paper's initial values leave them static)
        n, maps = cfg.hc_mult, cfg.hc_maps
        for name in ("hc_attn", "hc_mlp"):
            specs[name] = {
                "phi": ParamSpec((maps, n * h), (None, None), w),
                "alpha": ParamSpec((3,), (None,), ones_init()),
                "bias": ParamSpec((maps,), (None,), normal_init(1.0)),
            }
    return specs


def _layer_specs(cfg: GPTConfig) -> Dict[str, Any]:
    h, nh, hd, ffn = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim, cfg.ffn_hidden_size
    w = normal_init(cfg.initializer_range)
    specs: Dict[str, Any] = {
        "ln_1": {
            "scale": ParamSpec((h,), ("embed",), ones_init()),
            "bias": ParamSpec((h,), ("embed",), zeros_init()),
        },
        "attn": {
            "qkv_kernel": ParamSpec((h, 3, nh, hd), ("embed", None, "heads", "kv"), w),
            "qkv_bias": ParamSpec((3, nh, hd), (None, "heads", "kv"), zeros_init()),
            "out_kernel": ParamSpec((nh, hd, h), ("heads", "kv", "embed"), w),
            "out_bias": ParamSpec((h,), ("embed",), zeros_init()),
        },
        "ln_2": {
            "scale": ParamSpec((h,), ("embed",), ones_init()),
            "bias": ParamSpec((h,), ("embed",), zeros_init()),
        },
        "mlp": {
            "fc_in_kernel": ParamSpec((h, ffn), ("embed", "mlp"), w),
            "fc_in_bias": ParamSpec((ffn,), ("mlp",), zeros_init()),
            "fc_out_kernel": ParamSpec((ffn, h), ("mlp", "embed"), w),
            "fc_out_bias": ParamSpec((h,), ("embed",), zeros_init()),
        },
    }
    if cfg.num_experts > 1:
        from paddlefleetx_tpu.models.gpt.moe import moe_layer_specs

        specs["mlp"] = moe_layer_specs(cfg)
    return specs


def _pattern_layer_specs(cfg: GPTConfig, kind: str) -> Dict[str, Any]:
    """One layer of a ``layer_pattern`` block: ONE sub-block behind one
    RMSNorm (a ``P`` layer's sub-block holds two mixers, an ``ssm`` AND an
    ``attn`` group, side by side behind that norm).  Every layer has an
    ``mlp`` group (empty for a mixer layer): an expert layer is one whose
    ``mlp`` holds a router."""
    h, nh, nkv, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    w = normal_init(cfg.initializer_range)
    # the sub-block's output matrix: scaled down with the depth, so that the
    # residual stream of seeded weights keeps its size over the layers
    w_out = normal_init(cfg.initializer_range / (
        cfg.num_layers ** 0.5 if cfg.rescale_prenorm_residual else 1.0))
    specs: Dict[str, Any] = {"ln_1": _rms_specs(h), "mlp": {}}
    if kind in "MP":
        from paddlefleetx_tpu.models.gpt.ssm import mixer_specs

        specs["ssm"] = mixer_specs(cfg, w_out)
    if kind in "*PW":
        # a muP checkpoint's key_multiplier (0.011 as published) is a learning-rate
        # device, not a model of small keys: trained, W_k has grown against it.  Drawn
        # like the rest, the seeded scores' spread would be 0.02, every softmax uniform
        # and a reference check blind to rotation, pages and the constant itself; so the
        # seeded W_k is drawn against its constant (its FOLDED matrix is the plain draw)
        w_k = normal_init(cfg.initializer_range / cfg.mup.get("key_multiplier", 1.0))
        specs["attn"] = {
            "q_kernel": ParamSpec((h, nh, hd), ("embed", "heads", "kv"), w),
            "k_kernel": ParamSpec((h, nkv, hd), ("embed", "heads", "kv"), w_k),
            "v_kernel": ParamSpec((h, nkv, hd), ("embed", "heads", "kv"), w),
            "out_kernel": ParamSpec((nh, hd, h), ("heads", "kv", "embed"), w_out),
        }
    if kind in "E-":
        from paddlefleetx_tpu.models.gpt.moe import (
            dropless_layer_specs, relu2_specs, swiglu_specs)

        if kind == "E":
            specs["mlp"] = dropless_layer_specs(cfg, w_out)
        elif cfg.mlp_act == "relu2":
            specs["mlp"] = relu2_specs(h, cfg.ffn_hidden_size, w, w_out)
        else:
            specs["mlp"] = swiglu_specs(h, cfg.ffn_hidden_size, w)
    return specs


def gpt_specs(cfg: GPTConfig) -> Dict[str, Any]:
    w = normal_init(cfg.initializer_range)
    if cfg.layer_pattern:
        # layers of different kinds share no stack: the tree is made as it
        # is served, ``blocks`` a tuple of one dict a layer
        table = (cfg.vocab_size, cfg.hidden_size)
        word = ParamSpec(table, ("vocab", "embed"), slab_init(w, table))
        return {
            "embeddings": {"word": word},
            "blocks": tuple(_pattern_layer_specs(cfg, kind) for kind in cfg.layer_pattern),
            "final_ln": _rms_specs(cfg.hidden_size),
            "head": {"kernel": word},
        }
    if not cfg.classic_block:
        n_dense = cfg.leading_dense_layers
        word = ParamSpec((cfg.vocab_size, cfg.hidden_size), ("vocab", "embed"), w)
        specs: Dict[str, Any] = {
            "embeddings": {"word": word},
            "layers": stack_spec_tree(
                _block_layer_specs(cfg, cfg.moe_dropless), cfg.num_layers - n_dense),
            "final_ln": _rms_specs(cfg.hidden_size),
            "head": {"kernel": word},
        }
        if n_dense:
            specs["dense_layers"] = stack_spec_tree(_block_layer_specs(cfg, False), n_dense)
        return specs
    return {
        "embeddings": {
            "word": ParamSpec((cfg.vocab_size, cfg.hidden_size), ("vocab", "embed"), w),
            "position": ParamSpec(
                (cfg.max_position_embeddings, cfg.hidden_size), ("table", "embed"), w
            ),
        },
        "layers": stack_spec_tree(_layer_specs(cfg), cfg.num_layers),
        "final_ln": {
            "scale": ParamSpec((cfg.hidden_size,), ("embed",), ones_init()),
            "bias": ParamSpec((cfg.hidden_size,), ("embed",), zeros_init()),
        },
    }


def init(cfg: GPTConfig, key: jax.Array) -> Dict[str, Any]:
    return init_params(key, gpt_specs(cfg))


def gpt_logical_axes(cfg: GPTConfig) -> Dict[str, Any]:
    return logical_axes(gpt_specs(cfg))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


# LayerNorm alone on a v5e (PR 54: bfloat16 x, float32 scale and bias; 8 norms
# chained in one jit, forward alone and forward + backward under a random
# cotangent; ms a norm, every device op's self time from a trace of 5 chains.
# ``kernel`` is ``ops/fused_layernorm.py`` as it stands: 1 MiB of x a grid
# step, the backward recomputing mean / rstd.  In brackets the kernel as it
# stood before PR 54: 256 rows a step, the forward writing mean / rstd as two
# lane-padded ``(rows, 1)`` float32 columns for the backward to read):
#
#   width  rows    forward: composite  kernel  [before]    + backward: composite  kernel  [before]
#   1024   4096             0.0246     0.0097  [0.0122]                0.0485     0.0322  [0.0330]
#   1024   8192             0.0488     0.0188  [0.0236]                0.0997     0.0751  [0.1052]
#   1024   16384            0.0973     0.0369  [0.0463]                0.2071     0.1814  [0.2852]
#   1024   32768            0.2133     0.1257  [0.1807]                0.6205     0.4023  [0.6062]
#   2048   4096             0.0536     0.0186  [0.0209]                0.0928     0.0746  [0.0961]
#   2048   8192             0.1067     0.0364  [0.0408]                0.1967     0.1811  [0.2572]
#   2048   16384            0.2312     0.1255  [0.1526]                0.6108     0.4019  [0.5472]
#   2048   32768            0.5037     0.4092  [0.4643]                1.3886     1.0395  [1.1400]
#
# The kernel is ahead at all eight, forward and forward + backward (the one
# before it was BEHIND the composite forward + backward at 8,192 and 16,384
# rows, the 345M step's own shape; 256 rows a step with the recompute read
# 0.0387 / 0.1891 at 16,384 x 1,024, 128 rows 0.0480 / 0.2083).  The table is
# those eight points and nothing between or beyond them: a kernel's time
# alone does not say what the step around it does (PERF.md section 7), so a
# shape enters when somebody has timed it.  Of the eight, a cell trains
# 16,384 x 1,024 alone.  The serving cells' shapes (8 to 64 rows a decode
# step, one prompt of 512-1,024 rows a prefill, forward only, width 2,048)
# are NOT entered: a Mosaic call breaks the fusion it sits in and no serving
# cell resolves the difference; PERF.md section 7 has what they read alone.
# A batched prefill of 4,096 rows at these widths (8 prompts of 512,
# ``core/serving.GenerationServer``'s buckets) IS one of the eight, and takes
# the kernel.  Every width here is whole lanes (a multiple of 128) and every
# row count whole blocks of ``fused_layernorm._row_block``.
_KERNEL_AHEAD = frozenset(
    (rows, width, "bfloat16") for width in (1024, 2048) for rows in (4096, 8192, 16384, 32768))


def _norm_schedule(rows: int, width: int, dtype, compiled: bool) -> str:
    """What runs a LayerNorm over ``rows`` x ``width`` of ``dtype`` (the rows
    of ONE shard under a mesh), from those static values: the one place it is
    chosen.  ``kernel`` (``ops/fused_layernorm.py``) where Pallas kernels are
    compiled (on the CPU the interpreter is no kernel) and the shape was
    measured ahead (the table above); ``composite`` for everything else,
    until somebody measures it."""
    ahead = (rows, width, jnp.dtype(dtype).name) in _KERNEL_AHEAD
    return "kernel" if compiled and ahead else "composite"


def layer_norm(
    x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5,
    ctx: Optional[ShardingCtx] = None,
):
    """LayerNorm over the last dim of x [b, s, h], statistics in float32.
    ``_norm_schedule`` chooses what runs it from the shapes; nothing else
    does.  Under a mesh hand the ``ctx`` in: the rule then reads one shard's
    rows, and the kernel (row-independent) runs inside ``shard_map`` over the
    batch and seq axes, because a bare Mosaic kernel cannot be partitioned;
    a call without one is one device's."""
    act = ("batch", "seq", None)
    shard = x.shape
    if ctx is not None:
        from paddlefleetx_tpu.parallel.sharding import kernel_shard_shape

        shard = kernel_shard_shape(ctx.mesh, ctx.rules, x.shape, act)
    if _norm_schedule(math.prod(shard[:-1]), shard[-1], x.dtype,
                      not _device.pallas_interpret()) == "kernel":
        from paddlefleetx_tpu.ops.fused_layernorm import fused_layer_norm

        def kernel(x, scale, bias):
            return fused_layer_norm(x, scale, bias, eps=eps)

        if ctx is not None:
            kernel = ctx.shard_kernel(kernel, (act, (None,), (None,)), act)
        return kernel(x, scale, bias)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm over the last dim in float32, learned scale, no bias."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _norm(x: jax.Array, p: Dict[str, Any], cfg: GPTConfig, ctx: Optional[ShardingCtx] = None):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["scale"], cfg.norm_eps)
    return layer_norm(x, p["scale"], p["bias"], eps=cfg.norm_eps, ctx=ctx)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding over all head dims of x [b, s, n, d],
    positions 0..s-1, angles in float32."""
    s, d = x.shape[1], x.shape[-1]
    # lax.iota, not jnp.arange: a static arange is a host constant, which
    # this jax hoists into an argument of every program that traces it
    inv_freq = theta ** (-2.0 * jax.lax.iota(jnp.float32, d // 2) / d)
    ang = jax.lax.iota(jnp.float32, s)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    out = xf * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)
    return out.astype(x.dtype)


def rope_at(x: jax.Array, positions: jax.Array, theta: float,
            inv_freq: Optional[jax.Array] = None, factor: float = 1.0) -> jax.Array:
    """:func:`rope` at given ``positions`` [b, s] of x [b, s, n, d] (a decode
    step rotates one token a row, each at its own position).  :func:`rope`
    stays as it is written: the Trinity-Mini train step, a benchmark cell,
    lowers through it (tests/test_program_text.py).  ``inv_freq`` [d / 2]:
    the frequencies in place of theta's own (YaRN's blend,
    :func:`layer_rope_at`); ``factor`` multiplies cos and sin."""
    half = x.shape[-1] // 2
    if inv_freq is None:
        inv_freq = theta ** (-jax.lax.iota(jnp.float32, half) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq  # [b, s, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def layer_rope_at(x: jax.Array, positions: jax.Array, cfg: GPTConfig, kind: str) -> jax.Array:
    """q or k [b, s, n, d] of a layer_pattern's attention layer of ``kind``
    rotated at ``positions`` as ``GPTConfig.layer_rotation`` says: a ``W``
    layer plainly at ``rope_theta``; a layer that sees the whole context,
    under ``rope_scaling_factor`` > 1, at YaRN's blended frequencies over the
    whole head with cos and sin times ``rope_yarn_m``."""
    scaled, factor = cfg.layer_rotation(kind)
    if not scaled:
        return rope_at(x, positions, cfg.rope_theta)
    return rope_at(x, positions, cfg.rope_theta, rope_frequencies(cfg, x.shape[-1]), factor)


def rope_frequencies(cfg: GPTConfig, d: int = 0) -> jax.Array:
    """The d / 2 rotation frequencies (``d`` 0: latent attention's
    qk_rope_head_dim): theta^(-2i/d), under YaRN each blended with itself /
    factor by the linear ramp between the correction dims of beta_fast and
    beta_slow at the original context (the dims below the first keep their
    frequency, those above the second are divided by the factor; the dims
    floored and ceiled, as the published default truncates them)."""
    import math

    d = d or cfg.qk_rope_head_dim
    i = jax.lax.iota(jnp.float32, d // 2)
    freq = cfg.rope_theta ** (-2.0 * i / d)
    if cfg.rope_scaling_factor <= 1.0:
        return freq

    def correction_dim(rotations: float) -> float:
        return d * math.log(cfg.rope_original_max_position / (rotations * 2 * math.pi)) / (
            2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / cfg.rope_scaling_factor * ramp


def latent_softmax_scale(cfg: GPTConfig) -> float:
    """(qk_nope + qk_rope)^-0.5, times YaRN's m squared."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * cfg.rope_yarn_m ** 2


def rope_pairs(x: jax.Array, positions: jax.Array, cfg: GPTConfig) -> jax.Array:
    """Rotate ADJACENT pairs (2i, 2i+1) of the last dim of x [..., s, *, d]
    (or [..., s, d]) by positions [..., s] x ``rope_frequencies``, angles
    in float32; the cos/sin factor mscale / mscale_all_dim."""
    ang = positions.astype(jnp.float32)[..., None] * rope_frequencies(cfg)
    if x.ndim == positions.ndim + 2:
        ang = ang[..., None, :]
    m = 1.0
    if cfg.rope_scaling_factor > 1.0:
        import math

        m = (0.1 * cfg.rope_mscale * math.log(cfg.rope_scaling_factor) + 1.0) / cfg.rope_yarn_m
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def latent_projections(p, x, positions, cfg: GPTConfig):
    """x [b, s, h] at ``positions`` [b, s] -> (q_nope [b, s, n, nope],
    rotated q_rope [b, s, n, rot], what the cache keeps of each token: the
    normalised latent then the rotated shared key, [b, s, kv_lora + rot])."""
    dtype = x.dtype
    kl, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    c_q = rms_norm(x @ p["q_a_kernel"].astype(dtype), p["q_a_norm"], cfg.norm_eps)
    q = (c_q @ p["q_b_kernel"].astype(dtype)).reshape(
        x.shape[:2] + (cfg.num_attention_heads, -1))
    kv = x @ p["kv_a_kernel"].astype(dtype)
    c = rms_norm(kv[..., :kl], p["kv_a_norm"], cfg.norm_eps)
    k_r = rope_pairs(kv[..., kl:], positions, cfg)
    q_r = rope_pairs(q[..., nope:], positions, cfg)
    return q[..., :nope], q_r, jnp.concatenate([c, k_r], axis=-1)


def latent_attention_expanded(p, q_nope, q_r, latent, cfg: GPTConfig, ctx=None) -> jax.Array:
    """The EXPANDED form over one causal sequence: keys and values of every
    head made from the latents, [b, s, n, v_head_dim] out.  The softmax
    scale is folded into q (the attention entry point scales by d^-0.5);
    under ``attn_impl: flash`` the values are padded to the key width,
    which the kernel wants equal (the padding's columns are cut again)."""
    dtype = q_nope.dtype
    kl = cfg.kv_lora_rank
    c, k_r = latent[..., :kl], latent[..., kl:]
    k_nope = jnp.einsum("bsc,cnd->bsnd", c, p["k_b_kernel"].astype(dtype))
    v = jnp.einsum("bsc,cnd->bsnd", c, p["v_b_kernel"].astype(dtype))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None], k_nope.shape[:-1] + k_r.shape[-1:])], axis=-1)
    q = jnp.concatenate([q_nope, q_r], axis=-1)
    d = q.shape[-1]
    q = (q.astype(jnp.float32) * (latent_softmax_scale(cfg) * d ** 0.5)).astype(dtype)
    flash = cfg.attn_impl == "flash" and d > v.shape[-1]
    if flash:
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, d - v.shape[-1]),))
    out = attention(q, k, v, impl=cfg.attn_impl, causal=True, ctx=ctx)
    return out[..., :cfg.v_head_dim] if flash else out


def _layer_remat(cfg: GPTConfig, fn):
    """Wrap a per-layer scan body in jax.checkpoint per recompute granularity.

    "full" saves only layer-boundary activations (reference recompute
    single_model.py:320-405); "selective" additionally saves the named
    activations qkv + attn_out + attn_lse so the backward pass skips the
    expensive recomputes — the TPU-native middle ground the reference
    lacks.  All three attention paths name their result attn_out (XLA and
    ring where they return it, the flash kernel inside its custom_vjp
    forward rule beside attn_lse), so none re-runs its attention in the
    backward."""
    if not cfg.use_recompute:
        return fn
    if cfg.recompute_granularity == "full":
        return jax.checkpoint(fn)
    if cfg.recompute_granularity == "selective":
        # The save-set trades HBM residency+traffic against recompute FLOPs;
        # qkv+attn_out+attn_lse measured fastest on v5e (saving mlp_hidden
        # costs 3GB of HBM round-trips per step for a 0.7ms matmul re-run;
        # the flash kernel's attn_out is 32 MB a layer at the 345M recipe
        # against a 1.1 ms kernel re-run: PERF.md section 6, PR 49)
        policy = jax.checkpoint_policies.save_only_these_names("qkv", "attn_out", "attn_lse")
        return jax.checkpoint(fn, policy=policy)
    return fn


def _attention_block(
    p: Dict[str, Any],
    x: jax.Array,
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx],
    key: Optional[jax.Array],
    train: bool,
) -> jax.Array:
    """Fused-qkv causal self-attention.  x: [b, s, h] -> [b, s, h]."""
    dtype = x.dtype
    k_attn, k_resid = (jax.random.split(key) if key is not None else (None, None))

    # qkv: [b, s, 3, nh, hd]  (column-parallel: nh sharded over `model`)
    qkv = jnp.einsum("bsh,htnd->bstnd", x, p["qkv_kernel"].astype(dtype))
    qkv = qkv + p["qkv_bias"].astype(dtype)[None, None]
    qkv = checkpoint_name(qkv, "qkv")
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    if cfg.attn_impl == "ring" and ctx is not None:
        # context parallelism: seq stays sep-sharded; K/V rotate the ring.
        # NB: attention-prob dropout is skipped here, like the flash path
        # (reference disables dropout under flash too, hybrid_model.py:284)
        from paddlefleetx_tpu.parallel.ring_attention import ring_attention

        q = _constrain(ctx, q, ("batch", "seq", "heads", "kv"))
        chunk_k = int(getattr(cfg, "ring_chunk_k", 1024)) or None
        pos = ctx.attn_positions
        if cfg.use_recompute and cfg.recompute_granularity == "core_attn":
            ring = jax.checkpoint(
                lambda q, k, v, mesh=ctx.mesh: ring_attention(
                    q, k, v, mesh, causal=True, chunk_k=chunk_k, positions=pos
                )
            )
            out = ring(q, k, v)
        else:
            out = ring_attention(
                q, k, v, ctx.mesh, causal=True, chunk_k=chunk_k, positions=pos
            )
        out = checkpoint_name(out, "attn_out")
        out = jnp.einsum("bsnd,ndh->bsh", out, p["out_kernel"].astype(dtype))
        out = out + p["out_bias"].astype(dtype)
        return dropout(k_resid, out, cfg.hidden_dropout_prob, train)

    # Ulysses/TP reshard: heads spread over (model, sep), seq gathered
    q = _constrain(ctx, q, ("batch", None, "heads", "kv"))

    def core(q, k, v, dk):
        return attention(
            q,
            k,
            v,
            impl=cfg.attn_impl,
            causal=True,
            dropout_key=dk,
            dropout_rate=cfg.attention_probs_dropout_prob,
            train=train,
            ctx=ctx,
        )

    if cfg.use_recompute and cfg.recompute_granularity == "core_attn":
        core = jax.checkpoint(core, static_argnums=())
    out = core(q, k, v, k_attn)  # [b, s, nh, hd]

    # row-parallel output projection: contraction over sharded heads -> psum
    out = jnp.einsum("bsnd,ndh->bsh", out, p["out_kernel"].astype(dtype))
    out = out + p["out_bias"].astype(dtype)
    out = dropout(k_resid, out, cfg.hidden_dropout_prob, train)
    return out


def _mlp_block(
    p: Dict[str, Any],
    x: jax.Array,
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx],
    key: Optional[jax.Array],
    train: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out, moe_aux_loss); aux is 0 for the dense FFN."""
    if cfg.num_experts > 1:
        from paddlefleetx_tpu.models.gpt.moe import moe_mlp_block

        return moe_mlp_block(p, x, cfg, ctx, key, train)
    dtype = x.dtype
    h = x @ p["fc_in_kernel"].astype(dtype) + p["fc_in_bias"].astype(dtype)
    h = _constrain(ctx, h, ("batch", None, "mlp"))
    h = checkpoint_name(h, "mlp_hidden")
    h = jax.nn.gelu(h, approximate=True)
    h = h @ p["fc_out_kernel"].astype(dtype) + p["fc_out_bias"].astype(dtype)
    h = dropout(key, h, cfg.hidden_dropout_prob, train)
    return h, jnp.zeros((), jnp.float32)


def _decoder_layer(
    p: Dict[str, Any],
    x: jax.Array,
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx],
    key: Optional[jax.Array],
    train: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Pre-LN decoder block (reference TransformerDecoderLayer
    single_model.py:406: x + attn(ln(x)); x + mlp(ln(x)))."""
    k_attn, k_mlp = (jax.random.split(key) if key is not None else (None, None))

    def attn_part(p, x, k):
        y = layer_norm(x, p["ln_1"]["scale"], p["ln_1"]["bias"], ctx=ctx)
        y = _constrain(ctx, y, ("batch", "seq", "embed"))
        return _attention_block(p["attn"], y, cfg, ctx, k, train)

    if cfg.use_recompute and cfg.recompute_granularity == "full_attn":
        attn_part = jax.checkpoint(attn_part)

    x = x + attn_part(p, x, k_attn)
    x = _constrain(ctx, x, ("batch", "seq", "embed"))

    y = layer_norm(x, p["ln_2"]["scale"], p["ln_2"]["bias"], ctx=ctx)
    y, aux = _mlp_block(p["mlp"], y, cfg, ctx, k_mlp, train)
    x = x + y
    return _constrain(ctx, x, ("batch", "seq", "embed")), aux


def _block_attention(p, x, cfg: GPTConfig, ctx, window: int, rotate: bool) -> jax.Array:
    """Grouped-query causal attention as the vocabulary spells it.
    x: [b, s, h] -> [b, s, h]."""
    dtype = x.dtype
    if cfg.latent_attention:
        positions = jnp.broadcast_to(jax.lax.iota(jnp.int32, x.shape[1])[None], x.shape[:2])
        q_nope, q_r, latent = latent_projections(p, x, positions, cfg)
        with jax.named_scope("pfx.attn.mla.prefill"):
            out = latent_attention_expanded(p, q_nope, q_r, latent, cfg, ctx)
        return jnp.einsum("bsnd,ndh->bsh", out, p["out_kernel"].astype(dtype))

    def proj(name):
        return jnp.einsum("bsh,hnd->bsnd", x, p[f"{name}_kernel"].astype(dtype))

    q, k, v = proj("q"), proj("k"), proj("v")
    if cfg.qk_norm:
        q, k = rms_norm(q, p["q_norm"], cfg.norm_eps), rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rotate:
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    q = _constrain(ctx, q, ("batch", None, "heads", "kv"))
    with jax.named_scope("pfx.attn.window" if window else "pfx.attn.full"):
        out = attention(
            q, k, v, impl=cfg.attn_impl, causal=True, ctx=ctx, window=window,
        )
    if cfg.attn_gate:
        out = out * jax.nn.sigmoid(proj("gate").astype(jnp.float32)).astype(dtype)
    return jnp.einsum("bsnd,ndh->bsh", out, p["out_kernel"].astype(dtype))


def _block_layer(p, x, cfg: GPTConfig, ctx, kind: Tuple[int, bool], expert_bias):
    """One decoder layer of the described block: x + [norm](attn(norm(x))),
    then x + [norm](mlp(norm(x))).  ``kind`` = (window, rotate) is static;
    an expert layer is one whose parameters hold a router.  Returns
    (x, the expert layer's load statistics or None)."""
    window, rotate = kind
    y = _block_attention(p["attn"], _norm(x, p["ln_1"], cfg), cfg, ctx, window, rotate)
    if cfg.post_norms:
        y = _norm(y, p["post_attn_norm"], cfg)
    x = _constrain(ctx, x + y, ("batch", "seq", "embed"))
    m = _norm(x, p["ln_2"], cfg)
    from paddlefleetx_tpu.models.gpt.moe import dropless_moe_block, swiglu

    if "router_kernel" in p["mlp"]:
        # the training call site: the sorted pairs' buffer follows the load
        f, stats = dropless_moe_block(p["mlp"], m, cfg, ctx, expert_bias, load_ladder=True)
    else:
        f, stats = swiglu(m, p["mlp"]), None
    if cfg.post_norms:
        f = _norm(f, p["post_mlp_norm"], cfg)
    return _constrain(ctx, x + f, ("batch", "seq", "embed")), stats


def _block_stack(params, x, cfg: GPTConfig, ctx, expert_bias):
    """The described block's stack: leading dense layers one by one, then a
    ``lax.scan`` over whole periods of the window/full pattern (the kind of
    each position in a period is static), then what is left of a period.
    Returns (hidden, per-expert-layer statistics stacked on a leading axis,
    or None without expert layers)."""
    if ctx is not None and ctx.pipeline is not None and ctx.pipeline.num_stages > 1:
        raise NotImplementedError("pipeline stages know the GPT-2 block only")
    if cfg.hyper_connections:
        raise NotImplementedError(
            "hc_mult: the training forward keeps ONE residual stream; a stream of several "
            "copies is served only (the maps have no backward pass yet, ROADMAP queue 2)")
    n_dense = cfg.leading_dense_layers
    n_rest = cfg.num_layers - n_dense
    period = cfg.global_attn_every or 1
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731

    def layer(l):  # static layer index -> remat-wrapped layer function
        return _layer_remat(cfg, lambda p, x, b: _block_layer(p, x, cfg, ctx, cfg.layer_kind(l), b))

    for l in range(n_dense):
        x, _ = layer(l)(at(params["dense_layers"], l), x, None)
    bias = expert_bias if cfg.moe_dropless else jnp.zeros((n_rest, 0), jnp.float32)
    n_periods = n_rest // period
    whole = n_periods * period
    stats = []
    if n_periods:
        def body(x, inp):
            lp, b = inp
            out = []
            for j in range(period):
                x, st = layer(n_dense + j)(at(lp, j), x, b[j])
                out.append(st)
            return x, (None if out[0] is None else jax.tree.map(lambda *a: jnp.stack(a), *out))

        grouped = jax.tree.map(
            lambda a: a[:whole].reshape((n_periods, period) + a.shape[1:]),
            (params["layers"], bias))
        x, st = jax.lax.scan(body, x, grouped)
        if st is not None:
            stats.append(jax.tree.map(lambda a: a.reshape((whole,) + a.shape[2:]), st))
    for l in range(whole, n_rest):
        x, st = layer(n_dense + l)(at(params["layers"], l), x, bias[l])
        if st is not None:
            stats.append(jax.tree.map(lambda a: a[None], st))
    if not stats:
        return x, None
    return x, jax.tree.map(lambda *a: jnp.concatenate(a), *stats)


def transformer_stack(
    layers_params: Dict[str, Any],
    x: jax.Array,
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx],
    key: Optional[jax.Array],
    train: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Stacked-layer body: lax.scan (accumulating MoE aux losses), or the
    stage pipeline when enabled.  Returns (hidden, aux_loss_sum)."""

    if ctx is not None and ctx.pipeline is not None and ctx.pipeline.num_stages > 1:
        if cfg.num_experts > 1:
            # reference parity: MoE requires pp==1 (HybridCommGroupForMoE
            # asserts, comm_groups.py:150)
            raise NotImplementedError("MoE with pipeline parallelism unsupported")
        from paddlefleetx_tpu.parallel.pipeline import pipelined_stack

        S = ctx.pipeline.num_stages
        if cfg.num_layers % S:
            raise ValueError(f"num_layers {cfg.num_layers} not divisible by stages {S}")
        per_stage = cfg.num_layers // S

        def stage_fn(local_params, x_mb, stage, mb):
            def sbody(carry, inp):
                params_l, local_idx = inp
                # dropout key folds on the GLOBAL layer index AND the
                # microbatch index — each microbatch must draw its own mask
                k = (
                    jax.random.fold_in(
                        jax.random.fold_in(key, stage * per_stage + local_idx), mb
                    )
                    if key is not None
                    else None
                )
                out, _aux = _decoder_layer(params_l, carry, cfg, ctx, k, train)
                return out, None

            sbody_fn = _layer_remat(cfg, sbody)
            x_mb, _ = jax.lax.scan(
                sbody_fn, x_mb, (local_params, jnp.arange(per_stage))
            )
            return x_mb

        return (
            pipelined_stack(stage_fn, layers_params, x, ctx.pipeline, ctx.mesh),
            jnp.zeros((), jnp.float32),
        )

    def body(carry, inp):
        x, aux_sum = carry
        params_l, idx = inp
        k = jax.random.fold_in(key, idx) if key is not None else None
        out, aux = _decoder_layer(params_l, x, cfg, ctx, k, train)
        return (out, aux_sum + aux), None

    body_fn = _layer_remat(cfg, body)

    (x, aux), _ = jax.lax.scan(
        body_fn,
        (x, jnp.zeros((), jnp.float32)),
        (layers_params, jnp.arange(cfg.num_layers)),
    )
    return x, aux


def _embed(
    params: Dict[str, Any],
    input_ids: jax.Array,
    position_ids: Optional[jax.Array],
    cfg: GPTConfig,
    ctx: Optional[ShardingCtx],
    key: Optional[jax.Array],
    train: bool,
) -> jax.Array:
    """Word + position embedding with embedding dropout -> [b, s, h]."""
    dtype = jnp.dtype(cfg.dtype)
    s = input_ids.shape[1]
    if position_ids is None:
        position_ids = jnp.arange(s, dtype=jnp.int32)[None, :]
    word = params["word"].astype(dtype)
    if cfg.position == "learned":
        x = word[input_ids] + params["position"].astype(dtype)[position_ids]
    else:
        x = word[input_ids]
    if cfg.embed_scale_sqrt_hidden:
        x = x * cfg.hidden_size ** 0.5
    x = _constrain(ctx, x, ("batch", "seq", "embed"))
    return dropout(key, x, cfg.hidden_dropout_prob, train)


def forward_hidden(
    params: Dict[str, Any],
    input_ids: jax.Array,
    cfg: GPTConfig,
    *,
    position_ids: Optional[jax.Array] = None,
    ctx: Optional[ShardingCtx] = None,
    dropout_key: Optional[jax.Array] = None,
    train: bool = False,
    expert_bias: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any]:
    """Token ids [b, s] -> (final hidden [b, s, h], moe aux loss sum; for the
    dropless expert layer instead its load statistics, stacked over the
    expert layers).  ``expert_bias`` [expert layers, experts] is that
    layer's routing buffer (None = zeros)."""
    if cfg.layer_pattern:
        raise NotImplementedError(
            "a layer_pattern block is served (models/gpt/generation.py), not trained: the "
            "chunked scan has no backward pass yet")
    k_embed, k_layers = (
        jax.random.split(dropout_key) if dropout_key is not None else (None, None)
    )
    x = _embed(params["embeddings"], input_ids, position_ids, cfg, ctx, k_embed, train)

    if cfg.classic_block:
        x, aux = transformer_stack(params["layers"], x, cfg, ctx, k_layers, train)
    else:
        if cfg.moe_dropless and expert_bias is None:
            expert_bias = init_extra(cfg)["expert_bias"]
        x, aux = _block_stack(params, x, cfg, ctx, expert_bias)
    x = _norm(x, params["final_ln"], cfg, ctx)
    return _constrain(ctx, x, ("batch", "seq", "embed")), aux


def head_matrix(params: Dict[str, Any]) -> jax.Array:
    """[vocab, hidden] matrix the logits and the loss read."""
    return params["head"]["kernel"] if "head" in params else params["embeddings"]["word"]


def logits_from_hidden(
    params: Dict[str, Any], hidden: jax.Array, ctx: Optional[ShardingCtx] = None
) -> jax.Array:
    """LM head: the tied word embedding (reference parallel_matmul
    hybrid_model.py:66), or the ``head`` matrix of an untied model."""
    word = head_matrix(params).astype(hidden.dtype)
    logits = jnp.einsum("bsh,vh->bsv", hidden, word)
    return _constrain(ctx, logits, ("batch", "seq", "vocab"))


def forward(
    params: Dict[str, Any],
    input_ids: jax.Array,
    cfg: GPTConfig,
    *,
    position_ids: Optional[jax.Array] = None,
    ctx: Optional[ShardingCtx] = None,
    dropout_key: Optional[jax.Array] = None,
    train: bool = False,
    expert_bias: Optional[jax.Array] = None,
) -> jax.Array:
    hidden, _ = forward_hidden(
        params,
        input_ids,
        cfg,
        position_ids=position_ids,
        ctx=ctx,
        dropout_key=dropout_key,
        train=train,
        expert_bias=expert_bias,
    )
    return logits_from_hidden(params, hidden, ctx)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def cross_entropy(
    logits: jax.Array, labels: jax.Array, loss_mask: Optional[jax.Array] = None
) -> jax.Array:
    """Masked-mean token CE in fp32 (GPTPretrainingCriterion single_model.py:819).

    Under TP the ``vocab`` dim of logits is model-sharded; the logsumexp and
    label gather partition cleanly (XLA inserts the psum the reference's
    ParallelCrossEntropy issues manually, hybrid_model.py:951).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - picked
    if loss_mask is None:
        return jnp.mean(nll)
    loss_mask = loss_mask.astype(jnp.float32)
    return jnp.sum(nll * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)


def _pipeline_train_loss(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: GPTConfig,
    ctx: ShardingCtx,
    dropout_key: Optional[jax.Array],
) -> jax.Array:
    """Training loss under pipeline parallelism via the 1F1B schedule.

    Embedding, per-chunk layer blocks, and the head+CE all run inside the
    schedule (parallel/pipeline.py); this function just adapts the GPT
    pieces to the (embed_fn, chunk_fn, head_fn) contract and divides the
    returned numerator by the global mask sum (reference
    GPTPretrainingCriterion masked mean, single_model.py:819)."""
    from paddlefleetx_tpu.parallel.pipeline import (
        interleave_permutation,
        pipeline_loss_1f1b,
    )

    if cfg.num_experts > 1:
        raise NotImplementedError("MoE with pipeline parallelism unsupported")
    pcfg = ctx.pipeline
    S, V = pcfg.num_stages, pcfg.num_virtual_stages
    C = S * V
    if cfg.num_layers % C:
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by {S} stages x {V} virtual"
        )
    pc = cfg.num_layers // C

    k_embed, k_layers = (
        jax.random.split(dropout_key) if dropout_key is not None else (None, None)
    )

    # batch leaves enter the custom-vjp pipeline as floats (ids < 2^24 are
    # exact in f32; zero cotangents) and are cast back inside the fns
    bsz, seq = batch["tokens"].shape
    fbatch = {
        "tokens": batch["tokens"].astype(jnp.float32),
        "labels": batch["labels"].astype(jnp.float32),
    }
    loss_mask = batch.get("loss_mask")
    fbatch["loss_mask"] = (
        jnp.ones((bsz, seq), jnp.float32)
        if loss_mask is None
        else loss_mask.astype(jnp.float32)
    )
    if batch.get("position_ids") is not None:
        fbatch["position_ids"] = batch["position_ids"].astype(jnp.float32)

    def embed_fn(eparams, mb, mbi):
        toks = mb["tokens"].astype(jnp.int32)
        pos_ids = (
            mb["position_ids"].astype(jnp.int32) if "position_ids" in mb else None
        )
        k = jax.random.fold_in(k_embed, mbi) if k_embed is not None else None
        return _embed(eparams, toks, pos_ids, cfg, ctx, k, True)

    def chunk_fn(chunk_params, x_mb, c, mbi):
        def sbody(carry, inp):
            params_l, local_idx = inp
            # semantic layer index: params are pre-permuted so execution
            # chunk c holds semantic layers [c*pc, (c+1)*pc) — key folding
            # matches the single-device scan exactly
            k = (
                jax.random.fold_in(jax.random.fold_in(k_layers, c * pc + local_idx), mbi)
                if k_layers is not None
                else None
            )
            out, _aux = _decoder_layer(params_l, carry, cfg, ctx, k, True)
            return out, None

        sbody_fn = _layer_remat(cfg, sbody)
        x_mb, _ = jax.lax.scan(sbody_fn, x_mb, (chunk_params, jnp.arange(pc)))
        return x_mb

    def head_fn(hparams, y_mb, mb, mbi):
        y = layer_norm(y_mb, hparams["final_ln"]["scale"], hparams["final_ln"]["bias"], ctx=ctx)
        y = _constrain(ctx, y, ("batch", "seq", "embed"))
        word = hparams["word"].astype(y.dtype)
        logits = jnp.einsum("bsh,vh->bsv", y, word)
        logits = _constrain(ctx, logits, ("batch", "seq", "vocab")).astype(jnp.float32)
        labels = mb["labels"].astype(jnp.int32)
        from paddlefleetx_tpu.models.common import one_hot_token_nll

        return jnp.sum(one_hot_token_nll(logits, labels) * mb["loss_mask"])

    layers_params = params["layers"]
    if V > 1:
        # NOTE: this per-step permutation crosses stage-shard boundaries
        # (one all-to-all of the layer stack each way per step).  Storing
        # params pre-permuted would amortize it but ties checkpoint layout
        # to the pipeline config (Megatron's choice); revisit if V>1 runs
        # become bandwidth-bound.
        perm = interleave_permutation(cfg.num_layers, S, V)
        layers_params = jax.tree.map(lambda a: jnp.take(a, perm, axis=0), layers_params)

    eparams = params["embeddings"]
    hparams = {"final_ln": params["final_ln"], "word": params["embeddings"]["word"]}
    numer = pipeline_loss_1f1b(
        (embed_fn, chunk_fn, head_fn),
        pcfg,
        ctx.mesh,
        (eparams, layers_params, hparams),
        fbatch,
    )
    return numer / jnp.maximum(jnp.sum(fbatch["loss_mask"]), 1.0)


def loss_fn(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: GPTConfig,
    *,
    ctx: Optional[ShardingCtx] = None,
    dropout_key: Optional[jax.Array] = None,
    train: bool = True,
    extra: Optional[Dict[str, Any]] = None,
):
    """batch: tokens [b,s], labels [b,s], loss_mask [b,s], position_ids opt.

    MoE models add the load-balance aux loss scaled by moe_aux_loss_weight
    (reference sharded_moe.py l_aux handling).  The dropless expert layer
    adds nothing to the loss: its balance is the routing bias's, which
    lives in ``extra`` (``init_extra``); given ``extra`` the result is
    (loss, extra after this step's bias rule and counters)."""
    if (
        train
        and ctx is not None
        and ctx.pipeline is not None
        and ctx.pipeline.num_stages > 1
    ):
        return _pipeline_train_loss(params, batch, cfg, ctx, dropout_key)
    hidden, aux = forward_hidden(
        params,
        batch["tokens"],
        cfg,
        position_ids=batch.get("position_ids"),
        ctx=ctx,
        dropout_key=dropout_key,
        train=train,
        expert_bias=None if extra is None else extra["expert_bias"],
    )
    from paddlefleetx_tpu.parallel.mesh import AXIS_MODEL

    vocab_sharded = ctx is not None and ctx.mesh.shape.get(AXIS_MODEL, 1) > 1
    if cfg.use_chunked_ce and not vocab_sharded:
        from paddlefleetx_tpu.ops.chunked_ce import chunked_cross_entropy

        loss = chunked_cross_entropy(
            hidden,
            head_matrix(params),
            batch["labels"],
            batch.get("loss_mask"),
            chunk=cfg.ce_chunk_size,
        )
    else:
        logits = logits_from_hidden(params, hidden, ctx)
        loss = cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    if cfg.moe_dropless:
        return loss if extra is None else (loss, next_extra(extra, aux, cfg, train))
    if cfg.num_experts > 1:
        loss = loss + cfg.moe_aux_loss_weight * aux
    return loss


# ---------------------------------------------------------------------------
# Non-gradient state of the dropless expert layer (the engine's ``extra``)
# ---------------------------------------------------------------------------

_LO = 1 << 20  # exact cumulative counts as (hi, lo) int32: hi * 2^20 + lo


def init_extra(cfg: GPTConfig) -> Dict[str, Any]:
    """``expert_bias`` [expert layers, experts] (moved by the balance rule
    after each training step, outside gradient and weight decay) and the
    step records' counters (cumulative, but for the last step's
    ``pairs_held_layer_max``)."""
    n_layers = cfg.num_layers - cfg.leading_dense_layers
    pair = jnp.zeros((2,), jnp.int32)
    return {
        "expert_bias": jnp.zeros((n_layers, cfg.num_experts), jnp.float32),
        "counters": {"pairs_total": pair, "pairs_held": pair, "buffer_rows": pair,
                     "load_max_over_mean_sum": jnp.zeros((), jnp.float32),
                     "pairs_held_layer_max": jnp.zeros((), jnp.int32)},
    }


def _count(counter: jax.Array, n: jax.Array) -> jax.Array:
    lo = counter[1] + n % _LO
    return jnp.stack([counter[0] + n // _LO + lo // _LO, lo % _LO])


def next_extra(extra, stats, cfg: GPTConfig, train: bool):
    """After a training step: the bias rule on the step's load, and the
    counters.  Evaluation leaves both alone."""
    if not train:
        return extra
    from paddlefleetx_tpu.models.gpt.moe import next_expert_bias

    c = extra["counters"]
    n_layers = stats["load"].shape[0]
    total = jnp.sum(stats["load"][0]) * n_layers  # tokens x top_k, every layer alike
    return {
        "expert_bias": next_expert_bias(
            extra["expert_bias"], stats["load"], cfg.moe_bias_update_rate),
        "counters": {
            "pairs_total": _count(c["pairs_total"], total),
            "pairs_held": _count(c["pairs_held"], jnp.sum(stats["pairs_held"])),
            # the rows of the buffer each layer ran: pairs_held over it is the
            # buffers' fill, it over pairs_total says which rungs ran
            "buffer_rows": _count(c["buffer_rows"], jnp.sum(stats["buffer_rows"])),
            "load_max_over_mean_sum": c["load_max_over_mean_sum"]
            + jnp.max(stats["load_max_over_mean"]),
            # a gauge: the fullest layer's held pairs of THIS step
            "pairs_held_layer_max": jnp.max(stats["pairs_held"]),
        },
    }


def warm_start_step(params, extra, tokens, i, cfg: GPTConfig, ctx=None):
    """Pass ``i`` (traced) of the routing bias's warm start, before the
    first optimizer step and forward only: the balance rule on this batch's
    load, at a rate that falls geometrically from
    ``moe_bias_warm_start_rate`` (pass 0) to ``moe_bias_update_rate`` (the
    last pass).  Returns (extra, the pairs each expert layer held)."""
    from paddlefleetx_tpu.models.gpt.moe import next_expert_bias

    first, last = cfg.moe_bias_warm_start_rate, cfg.moe_bias_update_rate
    rate = first * (last / first) ** (i / max(cfg.moe_bias_warm_start_steps - 1, 1))
    stats = forward_hidden(params, tokens, cfg, ctx=ctx, expert_bias=extra["expert_bias"])[1]
    bias = next_expert_bias(extra["expert_bias"], stats["load"], rate)
    return dict(extra, expert_bias=bias), stats["pairs_held"]


def extra_record(vals: Dict[str, Any]) -> Dict[str, Any]:
    """Host side: fetched ``extra_scalars`` -> step-record keys."""
    out = {f"moe_{k}": int(vals[k][0]) * _LO + int(vals[k][1])
           for k in ("pairs_total", "pairs_held", "buffer_rows")}
    out["moe_load_max_over_mean_sum"] = round(float(vals["load_max_over_mean_sum"]), 4)
    out["moe_pairs_held_layer_max"] = int(vals["pairs_held_layer_max"])
    out["moe_bias_abs_max"] = round(float(vals["bias_abs_max"]), 6)
    return out


def extra_scalars(extra: Dict[str, Any]) -> Dict[str, jax.Array]:
    """Device side: what of ``extra`` rides the step's metrics fetch."""
    return {**extra["counters"], "bias_abs_max": jnp.max(jnp.abs(extra["expert_bias"]))}
