"""GPT model hyperparameter config.

Field vocabulary matches the reference's GPT YAML ``Model`` block
(ppfleetx/configs/nlp/gpt/pretrain_gpt_base.yaml and
models/language_model/gpt/dygraph/single_model.py:608 ``GPTModel.__init__``),
so reference configs translate 1:1.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_attention_heads: int = 16
    ffn_hidden_size: Optional[int] = None  # defaults to 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    # recompute (reference recompute_granularity full/full_attn/core_attn,
    # single_model.py:320-405; "selective" is TPU-native: saves the expensive
    # matmul outputs by name and recomputes only cheap elementwise ops)
    use_recompute: bool = False
    recompute_granularity: str = "full"
    # comma-separated checkpoint names kept live under "selective"
    # (qkv | attn_out | attn_lse | mlp_hidden); empty = measured-best default
    recompute_names: str = ""
    # fused LayerNorm Pallas kernel (ops/fused_layernorm.py) instead of the
    # jnp composite (reference consumes paddle fused norm ops, vit.py:23-115)
    use_fused_ln: bool = False
    # chunked softmax-CE (ops/chunked_ce.py): streams the vocab so the
    # [b,s,V] fp32 logits buffer never materializes — the HBM lever for
    # bigger per-chip batches.  Ignored under vocab (model-axis) sharding
    # (the GSPMD path owns that reduction) and under pipeline parallelism
    # (the 1F1B head computes per-microbatch logits, already 1/M the size).
    use_chunked_ce: bool = False
    ce_chunk_size: int = 4096
    # fused qkv projection (reference fuse_attn_qkv, hybrid_model.py:153)
    fuse_attn_qkv: bool = True
    # attention implementation: "xla" (jnp reference) | "flash" (Pallas kernel)
    attn_impl: str = "xla"
    # flash kernel tile size (0 = auto: PFX_FLASH_BLOCK env, else the
    # measured-best ladder in ops/flash_attention._block_sizes)
    flash_block: int = 0
    # flash backward schedule: "" = auto (PFX_FLASH_BWD env, else "split");
    # "fused" = single-kernel dq+dk+dv (computes each softmax tile once)
    flash_bwd: str = ""
    # unroll factor for the scan over layers (lax.scan unroll=N): trades
    # compile time + code size for removing the scan-boundary stacking
    # copies (docs/performance_tuning.md "Scan unroll"; not measured on
    # today's code).
    # 1 = rolled (default); must divide num_layers
    scan_unroll: int = 1
    # ring attention inner K-block (attn_impl="ring"): bounds the per-ring-
    # step score buffer to [s_local, ring_chunk_k]; 0 = unchunked
    ring_chunk_k: int = 1024
    # Megatron sequence parallelism: activations sharded on seq over `model`
    sequence_parallel: bool = False
    # compute dtype for activations (params/optimizer stay fp32)
    dtype: str = "bfloat16"
    # MoE (0 or 1 = dense; >1 enables expert-parallel FFN, reference
    # single_model.py:480-492 num_experts)
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.2
    moe_gate: str = "gshard"  # naive | gshard | switch
    moe_aux_loss_weight: float = 0.01
    # -- block vocabulary: what the one decoder block reads beside the sizes.
    # The defaults are the GPT-2 block (LayerNorm, learned positions, fused
    # qkv MHA with biases, tanh-GELU, tied head); docs/trinity_mini.md shows
    # a second family written in the same words.  norm, position, use_bias,
    # mlp_act and tie_embeddings move together until a family needs them
    # apart: all at their defaults, or rmsnorm + rope + no bias + swiglu +
    # untied head (the described block, whose other options are each held
    # to the reference in tests/test_trinity_block.py).
    norm: str = "layernorm"  # layernorm | rmsnorm (learned scale, no bias)
    norm_eps: float = 1e-5
    # norms on each sub-block's OUTPUT too, before the residual add
    post_norms: bool = False
    position: str = "learned"  # learned | rope (rotate-half over all head dims)
    rope_theta: float = 10000.0
    # query heads stay num_attention_heads; 0 = as many KV heads (MHA)
    num_kv_heads: int = 0
    # 0 = hidden_size / num_attention_heads
    attn_head_dim: int = 0
    qk_norm: bool = False  # per-head RMSNorm of q and k, one scale per layer
    attn_gate: bool = False  # sigmoid output gate on the attention result
    use_bias: bool = True  # biases on the projections and the MLP
    mlp_act: str = "gelu"  # gelu (tanh) | swiglu
    tie_embeddings: bool = True
    embed_scale_sqrt_hidden: bool = False  # x0 = E[tokens] * sqrt(hidden)
    # attention window (0 = none) on every layer but each
    # ``global_attn_every``-th one ((l + 1) % n == 0: no window, and under
    # ``position: rope`` no rotation either); 0 = every layer alike
    sliding_window: int = 0
    global_attn_every: int = 0
    # leading layers that keep the dense MLP when num_experts > 1
    num_dense_layers: int = 0
    # dropless expert layer (moe_gate: sigmoid; models/gpt/moe.py): the
    # router scores all num_experts, this process holds moe_experts_held of
    # them (0 = all) from id moe_expert_offset on
    moe_ffn_hidden_size: int = 0  # 0 = ffn_hidden_size
    moe_experts_held: int = 0
    moe_expert_offset: int = 0
    moe_shared_experts: int = 0
    moe_route_scale: float = 1.0
    moe_bias_update_rate: float = 0.001
    # warm start of the routing bias, before the first optimizer step of a
    # run that starts at step 0: this many forward-only passes of the bias
    # rule over the next training batches, at a rate that falls
    # geometrically from moe_bias_warm_start_rate to moe_bias_update_rate
    # (0 = none; docs/trinity_mini.md says what it is for)
    moe_bias_warm_start_steps: int = 0
    moe_bias_warm_start_rate: float = 0.0

    def __post_init__(self):
        if self.ffn_hidden_size is None:
            object.__setattr__(self, "ffn_hidden_size", 4 * self.hidden_size)
        for field in ("norm_eps", "rope_theta", "moe_route_scale", "moe_bias_update_rate",
                      "moe_bias_warm_start_rate"):
            # YAML reads "1e-05" (an override's spelling of a float) as a string
            object.__setattr__(self, field, float(getattr(self, field)))
        if not self.attn_head_dim and self.hidden_size % self.num_attention_heads:
            raise ValueError("num_attention_heads must divide hidden_size")
        if self.num_attention_heads % (self.num_kv_heads or self.num_attention_heads):
            raise ValueError("num_kv_heads must divide num_attention_heads")
        if not self.classic_block:
            if (self.norm, self.position, self.use_bias, self.mlp_act,
                    self.tie_embeddings) != ("rmsnorm", "rope", False, "swiglu", False):
                raise ValueError(
                    "a block other than the GPT-2 one is norm: rmsnorm, position: rope, "
                    "use_bias: False, mlp_act: swiglu, tie_embeddings: False together")
            if self.hidden_dropout_prob or self.attention_probs_dropout_prob:
                raise ValueError("only the GPT-2 block has dropout; set both "
                                 "dropout probabilities to 0")
        if self.moe_bias_warm_start_steps and not (
                self.moe_dropless
                and self.moe_bias_warm_start_rate >= self.moe_bias_update_rate > 0):
            raise ValueError("moe_bias_warm_start_steps needs moe_gate: sigmoid and "
                             "moe_bias_warm_start_rate >= moe_bias_update_rate > 0")
        if self.moe_dropless:
            last = self.moe_expert_offset + self.experts_held - 1
            if not 0 <= self.moe_expert_offset <= last < self.num_experts:
                raise ValueError(
                    f"experts {self.moe_expert_offset}..{last} held of {self.num_experts}")
        elif not self.classic_block and self.num_experts > 1:
            raise ValueError("the capacity-factor MoE layer serves the GPT-2 block only; "
                             "set moe_gate: sigmoid for the dropless layer")
        if self.recompute_granularity not in ("full", "selective", "full_attn", "core_attn"):
            raise ValueError(f"bad recompute_granularity {self.recompute_granularity}")
        raw = self.recompute_names
        parts = raw if isinstance(raw, (list, tuple)) else str(raw).split(",")
        names = tuple(str(n).strip() for n in parts if str(n).strip())
        bad = set(names) - {"qkv", "attn_out", "attn_lse", "mlp_hidden"}
        if bad:
            raise ValueError(
                f"bad recompute_names {sorted(bad)}; "
                "valid: qkv, attn_out, attn_lse, mlp_hidden"
            )
        if names and self.recompute_granularity != "selective":
            raise ValueError(
                "recompute_names only applies to recompute_granularity='selective'"
            )
        if self.scan_unroll < 1 or self.num_layers % self.scan_unroll:
            raise ValueError(
                f"scan_unroll {self.scan_unroll} must be >=1 and divide "
                f"num_layers {self.num_layers}"
            )
        if self.flash_bwd not in ("", "split", "fused"):
            raise ValueError(
                f"flash_bwd {self.flash_bwd!r}; valid: '' (auto), split, fused"
            )
        object.__setattr__(self, "recompute_names", ",".join(names))

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_attention_heads

    @property
    def moe_dropless(self) -> bool:
        return self.num_experts > 1 and self.moe_gate == "sigmoid"

    @property
    def experts_held(self) -> int:
        return self.moe_experts_held or self.num_experts

    @property
    def leading_dense_layers(self) -> int:
        """Layers before the first expert layer (0 without expert layers)."""
        return self.num_dense_layers if self.moe_dropless else 0

    @property
    def classic_block(self) -> bool:
        """True for the GPT-2 block: its parameter tree, its programs and
        the paths that know only it (pipeline, generation, ring attention)."""
        return (self.norm == "layernorm" and self.position == "learned"
                and not self.num_kv_heads and not self.attn_head_dim
                and not self.qk_norm and not self.attn_gate and self.use_bias
                and self.mlp_act == "gelu" and self.tie_embeddings
                and not self.post_norms and not self.embed_scale_sqrt_hidden
                and not self.sliding_window and not self.num_dense_layers
                and not self.moe_dropless)

    def layer_kind(self, layer: int) -> Tuple[int, bool]:
        """(window or 0, rotate q and k) of layer ``layer``, counted from 0
        over the whole stack, leading dense layers included."""
        is_global = self.global_attn_every > 0 and (layer + 1) % self.global_attn_every == 0
        return (0 if is_global else self.sliding_window,
                self.position == "rope" and not is_global)

    @property
    def recompute_name_tuple(self) -> Tuple[str, ...]:
        """Normalized selective-remat save-set; empty = measured-best default."""
        return tuple(n for n in self.recompute_names.split(",") if n)

    @staticmethod
    def from_config(model_cfg) -> "GPTConfig":
        """Build from a YAML ``Model`` section (unknown keys ignored)."""
        fields = {f.name for f in dataclasses.fields(GPTConfig)}
        kwargs = {k: v for k, v in dict(model_cfg).items() if k in fields}
        return GPTConfig(**kwargs)


# Reference model sizes (projects/gpt/docs, configs/nlp/gpt/*.yaml)
PRESETS = {
    "gpt-345M": dict(hidden_size=1024, num_layers=24, num_attention_heads=16),
    "gpt-1.3B": dict(hidden_size=2048, num_layers=24, num_attention_heads=16),
    "gpt-6.7B": dict(hidden_size=4096, num_layers=32, num_attention_heads=32),
    "gpt-13B": dict(hidden_size=5120, num_layers=40, num_attention_heads=40),
    "gpt-175B": dict(hidden_size=12288, num_layers=96, num_attention_heads=96),
}


def preset(name: str, **overrides) -> GPTConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown preset {name}; known: {sorted(PRESETS)}")
    return GPTConfig(**{**PRESETS[name], **overrides})
