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
    # copies a profile showed at ~4% of step time (builder-recorded,
    # docs/performance_tuning.md op table; not re-measured).
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

    def __post_init__(self):
        if self.ffn_hidden_size is None:
            object.__setattr__(self, "ffn_hidden_size", 4 * self.hidden_size)
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("num_attention_heads must divide hidden_size")
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
        return self.hidden_size // self.num_attention_heads

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
