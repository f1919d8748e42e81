"""GPT model hyperparameter config.

Field vocabulary matches the reference's GPT YAML ``Model`` block
(ppfleetx/configs/nlp/gpt/pretrain_gpt_base.yaml and
models/language_model/gpt/dygraph/single_model.py:608 ``GPTModel.__init__``),
so reference configs translate 1:1.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple


# The muP constants ``GPTConfig.mup_multipliers`` may name, under the
# published names (model_type falcon_h1), with how many numbers each takes
# (0 = one scalar); where each multiplies: models/gpt/convert.py fold_mup
MUP_NAMES = {
    "embedding_multiplier": 0, "lm_head_multiplier": 0,
    "ssm_in_multiplier": 0, "ssm_out_multiplier": 0,
    "ssm_multipliers": 5,  # the in-projection's segments z | x | B | C | dt
    "attention_in_multiplier": 0, "attention_out_multiplier": 0, "key_multiplier": 0,
    "mlp_multipliers": 2,  # the gate's pre-activation, the down-projection's output
}


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
    # naive | gshard | switch (the capacity-factor layer) | sigmoid | softmax
    # (the dropless layer's two routing rules, models/gpt/moe.py)
    moe_gate: str = "gshard"
    moe_aux_loss_weight: float = 0.01
    # -- block vocabulary: what the one decoder block reads beside the sizes.
    # The defaults are the GPT-2 block (LayerNorm, learned positions, fused
    # qkv MHA with biases, tanh-GELU, tied head); docs/trinity_mini.md shows
    # a second family written in the same words.  norm, position, use_bias,
    # mlp_act and tie_embeddings move together until a family needs them
    # apart: all at their defaults, or rmsnorm + rope + no bias + swiglu +
    # untied head (the described block, whose other options are each held
    # to the reference in tests/test_trinity_block.py), or, with a
    # ``layer_pattern``, rmsnorm + none + no bias + relu2 + untied head
    # (docs/nemotron_h.md) or the described block's own words, rmsnorm + rope
    # + no bias + swiglu + untied head (docs/falcon_h1.md).
    norm: str = "layernorm"  # layernorm | rmsnorm (learned scale, no bias)
    norm_eps: float = 1e-5
    # norms on each sub-block's OUTPUT too, before the residual add
    post_norms: bool = False
    # learned | rope (rotate-half over all head dims) | none (a
    # layer_pattern block: its state-space layers carry the order)
    position: str = "learned"
    rope_theta: float = 10000.0
    # query heads stay num_attention_heads; 0 = as many KV heads (MHA)
    num_kv_heads: int = 0
    # 0 = hidden_size / num_attention_heads
    attn_head_dim: int = 0
    qk_norm: bool = False  # per-head RMSNorm of q and k, one scale per layer
    attn_gate: bool = False  # sigmoid output gate on the attention result
    use_bias: bool = True  # biases on the projections and the MLP
    # gelu (tanh) | swiglu | relu2 (non-gated: W_down relu(W_up m)^2)
    mlp_act: str = "gelu"
    tie_embeddings: bool = True
    embed_scale_sqrt_hidden: bool = False  # x0 = E[tokens] * sqrt(hidden)
    # attention window (0 = none) on every layer but each
    # ``global_attn_every``-th one ((l + 1) % n == 0: no window, and under
    # ``position: rope`` no rotation either); 0 = every layer alike.  In a
    # ``layer_pattern`` the window belongs to the ``W`` layers, and which
    # layers those are is the pattern's to say (no ``global_attn_every``)
    sliding_window: int = 0
    global_attn_every: int = 0
    # leading layers that keep the dense MLP when num_experts > 1
    num_dense_layers: int = 0
    # dropless expert layer (moe_gate: sigmoid, or softmax: a softmax over
    # all experts, the top-k's weights renormalised over the k, no bias, no
    # scale; models/gpt/moe.py): the router scores all num_experts, this
    # process holds moe_experts_held of them (0 = all) from id
    # moe_expert_offset on
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
    # group-limited choice: the experts are moe_n_group groups of equal
    # size, a group's score is the sum of its two highest choice scores,
    # the moe_topk_group best groups stay and the top-k is taken among
    # their experts (1 group = the plain top-k)
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # latent attention (kv_lora_rank > 0; docs/deepseek_v3.md): queries
    # through a q_lora_rank bottleneck with an RMSNorm, keys and values
    # expanded from ONE normalised latent of kv_lora_rank a token, plus one
    # rotated key of qk_rope_head_dim shared by all heads.  Head h scores
    # with qk_nope_head_dim + qk_rope_head_dim dims and yields v_head_dim.
    # The rotation is over ADJACENT pairs (2i, 2i+1) of the rope dims.  What
    # a cache holds of a token is the latent and the rotated key, once.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN frequencies of the rotation (rope_scaling_factor > 1): each
    # frequency blended with itself / factor by the linear ramp between
    # the correction dims of beta_fast and beta_slow at the original
    # context; the softmax scale gains (0.1 mscale_all_dim ln factor + 1)^2.
    # In a ``layer_pattern`` YaRN belongs to the layers that see the WHOLE
    # context (``*`` and ``P``: cos and sin times that factor, so the cached
    # keys carry it); a ``W`` layer never looks past its window and rotates
    # plainly at ``rope_theta`` (``layer_rotation``)
    rope_scaling_factor: float = 1.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # the residual path of the described block (docs/xing4.md): hc_mult > 1
    # keeps that many copies of the residual stream a token, X [hc_mult,
    # hidden], and every sub-block mixes them by maps computed from the
    # stream itself (manifold-constrained hyper-connections): a read-in
    # h_pre in (0, 1), a write-back h_post in (0, 2) and a doubly stochastic
    # H_res from hc_sinkhorn_iters rounds of column-then-row normalisation
    # (hc_eps in the denominators) of exp(clip(., -hc_res_clamp,
    # hc_res_clamp)).  0 or 1 = the one stream, x + f(x).  Served only
    # (``ops/hyper_connection.py``): the training forward refuses it by name
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0

    # one sub-block a layer (docs/nemotron_h.md): a character a layer,
    # ``M`` a Mamba-2 mixer, ``*`` grouped-query attention (rotated under
    # ``position: rope``), ``W`` the same over the last ``sliding_window``
    # positions only (docs/mellum2.md: its cache is a RING of pages a row,
    # a second class of pages beside the ``*`` layers' growing ones),
    # ``P`` both of them side by side on the SAME normed
    # input, their results added (docs/falcon_h1.md: such a layer keeps a
    # recurrent state AND pages), ``E`` the dropless expert feed-forward,
    # ``-`` a dense feed-forward (each relu2 or swiglu, as ``mlp_act`` says); every
    # layer is x + mixer(RMSNorm(x)).  "" = the two-sub-block layers above.
    # ``num_layers`` is the pattern's length.
    layer_pattern: str = ""
    # the Mamba-2 mixer: ssm_heads heads of ssm_head_dim (d_inner is their
    # product, not a multiple of hidden_size), a state of ssm_state a head
    # dim, B and C shared by the heads of each of ssm_groups groups, a
    # causal depthwise conv of ssm_conv taps over x, B and C, the prefill's
    # chunk; dt's bias is the inverse softplus of a log-uniform draw in
    # [ssm_dt_min, ssm_dt_max] floored at ssm_dt_floor, A = -U(1, 16)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # out-projections of every mixer drawn at initializer_range /
    # sqrt(num_layers) (the published ``rescale_prenorm_residual``)
    rescale_prenorm_residual: bool = False
    # the constants a muP-parameterised checkpoint multiplies activations by
    # (a ``layer_pattern`` block; names and places: ``MUP_NAMES``,
    # docs/falcon_h1.md), as a mapping name -> number or list of numbers.
    # The serving programs carry NONE of them: each multiplies a linear map's
    # input or output, so ``models/gpt/convert.py`` ``fold_mup`` multiplies
    # them into the weights once, in float32, where the served tree is made.
    mup_multipliers: Any = ()

    def __post_init__(self):
        if self.ffn_hidden_size is None:
            object.__setattr__(self, "ffn_hidden_size", 4 * self.hidden_size)
        for field in ("norm_eps", "rope_theta", "moe_route_scale", "moe_bias_update_rate",
                      "moe_bias_warm_start_rate", "rope_scaling_factor", "rope_beta_fast",
                      "rope_beta_slow", "rope_mscale", "rope_mscale_all_dim",
                      "ssm_dt_min", "ssm_dt_max", "ssm_dt_floor", "hc_eps", "hc_res_clamp"):
            # YAML reads "1e-05" (an override's spelling of a float) as a string
            object.__setattr__(self, field, float(getattr(self, field)))
        if not self.attn_head_dim and self.hidden_size % self.num_attention_heads:
            raise ValueError("num_attention_heads must divide hidden_size")
        if self.num_attention_heads % (self.num_kv_heads or self.num_attention_heads):
            raise ValueError("num_kv_heads must divide num_attention_heads")
        self._read_mup_multipliers()
        if self.layer_pattern:
            self._check_layer_pattern()
        elif self.position == "none" or self.mlp_act == "relu2":
            raise ValueError("position: none and mlp_act: relu2 belong to a layer_pattern block")
        if not self.classic_block:
            described = ("rmsnorm", "rope", False, "swiglu", False)
            words = (described,) + (
                (("rmsnorm", "none", False, "relu2", False),) if self.layer_pattern else ())
            if (self.norm, self.position, self.use_bias, self.mlp_act,
                    self.tie_embeddings) not in words:
                raise ValueError(
                    "a block other than the GPT-2 one is norm: rmsnorm, position: rope, "
                    "use_bias: False, mlp_act: swiglu, tie_embeddings: False together "
                    "(with a layer_pattern: those, or position: none, mlp_act: relu2)")
            if self.hidden_dropout_prob or self.attention_probs_dropout_prob:
                raise ValueError("only the GPT-2 block has dropout; set both "
                                 "dropout probabilities to 0")
        if self.hyper_connections:
            if self.classic_block or self.layer_pattern:
                raise ValueError(
                    "hc_mult (a residual stream of several copies) belongs to the described "
                    "block with two sub-blocks a layer: neither the GPT-2 block nor a "
                    "layer_pattern block has a forward that holds it")
            if self.post_norms:
                raise ValueError("hc_mult does not take post_norms: no reference holds them")
            if self.hc_sinkhorn_iters < 1 or not self.hc_eps > 0 or not self.hc_res_clamp > 0:
                raise ValueError("hc_mult needs hc_sinkhorn_iters >= 1, hc_eps > 0 and "
                                 "hc_res_clamp > 0")
        if self.moe_bias_warm_start_steps and not (
                self.moe_gate == "sigmoid" and self.moe_dropless
                and self.moe_bias_warm_start_rate >= self.moe_bias_update_rate > 0):
            raise ValueError("moe_bias_warm_start_steps needs moe_gate: sigmoid and "
                             "moe_bias_warm_start_rate >= moe_bias_update_rate > 0")
        if self.latent_attention:
            if not (self.q_lora_rank and self.qk_nope_head_dim
                    and self.qk_rope_head_dim and self.v_head_dim):
                raise ValueError(
                    "kv_lora_rank (latent attention) needs the described block and "
                    "q_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim")
            if self.qk_rope_head_dim % 2:
                raise ValueError("qk_rope_head_dim must be even (pairs are rotated)")
            for option in ("num_kv_heads", "attn_head_dim", "qk_norm", "attn_gate",
                           "sliding_window", "global_attn_every"):
                if getattr(self, option):
                    raise ValueError(f"latent attention (kv_lora_rank) does not take {option}")
        if self.moe_n_group > 1 and not (
                self.moe_gate == "sigmoid" and self.moe_dropless
                and self.num_experts % self.moe_n_group == 0
                and 1 <= self.moe_topk_group <= self.moe_n_group
                and self.moe_top_k <= self.moe_topk_group * (self.num_experts // self.moe_n_group)
                and self.num_experts // self.moe_n_group >= 2):
            raise ValueError(
                "moe_n_group needs moe_gate: sigmoid, groups of equal size >= 2, "
                "moe_topk_group within 1..moe_n_group and moe_top_k experts inside them")
        if self.moe_dropless:
            last = self.moe_expert_offset + self.experts_held - 1
            if not 0 <= self.moe_expert_offset <= last < self.num_experts:
                raise ValueError(
                    f"experts {self.moe_expert_offset}..{last} held of {self.num_experts}")
        elif not self.classic_block and self.num_experts > 1:
            raise ValueError("the capacity-factor MoE layer serves the GPT-2 block only; "
                             "set moe_gate: sigmoid (or softmax) for the dropless layer")
        if self.recompute_granularity not in ("full", "selective", "full_attn", "core_attn"):
            raise ValueError(f"bad recompute_granularity {self.recompute_granularity}")

    def _read_mup_multipliers(self) -> None:
        """``mup_multipliers`` as a sorted tuple of (name, float or tuple of
        floats): hashable, as a jit's static argument has to be."""
        raw = self.mup_multipliers
        items = dict(raw).items() if raw else ()
        read = []
        for name, value in sorted(items):
            width = MUP_NAMES.get(name)
            if width is None:
                raise ValueError(f"mup_multipliers: unknown constant {name!r}; "
                                 f"known: {sorted(MUP_NAMES)}")
            if width:
                value = tuple(float(v) for v in value)
                if len(value) != width:
                    raise ValueError(f"mup_multipliers: {name} takes {width} numbers")
            else:
                value = float(value)
            read.append((name, value))
        object.__setattr__(self, "mup_multipliers", tuple(read))
        if read and not self.layer_pattern:
            raise ValueError("mup_multipliers belong to a layer_pattern block "
                             "(the fold is written for its tree)")

    def _check_layer_pattern(self) -> None:
        pattern = self.layer_pattern
        if set(pattern) - set("MP*WE-") or len(pattern) != self.num_layers:
            raise ValueError(
                f"layer_pattern {pattern!r}: one of M (Mamba-2), * (attention), W (attention "
                f"over a window), P (both, side by side), E (experts), - (dense) for each of "
                f"the {self.num_layers} layers")
        if "E" in pattern and self.mlp_act != "relu2" and set(pattern) - set("*WE"):
            raise ValueError("SwiGLU experts (E) are held to a reference beside attention "
                             "layers (* and W) alone; beside M, P or - layers a layer_pattern "
                             "block has relu2 experts (mlp_act: relu2)")
        if ("W" in pattern) != (self.sliding_window > 0):
            raise ValueError("a W layer needs sliding_window, and sliding_window a W layer: "
                             "the window belongs to the pattern's W layers")
        if "W" in pattern and (self.ssm_layers or self.position != "rope"):
            raise ValueError("W layers beside state-space layers (M, P), or without "
                             "position: rope, are not written: no reference holds them yet")
        if self.ssm_layers:
            if not (self.ssm_heads and self.ssm_head_dim and self.ssm_state
                    and self.ssm_conv >= 2 and self.ssm_chunk >= 1):
                raise ValueError("an M or P layer needs ssm_heads, ssm_head_dim, ssm_state, "
                                 "ssm_conv >= 2 and ssm_chunk")
            if self.ssm_heads % self.ssm_groups or (
                    self.ssm_heads * self.ssm_head_dim) % self.ssm_groups:
                raise ValueError("ssm_groups must divide ssm_heads")
        if "E" in pattern and not self.moe_dropless:
            raise ValueError("an E layer needs num_experts and moe_gate: sigmoid or softmax")
        for option in ("qk_norm", "attn_gate", "post_norms", "global_attn_every",
                       "num_dense_layers", "kv_lora_rank",
                       "embed_scale_sqrt_hidden"):
            if getattr(self, option):
                raise ValueError(f"a layer_pattern block does not take {option}")

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_attention_heads

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def hyper_connections(self) -> bool:
        """True where the residual stream is several copies mixed by maps."""
        return self.hc_mult > 1

    @property
    def hc_maps(self) -> int:
        """Numbers a sub-block's maps take a token: h_pre, h_post, H_res."""
        return self.hc_mult * (2 + self.hc_mult)

    @property
    def cached_token(self) -> Tuple[Tuple[int, int], ...]:
        """What a cache holds of one token in one layer THAT CACHES TOKENS
        (:attr:`kv_layers` of them): a (heads, width) pair for each pool.
        Per-head keys and values are two pools; the latent and its rotated
        key are one, of one "head"."""
        if self.latent_attention:
            return ((1, self.kv_lora_rank + self.qk_rope_head_dim),)
        return ((self.kv_heads, self.head_dim),) * 2

    @property
    def kv_layers(self) -> int:
        """Layers whose cache is pages of tokens that GROW with the row:
        all of them, or a layer_pattern's full attention layers (``*`` and
        ``P``).  The pools' leading axis: attention layer number ``a`` of the
        pattern is ``pools.k[a]``, NOT the layer's place in the stack."""
        if not self.layer_pattern:
            return self.num_layers
        return self.layer_pattern.count("*") + self.layer_pattern.count("P")

    @property
    def window_layers(self) -> int:
        """A layer_pattern's ``W`` layers: each keeps, a row, a RING of
        :meth:`ring_pages` pages whatever the row's length, the second class
        of pages of one arena (``pools.wk[w]``, ``w`` counting the ``W``
        layers alone).  0 for every other block."""
        return self.layer_pattern.count("W")

    def ring_pages(self, block: int) -> int:
        """Pages of ``block`` tokens a row's ring holds in a ``W`` layer: the
        ``sliding_window`` positions a query sees lie in at most this many
        consecutive pages (a window of 1,024 on pages of 128: 9; token t
        lives in ring slot ``(t // block) % ring_pages``).  0 without ``W``
        layers."""
        if not self.window_layers:
            return 0
        return -(-self.sliding_window // int(block)) + 1

    @property
    def ssm_layers(self) -> int:
        """Layers that keep a recurrent state a row (``M`` and ``P``): the
        leading axis of ``pools.ssm`` / ``.conv``.  A ``P`` layer counts here
        AND in :attr:`kv_layers`: the two sets of layers overlap."""
        return self.layer_pattern.count("M") + self.layer_pattern.count("P")

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Width of what the conv sees: x, then B and C of every group."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def row_state(self) -> Tuple[Tuple[str, Tuple[int, ...], str], ...]:
        """What a ROW keeps beside its pages, whatever its length, in each
        of the :attr:`ssm_layers` state-space layers: (name, shape, dtype)
        of the recurrent state ``S`` [heads, head_dim, state] and of the
        last ``ssm_conv - 1`` columns the conv saw.  The state is float32
        whatever ``dtype`` is (rounded to bfloat16 at every step it loses
        what a slowly decaying head adds; docs/nemotron_h.md), the columns
        are activations.  () for a block whose every layer caches tokens."""
        if not self.ssm_layers:
            return ()
        return (("ssm", (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                 "float32"),
                ("conv", (self.ssm_conv - 1, self.ssm_conv_dim), self.dtype))

    @property
    def kv_block_default(self) -> int:
        """Tokens a page of the paged arena holds unless a caller passes its
        own ``block`` (0 = the library's default): a latent page holds one
        vector a token, so 128 of them make the page of a DMA's size that
        16 tokens of per-head keys make; so do 128 tokens of a few shared
        KV heads (4 or more query heads a KV head: 32/2 and 20/4 both)."""
        if self.latent_attention or (self.layer_pattern and self.kv_heads * 4 <=
                                     self.num_attention_heads):
            return 128
        return 0

    @property
    def mup(self) -> Dict[str, Any]:
        """``mup_multipliers`` by name ({} without them)."""
        return dict(self.mup_multipliers)

    @property
    def rope_yarn_m(self) -> float:
        """YaRN's attention factor m (1 without scaling)."""
        if self.rope_scaling_factor <= 1.0:
            return 1.0
        import math

        return 0.1 * self.rope_mscale_all_dim * math.log(self.rope_scaling_factor) + 1.0

    @property
    def moe_dropless(self) -> bool:
        return self.num_experts > 1 and self.moe_gate in ("sigmoid", "softmax")

    @property
    def experts_held(self) -> int:
        return self.moe_experts_held or self.num_experts

    @property
    def leading_dense_layers(self) -> int:
        """Layers before the first expert layer (0 without expert layers)."""
        return self.num_dense_layers if self.moe_dropless else 0

    @property
    def sorted_pair_products(self) -> int:
        """Grouped products one pass over sorted pairs dispatches (a serving
        prefill's ``pfx_grouped_matmul`` calls): expert layers x the
        matrices of an expert, two under ``mlp_act: relu2``, else three."""
        if not self.moe_dropless:
            return 0
        layers = (self.layer_pattern.count("E") if self.layer_pattern
                  else self.num_layers - self.leading_dense_layers)
        return layers * (2 if self.mlp_act == "relu2" else 3)

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
                and not self.moe_dropless and not self.kv_lora_rank
                and not self.layer_pattern)

    def layer_kind(self, layer: int) -> Tuple[int, bool]:
        """(window or 0, rotate q and k) of layer ``layer``, counted from 0
        over the whole stack, leading dense layers included."""
        is_global = self.global_attn_every > 0 and (layer + 1) % self.global_attn_every == 0
        if self.layer_pattern:
            # the pattern names the kinds: W has the window, and EVERY
            # attention layer rotates (how: ``layer_rotation``)
            kind = self.layer_pattern[layer]
            if kind not in "*PW":
                raise ValueError(f"layer {layer} of {self.layer_pattern!r} is no attention layer")
            return (self.sliding_window if kind == "W" else 0, self.position == "rope")
        return (0 if is_global else self.sliding_window,
                self.position == "rope" and not is_global)

    def layer_rotation(self, kind: str) -> Tuple[bool, float]:
        """How a layer_pattern's attention layer of ``kind`` rotates q and k
        under ``position: rope``: (YaRN's blended frequencies or the plain
        ones, the factor on cos and sin).  A ``W`` layer never sees past its
        window: plain, 1.  A layer that sees the whole context (``*``, ``P``)
        takes YaRN where ``rope_scaling_factor`` > 1, and its cos and sin
        carry ``rope_yarn_m``, so q and the CACHED keys both do and the
        scores gain its square."""
        scaled = kind != "W" and self.rope_scaling_factor > 1.0
        return scaled, (self.rope_yarn_m if scaled else 1.0)

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
