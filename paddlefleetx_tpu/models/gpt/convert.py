"""HF GPT-2 checkpoint -> native param tree.

The reference ships pretrained-weight download/convert tooling
(utils/download.py + per-model checkpoint loaders); the TPU-native
equivalent imports the ubiquitous HuggingFace GPT-2 format, so a user
switching frameworks can bring standard weights.  Mapping notes:

- HF ``Conv1D`` weights are already [in, out] — no transpose needed.
- ``c_attn`` packs q|k|v along the output dim: [h, 3h] reshapes to
  [h, 3, nh, hd], matching the fused qkv einsum ``bsh,htnd->bstnd``.
- activations (gelu tanh-approx) and LN eps (1e-5) already agree.
- the LM head is tied to the word embedding in both implementations.

Also here, because it is a checkpoint's conversion too: :func:`fold_mup`, which
multiplies a muP-parameterised checkpoint's constants (``GPTConfig.mup_multipliers``)
into the weights of a ``layer_pattern`` tree.  It is the ONE place those
constants live in the program (docs/falcon_h1.md).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from paddlefleetx_tpu.models.gpt.config import GPTConfig


def hf_gpt2_config(hf_cfg, **overrides) -> GPTConfig:
    """GPTConfig from a transformers GPT2Config.

    Raises on variants the native model hardcodes differently — a silent
    convert would produce wrong logits with no error anywhere downstream.
    """
    act = getattr(hf_cfg, "activation_function", "gelu_new")
    if act != "gelu_new":
        raise ValueError(f"unsupported activation_function {act!r} (need gelu_new)")
    eps = float(getattr(hf_cfg, "layer_norm_epsilon", 1e-5))
    if abs(eps - 1e-5) > 1e-12:
        raise ValueError(f"unsupported layer_norm_epsilon {eps} (model hardcodes 1e-5)")
    n_inner = getattr(hf_cfg, "n_inner", None)
    if n_inner is not None and int(n_inner) != 4 * int(hf_cfg.n_embd):
        raise ValueError(f"unsupported n_inner {n_inner} (need 4*n_embd)")
    if getattr(hf_cfg, "scale_attn_by_inverse_layer_idx", False):
        raise ValueError("scale_attn_by_inverse_layer_idx not supported")
    if getattr(hf_cfg, "reorder_and_upcast_attn", False):
        raise ValueError("reorder_and_upcast_attn not supported")
    kw = dict(
        vocab_size=int(hf_cfg.vocab_size),
        hidden_size=int(hf_cfg.n_embd),
        num_layers=int(hf_cfg.n_layer),
        num_attention_heads=int(hf_cfg.n_head),
        max_position_embeddings=int(hf_cfg.n_positions),
    )
    kw.update(overrides)
    return GPTConfig(**kw)


def convert_hf_gpt2_state_dict(
    sd: Dict[str, "np.ndarray"], cfg: GPTConfig, pad_vocab_to: Optional[int] = None
) -> Dict:
    """torch/HF ``GPT2LMHeadModel.state_dict()`` -> stacked param tree.

    ``sd`` values may be torch tensors or numpy arrays.  ``pad_vocab_to``
    grows the embedding with zero rows (MXU-friendly multiples of 128); the
    model config must then use the padded vocab_size.
    """

    from paddlefleetx_tpu.models.convert_common import make_getter, make_stacker

    get = make_getter(sd)

    h, L = cfg.hidden_size, cfg.num_layers
    nh, hd = cfg.num_attention_heads, cfg.head_dim

    word = get("transformer.wte.weight").astype(np.float32)
    if pad_vocab_to is not None:
        if pad_vocab_to < word.shape[0]:
            raise ValueError(f"pad_vocab_to {pad_vocab_to} < vocab {word.shape[0]}")
        pad = np.zeros((pad_vocab_to - word.shape[0], h), np.float32)
        word = np.concatenate([word, pad], axis=0)
    if word.shape[0] != cfg.vocab_size:
        raise ValueError(
            f"config vocab_size {cfg.vocab_size} != embedding rows {word.shape[0]}"
        )

    stack = make_stacker(get, L)

    params = {
        "embeddings": {
            "word": word,
            "position": get("transformer.wpe.weight").astype(np.float32),
        },
        "layers": {
            "ln_1": {
                "scale": stack("transformer.h.{i}.ln_1.weight"),
                "bias": stack("transformer.h.{i}.ln_1.bias"),
            },
            "attn": {
                "qkv_kernel": stack("transformer.h.{i}.attn.c_attn.weight", (h, 3, nh, hd)),
                "qkv_bias": stack("transformer.h.{i}.attn.c_attn.bias", (3, nh, hd)),
                "out_kernel": stack("transformer.h.{i}.attn.c_proj.weight", (nh, hd, h)),
                "out_bias": stack("transformer.h.{i}.attn.c_proj.bias"),
            },
            "ln_2": {
                "scale": stack("transformer.h.{i}.ln_2.weight"),
                "bias": stack("transformer.h.{i}.ln_2.bias"),
            },
            "mlp": {
                "fc_in_kernel": stack("transformer.h.{i}.mlp.c_fc.weight"),
                "fc_in_bias": stack("transformer.h.{i}.mlp.c_fc.bias"),
                "fc_out_kernel": stack("transformer.h.{i}.mlp.c_proj.weight"),
                "fc_out_bias": stack("transformer.h.{i}.mlp.c_proj.bias"),
            },
        },
        "final_ln": {
            "scale": get("transformer.ln_f.weight").astype(np.float32),
            "bias": get("transformer.ln_f.bias").astype(np.float32),
        },
    }
    return params


def mup_scale(group: str, name: str, cfg: GPTConfig):
    """What the muP constants multiply leaf ``name`` of parameter group
    ``group`` by: a float, or for the mixer's in-projection a vector over
    its columns.  Each constant of the published forward (``model_type:
    falcon_h1``) multiplies the INPUT or the OUTPUT of one linear map, so it
    is that map's own scale:

        x0 = embedding_multiplier E[token]                     embeddings.word
        Mixer(ssm_in_multiplier h):  (W_in u) * ssm_multipliers by segment
              [z | x | B | C | dt], BEFORE the conv            ssm.in_kernel's columns
        ssm_out_multiplier Mixer(..)                           ssm.out_kernel
        Attn(attention_in_multiplier h)                        attn.q/k/v_kernel
        k = key_multiplier W_k u                               attn.k_kernel
        attention_out_multiplier Attn(..)                      attn.out_kernel
        silu(mlp_multipliers[0] W_gate m) * W_up m             mlp.w1
        mlp_multipliers[1] W_down (..)                         mlp.w2
        logits = lm_head_multiplier W_head x                   head.kernel

    A norm stands between the residual stream and every one of these maps,
    and the conv, dt's softplus and the recurrence come AFTER the scaled
    in-projection, so no constant crosses a non-linearity on its way into a
    weight."""
    m = cfg.mup

    def one(key: str) -> float:
        return float(m.get(key, 1.0))

    if (group, name) == ("embeddings", "word"):
        return one("embedding_multiplier")
    if (group, name) == ("head", "kernel"):
        return one("lm_head_multiplier")
    if group == "ssm" and name == "in_kernel":
        gn = cfg.ssm_groups * cfg.ssm_state
        widths = (cfg.ssm_inner, cfg.ssm_inner, gn, gn, cfg.ssm_heads)
        segments = m.get("ssm_multipliers", (1.0,) * 5)
        return one("ssm_in_multiplier") * np.concatenate(
            [np.full((w,), c, np.float32) for w, c in zip(widths, segments)])
    if group == "ssm" and name == "out_kernel":
        return one("ssm_out_multiplier")
    if group == "attn" and name in ("q_kernel", "k_kernel", "v_kernel"):
        return one("attention_in_multiplier") * (one("key_multiplier") if name == "k_kernel" else 1.0)
    if group == "attn" and name == "out_kernel":
        return one("attention_out_multiplier")
    if group == "mlp" and name in ("w1", "w2"):
        return float(m.get("mlp_multipliers", (1.0, 1.0))[name == "w2"])
    return 1.0


def fold_mup_leaf(path, x, cfg: GPTConfig):
    """Leaf ``x`` at tree path ``path`` (``jax.tree_util`` keys) times its
    :func:`mup_scale`, in float32, back in its own dtype; ``x`` itself where
    nothing multiplies it."""
    if not cfg.mup_multipliers:
        return x
    group, name = (getattr(k, "key", None) for k in path[-2:])
    scale = mup_scale(group, name, cfg)
    if isinstance(scale, float) and scale == 1.0:
        return x
    import jax.numpy as jnp

    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def fold_mup(params: Dict, cfg: GPTConfig) -> Dict:
    """A ``layer_pattern`` tree as a checkpoint holds it (``model.init``'s
    tree) -> the tree as it is SERVED, every muP constant inside the matrix it
    scales.  Once: a folded tree folded again is another model.
    ``generation.init_serving_params`` folds each seeded leaf as it makes it;
    ``generation.serving_params`` only casts, and takes a folded tree."""
    import jax

    return jax.tree_util.tree_map_with_path(lambda p, x: fold_mup_leaf(p, x, cfg), params)
