"""Parameters held, bytes and required FLOPs of the DeepSeek-V3 block
(configuration deepseek-v3), from the sizes in the configuration file's
``model`` group: the benchmark's own arithmetic, like ``math/afmoe.py`` for
the AFMoE block.  2 FLOPs a multiply-add; lookups, norms, rotations and
elementwise work not counted.

    python3 pfx_bench/math/deepseek_v3.py      # self-check against PERF.md's table
"""


def _mla_params(m):
    h, n = m["hidden_size"], m["num_attention_heads"]
    ql, kl = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rot, v = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (h * ql + ql * n * (nope + rot) + h * (kl + rot) + kl * n * (nope + v) + n * v * h)


def layer_params(m: dict) -> dict:
    """Matrix parameters of one layer by part (norm scales and the routing
    bias left out: 0.01 M a layer)."""
    h, f = m["hidden_size"], m["moe_ffn_hidden_size"]
    return {
        "mla": _mla_params(m),
        "dense_mlp": 3 * h * m["ffn_hidden_size"],
        "expert": 3 * h * f,
        "shared": 3 * h * f * m["moe_shared_experts"],
        "router": h * m["num_experts"],
    }


def param_count(m: dict) -> int:
    """Matrix parameters held on this chip."""
    p, n_dense = layer_params(m), m["num_dense_layers"]
    dense = p["mla"] + p["dense_mlp"]
    expert = p["mla"] + p["shared"] + p["router"] + m["moe_experts_held"] * p["expert"]
    return (n_dense * dense + (m["num_layers"] - n_dense) * expert
            + 2 * m["vocab_size"] * m["hidden_size"])


def weight_bytes(m: dict, bytes_per_weight: int = 2) -> int:
    """What the server holds: every matrix in the compute dtype, but the
    routers' kernels, which stay float32."""
    router = (m["num_layers"] - m["num_dense_layers"]) * layer_params(m)["router"]
    return (param_count(m) - router) * bytes_per_weight + router * 4


def cached_token_bytes(m: dict, bytes_per_value: int = 2) -> int:
    """One cached token in one layer: the latent and the rotated key."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * bytes_per_value


def mla_decode_work(m: dict, attended_tokens: float, row_steps: float) -> dict:
    """What ``pfx_decode_mla_paged`` must do, over all layers, for decode
    steps whose live rows attended ``attended_tokens`` cached tokens in all
    (the scheduler's ``kv_tokens``) in ``row_steps`` (row, step) pairs.  A
    cached token and layer: the latent read once (all heads share it),
    heads x (w + kv_lora) multiply-adds.  A (row, step) and layer: the
    absorbed queries read (bf16) and the result written (float32)."""
    n, kl = m["num_attention_heads"], m["kv_lora_rank"]
    w = kl + m["qk_rope_head_dim"]
    layers = m["num_layers"]
    return {
        "flops": layers * attended_tokens * n * (w + kl) * 2,
        "bytes": layers * (attended_tokens * cached_token_bytes(m)
                           + row_steps * (n * w * 2 + n * kl * 4)),
    }


def roofline_seconds(work: dict, peaks: dict) -> float:
    """The kernel sits at the ridge (242 FLOPs a byte against the v5e's
    240), so both terms are kept: the larger one bounds it."""
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["bf16_flops_per_s"])


def prefill_flops(m: dict, prompt_len: int, held_pairs: float = None) -> float:
    """Required FLOPs of one prompt's prefill: every matrix at every token
    (the routed experts at the pairs on held experts; by default the
    expected top_k x held / experts a token), expanded attention at the
    causal half, the head at the last token only."""
    p, n_dense = layer_params(m), m["num_dense_layers"]
    n_exp = m["num_layers"] - n_dense
    if held_pairs is None:
        held_pairs = prompt_len * m["moe_top_k"] * m["moe_experts_held"] / m["num_experts"]
    per_token = (m["num_layers"] * p["mla"] + n_dense * p["dense_mlp"]
                 + n_exp * (p["shared"] + p["router"]))
    pairs = prompt_len * (prompt_len + 1) // 2
    d_qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attention = m["num_layers"] * m["num_attention_heads"] * pairs * (d_qk + m["v_head_dim"])
    return 2.0 * (prompt_len * per_token + n_exp * held_pairs * p["expert"] + attention
                  + m["vocab_size"] * m["hidden_size"])


def decode_step_weight_bytes(m: dict) -> int:
    """Weight bytes a decode step reads when every held expert has a row:
    the whole tree but the embedding's rows it does not look up."""
    return weight_bytes(m) - m["vocab_size"] * m["hidden_size"] * 2


if __name__ == "__main__":
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "deepseek-v3.json")) as f:
        model = json.load(f)["model"]
    parts = layer_params(model)
    print({k: round(v / 1e6, 1) for k, v in parts.items()})
    print("parameters held", param_count(model), "bytes", weight_bytes(model))
    assert abs(parts["mla"] / 1e6 - 187.1) < 0.1 and abs(param_count(model) / 1e9 - 4.33) < 0.01
    work = mla_decode_work(model, 1.0, 0.0)
    print("a cached token and layer:", work["flops"] / 7, "FLOPs,", work["bytes"] / 7, "bytes")
    assert work["flops"] / 7 == 278528 and work["bytes"] / 7 == 1152
    print("prefill of 2048 tokens: %.2f TFLOP" % (prefill_flops(model, 2048) / 1e12))
