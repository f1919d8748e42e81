"""Parameters held, bytes and required FLOPs of the Nemotron-H block
(configuration nemotron-3-nano), from the sizes in the configuration file's
``model`` group: the benchmark's own arithmetic, like ``math/deepseek_v3.py``
for the DeepSeek-V3 block.  2 FLOPs a multiply-add; lookups, norms, the conv
and elementwise work not counted unless a function says so.

    python3 pfx_bench/math/nemotron_h.py      # self-check against PERF.md's table
"""


def _kinds(m):
    pattern = m["layer_pattern"]
    return {k: pattern.count(k) for k in "M*E-"}


def layer_params(m: dict) -> dict:
    """Matrix parameters of one layer by part (norm scales, the conv, dt's
    bias, A, D and the routing bias left out: 0.04 M a Mamba layer)."""
    h, f = m["hidden_size"], m["moe_ffn_hidden_size"]
    inner = m["ssm_heads"] * m["ssm_head_dim"]
    conv_dim = inner + 2 * m["ssm_groups"] * m["ssm_state"]
    n, kv, d = m["num_attention_heads"], m["num_kv_heads"], m["attn_head_dim"]
    return {
        "mamba": h * (inner + conv_dim + m["ssm_heads"]) + inner * h,
        "attention": 2 * h * n * d + 2 * h * kv * d,
        "expert": 2 * h * f,
        "shared": 2 * h * f * m["moe_shared_experts"],
        "router": h * m["num_experts"],
        "dense_mlp": 2 * h * m["ffn_hidden_size"],
    }


def param_count(m: dict) -> int:
    """Matrix parameters held on this chip."""
    p, k = layer_params(m), _kinds(m)
    expert = p["shared"] + p["router"] + m["moe_experts_held"] * p["expert"]
    return (k["M"] * p["mamba"] + k["*"] * p["attention"] + k["E"] * expert
            + k["-"] * p["dense_mlp"] + 2 * m["vocab_size"] * m["hidden_size"])


def weight_bytes(m: dict, bytes_per_weight: int = 2) -> int:
    """What the server holds: every matrix in the compute dtype, but the
    routers' kernels, which stay float32."""
    router = _kinds(m)["E"] * layer_params(m)["router"]
    return (param_count(m) - router) * bytes_per_weight + router * 4


def state_bytes_per_row(m: dict, state_bytes: int = 4, conv_bytes: int = 2) -> int:
    """What a row keeps beside its pages, whatever its length: the recurrent
    state and the conv's last columns of every Mamba layer."""
    inner = m["ssm_heads"] * m["ssm_head_dim"]
    conv_dim = inner + 2 * m["ssm_groups"] * m["ssm_state"]
    return _kinds(m)["M"] * (inner * m["ssm_state"] * state_bytes
                             + (m["ssm_conv"] - 1) * conv_dim * conv_bytes)


def cached_token_bytes(m: dict, bytes_per_value: int = 2) -> int:
    """One cached token over all attention layers: K and V of the KV heads."""
    return _kinds(m)["*"] * 2 * m["num_kv_heads"] * m["attn_head_dim"] * bytes_per_value


def ssm_decode_work(m: dict, attended_tokens: float, row_steps: float) -> dict:
    """What ``pfx_ssm_decode`` must do, over all Mamba layers, for decode
    steps of ``row_steps`` LIVE (row, step) pairs (the scheduler's
    ``row_steps``; ``attended_tokens`` is the reader's other counter and
    plays no part: the state's cost does not follow the context).  A (row,
    step) and layer: the state read and written once in float32 (2 x heads
    x head_dim x state x 4 bytes), the vectors in (dt x, exp(dt A), D x over
    the (head, head_dim) pairs; B and C of every group) and y out, float32;
    5 FLOPs an element of the state (decay, outer product and its add, the
    product with C and its sum).  The kernel walks every SLOT, live or not:
    what it does for the others is not counted here, so dead slots lower
    the share."""
    del attended_tokens
    inner = m["ssm_heads"] * m["ssm_head_dim"]
    state = inner * m["ssm_state"]
    vectors = 4 * inner + 2 * m["ssm_groups"] * m["ssm_state"]
    layers = _kinds(m)["M"]
    return {
        "flops": layers * row_steps * 5 * state,
        "bytes": layers * row_steps * (2 * state * 4 + vectors * 4),
    }


def roofline_seconds(work: dict, peaks: dict) -> float:
    """0.6 FLOPs a byte: the HBM bounds it (the FLOP term is kept so that
    the function reads as the other configurations' do)."""
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["bf16_flops_per_s"])


def decode_step_bytes(m: dict, slots: int) -> dict:
    """Bytes a decode step reads and writes: the weights (every held expert
    has a row; the embedding's rows are looked up, not streamed) and the
    states of every slot."""
    weights = weight_bytes(m) - m["vocab_size"] * m["hidden_size"] * 2
    return {"weights": weights,
            "states": 2 * slots * state_bytes_per_row(m, conv_bytes=0)}


if __name__ == "__main__":
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "nemotron-3-nano.json")) as f:
        model = json.load(f)["model"]
    parts = layer_params(model)
    print({k: round(v / 1e6, 2) for k, v in parts.items()})
    print("parameters held", param_count(model), "bytes", weight_bytes(model))
    assert abs(parts["mamba"] / 1e6 - 38.71) < 0.01 and abs(parts["attention"] / 1e6 - 23.40) < 0.01
    assert abs(param_count(model) / 1e9 - 5.258) < 0.001
    assert state_bytes_per_row(model) == 49_082_368 and cached_token_bytes(model) == 6144
    work = ssm_decode_work(model, 0.0, 1.0)
    print("a (row, step):", work["bytes"] / 23, "bytes a layer,", work["flops"] / 23, "FLOPs")
    assert work["bytes"] / 23 == 2 * 2_097_152 + 4 * (4 * 4096 + 2048)
    print("decode step, 48 slots:", decode_step_bytes(model, 48))
