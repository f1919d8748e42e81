"""Parameters, bytes and required FLOPs of the Falcon-H1 block (configuration
falcon-h1-34b), from the sizes in the configuration file's ``model`` group: the
benchmark's own arithmetic, like ``math/nemotron_h.py`` for the Nemotron-H
block.  2 FLOPs a multiply-add; lookups, norms, the conv, the rotation and
elementwise work not counted unless a function says so.  A published layer is
two of the ``model`` group's ``layer_pattern`` (``P`` the parallel mixers, ``-``
the MLP).

    python3 pfx_bench/math/falcon_h1.py      # self-check against PERF.md's numbers
"""


def _kinds(m):
    pattern = m["layer_pattern"]
    return {k: pattern.count(k) for k in "P-"}


def _ssm(m):
    inner = m["ssm_heads"] * m["ssm_head_dim"]
    return inner, inner + 2 * m["ssm_groups"] * m["ssm_state"]


def layer_params(m: dict) -> dict:
    """Parameters of one PUBLISHED layer by part: the three groups of
    matrices, and ``small``, everything else (the conv's kernel and bias,
    dt's bias, A, D, the gated norm's scale, the two RMSNorm scales)."""
    h = m["hidden_size"]
    inner, conv_dim = _ssm(m)
    n, kv, d = m["num_attention_heads"], m["num_kv_heads"], m["attn_head_dim"]
    return {
        "mamba": h * (inner + conv_dim + m["ssm_heads"]) + inner * h,
        "attention": 2 * h * n * d + 2 * h * kv * d,
        "mlp": 3 * h * m["ffn_hidden_size"],
        "small": (m["ssm_conv"] + 1) * conv_dim + 3 * m["ssm_heads"] + inner + 2 * h,
    }


def param_count(m: dict, layers: int = None) -> int:
    """Parameters of ``layers`` published layers (default: those held here,
    the ``P`` count), the embedding, the untied head and the final norm."""
    layers = _kinds(m)["P"] if layers is None else layers
    h = m["hidden_size"]
    return layers * sum(layer_params(m).values()) + 2 * m["vocab_size"] * h + h


def weight_bytes(m: dict, bytes_per_weight: int = 2) -> int:
    """What the server holds: every matrix in the compute dtype, the small
    leaves float32."""
    p, layers, h = layer_params(m), _kinds(m)["P"], m["hidden_size"]
    matrices = layers * (p["mamba"] + p["attention"] + p["mlp"]) + 2 * m["vocab_size"] * h
    # the conv's kernel is cast with the matrices
    conv = layers * m["ssm_conv"] * _ssm(m)[1]
    return (matrices + conv) * bytes_per_weight + (layers * p["small"] - conv + h) * 4


def state_bytes_per_row(m: dict, state_bytes: int = 4, conv_bytes: int = 2) -> int:
    """What a row keeps beside its pages, whatever its length: the recurrent
    state and the conv's last columns of every ``P`` layer."""
    inner, conv_dim = _ssm(m)
    return _kinds(m)["P"] * (inner * m["ssm_state"] * state_bytes
                             + (m["ssm_conv"] - 1) * conv_dim * conv_bytes)


def cached_token_bytes(m: dict, bytes_per_value: int = 2) -> int:
    """One cached token over all ``P`` layers: K and V of the KV heads."""
    return _kinds(m)["P"] * 2 * m["num_kv_heads"] * m["attn_head_dim"] * bytes_per_value


def ssm_decode_work(m: dict, attended_tokens: float, row_steps: float) -> dict:
    """What ``pfx_ssm_decode`` must do, over all ``P`` layers, for decode
    steps of ``row_steps`` LIVE (row, step) pairs (``attended_tokens`` plays
    no part: the state's cost does not follow the context).  A (row, step)
    and layer: the state read and written once in float32, the vectors in
    (dt x, exp(dt A), D x over the (head, head_dim) pairs; B and C of every
    group) and y out, float32; 5 FLOPs an element of the state."""
    del attended_tokens
    inner, _ = _ssm(m)
    state = inner * m["ssm_state"]
    vectors = 4 * inner + 2 * m["ssm_groups"] * m["ssm_state"]
    layers = _kinds(m)["P"]
    return {
        "flops": layers * row_steps * 5 * state,
        "bytes": layers * row_steps * (2 * state * 4 + vectors * 4),
    }


def gqa_decode_work(m: dict, attended_tokens: float, row_steps: float) -> dict:
    """What ``pfx_decode_paged`` must do, over all ``P`` layers, for decode
    steps whose live rows attended ``attended_tokens`` cached tokens in all
    (the scheduler's ``kv_tokens``) in ``row_steps`` (row, step) pairs.  A
    cached token and layer: its keys and values read ONCE (the query heads of
    a KV head share them), 2 x heads x head_dim multiply-adds.  A (row, step)
    and layer: the queries read and the result written, 2 bytes a value.
    Nothing a padded group, a spare page or a dead slot adds is counted: they
    lower the share."""
    n, d = m["num_attention_heads"], m["attn_head_dim"]
    layers = _kinds(m)["P"]
    return {
        "flops": layers * attended_tokens * n * d * 2 * 2,
        "bytes": attended_tokens * cached_token_bytes(m) + layers * row_steps * 2 * n * d * 2,
    }


def roofline_seconds(work: dict, peaks: dict) -> float:
    """The state update does 0.6 FLOPs a byte and the attention 10 (5 query
    heads a KV head): the HBM bounds both.  The FLOP term is kept so that the
    function reads as the other configurations' do."""
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["bf16_flops_per_s"])


def decode_step_bytes(m: dict, live_rows: int, cached_tokens: int = 0) -> dict:
    """Bytes a decode step reads and writes: the weights (the embedding's
    rows are looked up, not streamed; the head is streamed whole), the state
    of every LIVE row read and written, the cached tokens read."""
    weights = weight_bytes(m) - m["vocab_size"] * m["hidden_size"] * 2
    return {"weights": weights,
            "states": 2 * live_rows * state_bytes_per_row(m, conv_bytes=0),
            "kv": cached_tokens * cached_token_bytes(m)}


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Required FLOPs of one prompt's prefill: every matrix at every token,
    the head at the last token only, attention at the causal half; the
    chunked scan's own products are not counted."""
    p, layers = layer_params(m), _kinds(m)["P"]
    n, d = m["num_attention_heads"], m["attn_head_dim"]
    matrices = layers * (p["mamba"] + p["attention"] + p["mlp"])
    return (2.0 * prompt_len * matrices + 2.0 * m["vocab_size"] * m["hidden_size"]
            + layers * 2.0 * n * d * prompt_len * prompt_len)


if __name__ == "__main__":
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "falcon-h1-34b.json")) as f:
        model = json.load(f)["model"]
    parts = layer_params(model)
    print({k: round(v / 1e6, 3) for k, v in parts.items()})
    assert parts["mamba"] == 68_321_280 and parts["attention"] == 31_457_280
    assert parts["mlp"] == 330_301_440 and sum(parts.values()) == 430_120_032
    print("uncut:", param_count(model, 72), "held:", param_count(model), "bytes", weight_bytes(model))
    assert abs(param_count(model, 72) / 1e9 - 33.64) < 0.005
    assert abs(weight_bytes(model) / 1e9 - 10.51) < 0.005
    assert state_bytes_per_row(model) == 25_350_144 and cached_token_bytes(model) == 12_288
    work = ssm_decode_work(model, 0.0, 1.0)
    assert work["bytes"] / 6 == 2 * 4_194_304 + 4 * (4 * 4096 + 1024)
    print("decode step, 24 live rows at 400 tokens:", decode_step_bytes(model, 24, 24 * 400))
    print("a 256-token prefill:", prefill_flops(model, 256) / 1e12, "TFLOP")
