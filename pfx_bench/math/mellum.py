"""Parameters, bytes and required FLOPs of the Mellum 2 block (configuration
mellum2-12b-a2.5b), from the sizes in the configuration file's ``model`` group:
the benchmark's own arithmetic, like ``math/falcon_h1.py`` for the Falcon-H1
block.  2 FLOPs a multiply-add; lookups, norms, the rotation and elementwise
work not counted unless a function says so.  A published layer is two of the
``model`` group's ``layer_pattern`` (``W`` window attention or ``*`` full
attention, then ``E`` the experts).

    python3 pfx_bench/math/mellum.py      # self-check against PERF.md's numbers
"""

PAGE_TOKENS = 128  # GPTConfig.kv_block_default at 8 query heads a KV head


def _kinds(m):
    pattern = m["layer_pattern"]
    return {k: pattern.count(k) for k in "W*E"}


def layer_params(m: dict, experts: int = None) -> dict:
    """Parameters of one PUBLISHED layer by part, with ``experts`` experts
    (default: those held here)."""
    h, f = m["hidden_size"], m["moe_ffn_hidden_size"]
    n, kv, d = m["num_attention_heads"], m["num_kv_heads"], m["attn_head_dim"]
    experts = m["moe_experts_held"] if experts is None else experts
    return {"attention": 2 * h * n * d + 2 * h * kv * d, "router": h * m["num_experts"],
            "experts": experts * 3 * h * f, "norms": 2 * h}


def param_count(m: dict, experts: int = None) -> int:
    """Parameters of every layer, the embedding, the untied head and the
    final norm, with ``experts`` experts a layer (default: those held)."""
    h = m["hidden_size"]
    return (_kinds(m)["E"] * sum(layer_params(m, experts).values())
            + 2 * m["vocab_size"] * h + h)


def weight_bytes(m: dict, bytes_per_weight: int = 2) -> int:
    """What the server holds: every matrix in the compute dtype, the routers
    and the norms' scales float32."""
    p, layers, h = layer_params(m), _kinds(m)["E"], m["hidden_size"]
    matrices = layers * (p["attention"] + p["experts"]) + 2 * m["vocab_size"] * h
    return matrices * bytes_per_weight + (layers * (p["router"] + p["norms"]) + h) * 4


def cached_token_bytes(m: dict, bytes_per_value: int = 2) -> int:
    """One cached token in ONE attention layer: K and V of the KV heads."""
    return 2 * m["num_kv_heads"] * m["attn_head_dim"] * bytes_per_value


def ring_pages(m: dict) -> int:
    return -(-m["sliding_window"] // PAGE_TOKENS) + 1


def row_bytes(m: dict, tokens: int) -> dict:
    """What a row of ``tokens`` reserved tokens holds of each class of pages:
    the full layers' pages grow with it, a window layer's ring does not."""
    page = PAGE_TOKENS * cached_token_bytes(m)
    kinds = _kinds(m)
    return {"full": -(-tokens // PAGE_TOKENS) * kinds["*"] * page,
            "window": ring_pages(m) * kinds["W"] * page}


def _attention_work(m, layers, attended_tokens, row_steps):
    n, d = m["num_attention_heads"], m["attn_head_dim"]
    return {
        "flops": layers * attended_tokens * n * d * 2 * 2,
        "bytes": layers * (attended_tokens * cached_token_bytes(m) + row_steps * 2 * n * d * 2),
    }


def gqa_decode_work(m: dict, attended_tokens: float, row_steps: float) -> dict:
    """What ``pfx_decode_paged`` (the FULL layers' calls) must do, over the 7
    full layers, for decode steps whose live rows attended
    ``attended_tokens`` cached tokens in all (the scheduler's ``kv_tokens``)
    in ``row_steps`` (row, step) pairs.  A cached token and layer: its keys
    and values read ONCE (the 8 query heads of a KV head share them), 2 x
    heads x head_dim multiply-adds.  A (row, step) and layer: the queries read
    and the result written, 2 bytes a value.  Nothing a padded group, a spare
    page or a dead slot adds is counted: they lower the share."""
    return _attention_work(m, _kinds(m)["*"], attended_tokens, row_steps)


def window_decode_work(m: dict, attended_tokens: float, row_steps: float) -> dict:
    """What ``pfx_decode_window`` (the WINDOW layers' calls) must do, over
    the 21 window layers: as :func:`gqa_decode_work`, at the tokens the
    window layers attended (the scheduler's ``kv_window_tokens``: each live
    row's context capped at the window).  A ring's spare page, the part of
    its oldest page that lies before the window and a dead slot add nothing
    here: they lower the share."""
    return _attention_work(m, _kinds(m)["W"], attended_tokens, row_steps)


def roofline_seconds(work: dict, peaks: dict) -> float:
    """Both calls do 16 FLOPs a byte (8 query heads a KV head): the HBM
    bounds them (the v5e's ridge is 240).  The FLOP term is kept so that the
    function reads as the other configurations' do."""
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["bf16_flops_per_s"])


def decode_step_bytes(m: dict, live_rows: int, context: int) -> dict:
    """Bytes a decode step reads: the weights (the embedding's rows are
    looked up, not streamed; the head is streamed whole; every held expert
    runs on every row), the full layers' pages and the window layers' rings
    of ``live_rows`` rows at ``context`` tokens each."""
    kinds = _kinds(m)
    token = cached_token_bytes(m)
    return {"weights": weight_bytes(m) - m["vocab_size"] * m["hidden_size"] * 2,
            "experts": kinds["E"] * layer_params(m)["experts"] * 2,
            "full_pages": live_rows * context * kinds["*"] * token,
            "window_pages": live_rows * min(context, m["sliding_window"]) * kinds["W"] * token,
            "without_a_window": live_rows * context * (kinds["*"] + kinds["W"]) * token}


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Required FLOPs of one prompt's prefill: attention's and the router's
    matrices at every token, the experts at the pairs that land here (top-k x
    held / experts of them, if the router spreads them evenly), the head at
    the last token only, full attention at the causal half and window
    attention at the positions the window leaves visible."""
    p, kinds = layer_params(m), _kinds(m)
    n, d, w = m["num_attention_heads"], m["attn_head_dim"], m["sliding_window"]
    pairs = prompt_len * m["moe_top_k"] * m["moe_experts_held"] / m["num_experts"]
    expert = 3 * m["hidden_size"] * m["moe_ffn_hidden_size"]
    seen_full = prompt_len * (prompt_len + 1) / 2
    seen_window = sum(min(i + 1, w) for i in range(prompt_len))
    return (2.0 * prompt_len * kinds["E"] * (p["attention"] + p["router"])
            + 2.0 * pairs * kinds["E"] * expert + 2.0 * m["vocab_size"] * m["hidden_size"]
            + 4.0 * n * d * (kinds["*"] * seen_full + kinds["W"] * seen_window))


if __name__ == "__main__":
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "mellum2-12b-a2.5b.json")) as f:
        model = json.load(f)["model"]
    parts = layer_params(model)
    print({k: round(v / 1e6, 3) for k, v in parts.items()})
    assert parts["attention"] == 21_233_664 and parts["router"] == 147_456
    assert parts["experts"] == 16 * 6_193_152 and sum(parts.values()) == 120_476_160
    print("uncut:", param_count(model, 64), "held:", param_count(model), "bytes", weight_bytes(model))
    assert abs(param_count(model, 64) / 1e9 - 12.15) < 0.005
    assert param_count(model) == 3_826_319_616 and abs(weight_bytes(model) / 1e9 - 7.66) < 0.005
    row = row_bytes(model, 2816)
    assert ring_pages(model) == 9 and sum(row.values()) == 343 * 262_144
    assert row["window"] == 49_545_216 and 7 * cached_token_bytes(model) == 14_336
    work = window_decode_work(model, 1024.0, 1.0)
    assert work["bytes"] == 21 * (1024 * 2048 + 2 * 32 * 128 * 2)
    print("decode step, 24 live rows at 2,200 tokens:", decode_step_bytes(model, 24, 2200))
    print("a 2,048-token prefill:", prefill_flops(model, 2048) / 1e12, "TFLOP")
