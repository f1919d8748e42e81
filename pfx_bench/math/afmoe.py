"""Parameters held and required FLOPs of the AFMoE block family
(configuration trinity-mini), from the sizes in the configuration file's
``model`` group: the benchmark's own arithmetic, like ``model_math.py`` for
the dense GPT.

FLOPs are what the forward and backward passes REQUIRE: 2 per multiply-add,
backward = 2 x forward, attention at the positions the mask leaves visible
(causal, and the window where a layer has one), the routed experts at the
pairs that actually landed on held experts (from the program's counters),
recomputation not counted, lookups, norms and elementwise work not counted.

``pallas_flops_per_step`` is the other quantity: what the Mosaic kernels of
ONE training step have to compute, calls that recompute included, because
it is divided by the time those calls took (kernels.pallas_roofline.train).

    python3 pfx_bench/math/afmoe.py      # self-check against PERF.md's table
"""


def _kinds(m):
    """[(is expert layer, window or 0)] per layer."""
    every, out = m["global_attn_every"], []
    for l in range(m["num_layers"]):
        is_global = every > 0 and (l + 1) % every == 0
        out.append((l >= m["num_dense_layers"], 0 if is_global else m["sliding_window"]))
    return out


def _attn_params(m):
    h, nq, nkv, d = (m["hidden_size"], m["num_attention_heads"], m["num_kv_heads"],
                     m["attn_head_dim"])
    return h * nq * d * 3 + h * nkv * d * 2  # q, gate, out; k, v


def _swiglu_params(h, f):
    return 3 * h * f


def param_count(m: dict) -> int:
    """Matrix parameters held on this chip (norm scales left out, as the
    table in PERF.md leaves them out: 0.03 M)."""
    h, f_moe = m["hidden_size"], m["moe_ffn_hidden_size"]
    dense = _attn_params(m) + _swiglu_params(h, m["ffn_hidden_size"])
    expert = (_attn_params(m) + h * m["num_experts"]
              + (m["moe_shared_experts"] + m["moe_experts_held"]) * _swiglu_params(h, f_moe))
    n_dense = m["num_dense_layers"]
    return (n_dense * dense + (m["num_layers"] - n_dense) * expert
            + 2 * m["vocab_size"] * h)


def visible_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs one sequence's mask leaves visible."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def forward_flops_per_token(m: dict, seq_len: int, held_pairs_per_token: float) -> dict:
    """By part; ``held_pairs_per_token`` is per expert layer."""
    h, nq, d = m["hidden_size"], m["num_attention_heads"], m["attn_head_dim"]
    parts = {"projections": 0.0, "attention": 0.0, "dense_mlp": 0.0, "router": 0.0,
             "shared_experts": 0.0, "routed_experts": 0.0,
             "head": 2.0 * h * m["vocab_size"]}
    expert = 2.0 * _swiglu_params(h, m["moe_ffn_hidden_size"])
    for is_expert, window in _kinds(m):
        parts["projections"] += 2.0 * _attn_params(m)
        parts["attention"] += 2.0 * 2 * nq * d * visible_pairs(seq_len, window) / seq_len
        if is_expert:
            parts["router"] += 2.0 * h * m["num_experts"]
            parts["shared_experts"] += m["moe_shared_experts"] * expert
            parts["routed_experts"] += held_pairs_per_token * expert
        else:
            parts["dense_mlp"] += 2.0 * _swiglu_params(h, m["ffn_hidden_size"])
    return parts


def train_flops_per_token(m: dict, seq_len: int, held_pairs_per_token: float) -> float:
    return 3.0 * sum(forward_flops_per_token(m, seq_len, held_pairs_per_token).values())


def pallas_flops_per_step(m: dict, batch: int, seq_len: int, held_pairs: float) -> float:
    """FLOPs the Mosaic kernels of one training step must compute under
    full recompute.  Flash attention: the forward kernel runs twice (the
    pass and its recomputation), 2 matrix products over the visible pairs
    each; the backward kernels need 5 (scores again, dP, dQ, dK, dV; the
    split schedule runs 7, the two extra are not required).  Grouped
    products (XLA lowers ``ragged_dot`` to Mosaic calls): 3 forward, 3
    recomputed, 6 backward (dX and dW of each), over ``held_pairs`` rows in
    all expert layers together."""
    nq, d = m["num_attention_heads"], m["attn_head_dim"]
    flash = sum(2.0 * nq * d * visible_pairs(seq_len, w) * batch * (2 + 2 + 5)
                for _, w in _kinds(m))
    grouped = 12 * 2.0 * held_pairs * m["hidden_size"] * m["moe_ffn_hidden_size"]
    return flash + grouped


def held_pairs_per_step(records, base) -> float:
    """Mean pairs a step put on held experts (all expert layers together)
    from the cumulative ``moe_pairs_held`` of the step records."""
    return (records[-1]["moe_pairs_held"] - base["moe_pairs_held"]) / max(
        1, records[-1]["step"] - base["step"])


if __name__ == "__main__":
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "trinity-mini.json")) as f:
        model = json.load(f)["model"]
    n = param_count(model)
    assert abs(n - 705.5e6) < 0.3e6, n
    expected_pairs = model["moe_top_k"] * model["moe_experts_held"] / model["num_experts"]
    parts = forward_flops_per_token(model, 8192, expected_pairs)
    total = sum(parts.values())
    assert abs(total - 738e6) < 1e6, total
    assert abs(parts["routed_experts"] - 50.3e6) < 0.1e6, parts
    assert abs(parts["head"] - 102.5e6) < 0.1e6, parts
    assert abs(visible_pairs(8192, 2048) / 8192 - 1792) < 1, visible_pairs(8192, 2048)
    step = pallas_flops_per_step(model, 2, 8192, 4 * 16384)
    print(json.dumps({"parameters_held": n, "forward_flops_per_token": parts,
                      "forward_total": total, "train_flops_per_token": 3 * total,
                      "pallas_flops_per_step": step}))
