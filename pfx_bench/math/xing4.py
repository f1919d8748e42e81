"""Parameters held, bytes and required FLOPs of the Xing4.0 block
(configuration xing4.0-29b-a4b), from the sizes in the configuration file's
``model`` group: the benchmark's own arithmetic, like ``math/deepseek_v3.py``
for the block whose attention and expert layer it shares.  2 FLOPs a
multiply-add; lookups, norms, rotations and elementwise work not counted
unless a function says so.

    python3 pfx_bench/math/xing4.py      # self-check against the configuration's ``deployment``
"""


def _mla_params(m):
    h, n = m["hidden_size"], m["num_attention_heads"]
    ql, kl = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rot, v = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (h * ql + ql * n * (nope + rot) + h * (kl + rot) + kl * n * (nope + v) + n * v * h)


def hc_maps(m: dict) -> int:
    """Numbers a sub-block's maps take a token: h_pre, h_post, H_res."""
    return m["hc_mult"] * (2 + m["hc_mult"])


def layer_params(m: dict) -> dict:
    """Matrix parameters of one layer by part (norm scales, the routing bias
    and the maps' gates and biases left out: 0.01 M a layer).  ``maps`` is ONE
    sub-block's ``Phi``; a layer has two."""
    h, f = m["hidden_size"], m["moe_ffn_hidden_size"]
    return {
        "mla": _mla_params(m),
        "dense_mlp": 3 * h * m["ffn_hidden_size"],
        "expert": 3 * h * f,
        "shared": 3 * h * f * m["moe_shared_experts"],
        "router": h * m["num_experts"],
        "maps": hc_maps(m) * m["hc_mult"] * h,
    }


def layer_count(m: dict, expert_layer: bool) -> int:
    """Matrix parameters of a whole layer with every held expert."""
    p = layer_params(m)
    mlp = (p["shared"] + p["router"] + m["moe_experts_held"] * p["expert"]
           if expert_layer else p["dense_mlp"])
    return p["mla"] + 2 * p["maps"] + mlp


def param_count(m: dict) -> int:
    """Matrix parameters held on this chip."""
    n_dense = m["num_dense_layers"]
    return (n_dense * layer_count(m, False) + (m["num_layers"] - n_dense) * layer_count(m, True)
            + 2 * m["vocab_size"] * m["hidden_size"])


def weight_bytes(m: dict, bytes_per_weight: int = 2) -> int:
    """What the server holds: every matrix in the compute dtype, but the
    routers' kernels and the maps' ``Phi``, which stay float32."""
    p = layer_params(m)
    f32 = ((m["num_layers"] - m["num_dense_layers"]) * p["router"]
           + m["num_layers"] * 2 * p["maps"])
    return (param_count(m) - f32) * bytes_per_weight + f32 * 4


def cached_token_bytes(m: dict, bytes_per_value: int = 2) -> int:
    """One cached token in one layer: the latent and the rotated key."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * bytes_per_value


def arena_bytes(m: dict, rows: int) -> int:
    """The latent pages of ``rows`` rows at the served cap, every layer."""
    return rows * m["max_position_embeddings"] * m["num_layers"] * cached_token_bytes(m)


def mla_decode_work(m: dict, attended_tokens: float, row_steps: float) -> dict:
    """What ``pfx_decode_mla_paged`` must do, over all layers, for decode
    steps whose live rows attended ``attended_tokens`` cached tokens in all
    (the scheduler's ``kv_tokens``) in ``row_steps`` (row, step) pairs
    (``math/deepseek_v3.py``'s count at this model's 32 heads: 60 FLOPs a
    byte of latent, so the pages' read bounds it, where 128 heads sit at the
    ridge)."""
    n, kl = m["num_attention_heads"], m["kv_lora_rank"]
    w = kl + m["qk_rope_head_dim"]
    layers = m["num_layers"]
    return {
        "flops": layers * attended_tokens * n * (w + kl) * 2,
        "bytes": layers * (attended_tokens * cached_token_bytes(m)
                           + row_steps * (n * w * 2 + n * kl * 4)),
    }


def _hc_calls(m: dict) -> int:
    """Calls of each of the two kernels a forward: 2 sub-blocks a layer."""
    return 2 * m["num_layers"]


def _hc_work(m: dict, tokens: float, row_steps: float, stream_passes: int, flops: int) -> dict:
    """Bytes that MUST cross the HBM and FLOPs, over all 2 x layers calls of
    one of the two kernels, for ``tokens`` tokens that went through the maps
    once a forward (the scheduler's ``hc_tokens``) of which ``row_steps`` were
    a decode step's live rows (the rest are prefills' real prompt tokens).
    Only a PREFILL's stream is counted in bytes, ``stream_passes`` times n C
    values a token and call: 2,048 x 4 x 3,584 bfloat16 is 59 MB and lies in
    the HBM.  A decode step's whole stream is 1.8 MB, and ``u`` / ``f`` (15 MB a
    prefill) and the maps are small too: the v5e keeps arrays of 33 MB and
    less in faster memory than the HBM (PERF.md section 6, PR 54), so counting
    them at the HBM's rate would count bytes it never moves (my chip run, PR
    55: counted so, the decode calls read 1.1 TB/s and the share 99%).
    ``Phi`` (1.4 MB a call) and the Sinkhorn rounds are not counted."""
    prefill = max(tokens - row_steps, 0.0)
    calls = _hc_calls(m)
    return {"bytes": calls * prefill * stream_passes * m["hc_mult"] * m["hidden_size"] * 2,
            "flops": calls * tokens * flops}


def hc_pre_work(m: dict, tokens: float, row_steps: float = 0.0) -> dict:
    """What ``pfx_hc_pre`` must do: the stream read ONCE; the product with
    ``Phi``, the sum of squares and the read-in mix."""
    n, c = m["hc_mult"], m["hidden_size"]
    return _hc_work(m, tokens, row_steps, 1, 2 * hc_maps(m) * n * c + 2 * n * c + 2 * n * c)


def hc_post_work(m: dict, tokens: float, row_steps: float = 0.0) -> dict:
    """What ``pfx_hc_post`` must do: the stream read and written where it
    was; n (n + 1) C multiply-adds a token."""
    n, c = m["hc_mult"], m["hidden_size"]
    return _hc_work(m, tokens, row_steps, 2, 2 * n * (n + 1) * c)


def roofline_seconds(work: dict, peaks: dict) -> float:
    """The larger of the two terms bounds a kernel (all three here by their
    bytes wherever a prefill's tokens are among them: 60, 26 and 2.5 FLOPs a
    byte against the v5e's 240)."""
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["bf16_flops_per_s"])


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Required FLOPs of one prompt's prefill: every matrix at every token
    (the routed experts at top_k pairs a token: all are held), expanded
    attention at the causal half, the maps' products, the head at the last
    token only."""
    p, n_dense = layer_params(m), m["num_dense_layers"]
    n_exp = m["num_layers"] - n_dense
    per_token = (m["num_layers"] * (p["mla"] + 2 * p["maps"]) + n_dense * p["dense_mlp"]
                 + n_exp * (p["shared"] + p["router"] + m["moe_top_k"] * p["expert"]))
    pairs = prompt_len * (prompt_len + 1) // 2
    d_qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attention = m["num_layers"] * m["num_attention_heads"] * pairs * (d_qk + m["v_head_dim"])
    return 2.0 * (prompt_len * per_token + attention + m["vocab_size"] * m["hidden_size"])


def decode_step_weight_bytes(m: dict) -> int:
    """Weight bytes a decode step reads when every held expert has a row:
    the whole tree but the embedding's rows it does not look up."""
    return weight_bytes(m) - m["vocab_size"] * m["hidden_size"] * 2


if __name__ == "__main__":
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "xing4.0-29b-a4b.json")) as f:
        model = json.load(f)["model"]
    parts = layer_params(model)
    print(parts)
    assert parts == {"mla": 28_409_856, "dense_mlp": 99_090_432, "expert": 11_010_048,
                     "shared": 11_010_048, "router": 229_376, "maps": 344_064}
    dense, expert = layer_count(model, False), layer_count(model, True)
    vocab = 2 * model["vocab_size"] * model["hidden_size"]
    print("dense layer", dense, "expert layer", expert, "vocabulary", vocab)
    maps = 2 * parts["maps"]  # ISSUE 55 counts a layer without its maps: 744.3 M and 127.5 M
    assert abs((expert - maps) / 1e6 - 744.3) < 0.1 and abs((dense - maps) / 1e6 - 127.5) < 0.1
    assert abs(vocab / 1e6 - 939.5) < 0.1
    held, at_rest = param_count(model), weight_bytes(model)
    print("parameters held", held, "bytes", at_rest, "arena", arena_bytes(model, 64))
    assert abs(held / 1e9 - 4.79) < 0.01 and abs(at_rest / 1e9 - 9.59) < 0.01
    assert abs(arena_bytes(model, 64) / 1e9 - 1.13) < 0.01
    assert abs((at_rest + arena_bytes(model, 64)) / 16e9 - 0.67) < 0.01
    for name, fn in (("pfx_hc_pre", hc_pre_work), ("pfx_hc_post", hc_post_work)):
        w = fn(model, 1.0)
        print(name, "a prefill's token and forward:", w["bytes"], "bytes,", w["flops"], "FLOPs,",
              round(w["flops"] / w["bytes"], 1), "FLOPs a byte")
        assert fn(model, 5.0, 5.0)["bytes"] == 0  # a decode step's rows cross no HBM
    work = mla_decode_work(model, 1.0, 0.0)
    print("a cached token and layer:", work["flops"] / 6, "FLOPs,", work["bytes"] / 6, "bytes")
    assert work["bytes"] / 6 == 1152 and work["flops"] / 6 == 32 * (576 + 512) * 2
    print("prefill of 2048 tokens: %.2f TFLOP" % (prefill_flops(model, 2048) / 1e12))
