"""Model FLOP/s utilization with the configuration's own arithmetic:
tokens per second per chip times the FLOPs the forward and backward passes
require per token (the ``math`` file the configuration names; recompute not
counted; routed experts at the pairs the window's records counted on held
experts) over the chip's bf16 peak (peaks.json)."""

import config_math


def read(ctx):
    rate = ctx.get("end_to_end", {}).get("train_tokens_per_s")
    math, recs, base = config_math.load(ctx), ctx.get("engine_records"), ctx.get("engine_base_record")
    if not rate or math is None or not recs or base is None or "moe_pairs_held" not in base:
        return None
    n_expert_layers = ctx["model"]["num_layers"] - ctx["model"]["num_dense_layers"]
    pairs = math.held_pairs_per_step(recs, base) / n_expert_layers / ctx["tokens_per_step"]
    flops = math.train_flops_per_token(ctx["model"], ctx["seq_len"], pairs)
    return 100.0 * rate * flops / ctx["peaks"]["bf16_flops_per_s"]
