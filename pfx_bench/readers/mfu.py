"""Model FLOP/s utilization: tokens per second per chip times the FLOPs the
forward and backward passes require per token (model_math.py; recompute
not counted) over the chip's bf16 peak (peaks.json)."""

import model_math


def read(ctx):
    rate = ctx.get("end_to_end", {}).get("train_tokens_per_s")
    if not rate:
        return None
    flops = model_math.train_flops_per_token(ctx["model"], ctx["seq_len"])
    return 100.0 * rate * flops / ctx["peaks"]["bf16_flops_per_s"]
