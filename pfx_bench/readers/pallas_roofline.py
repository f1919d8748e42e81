"""Share of the bf16 peak that the Mosaic kernels of the traced steps
reached: the FLOPs those calls must compute (the configuration's ``math``
file, ``pallas_flops_per_step``: flash forward, recomputed forward and
backward; grouped products at the pairs the traced steps' records counted)
over the device time of ALL calls of the category (``category_share`` x
``busy_s`` of the trace: not the ten names of ``device_ops``, whose part of
a kernel's time would read too high) times the peak.  Compute-bound
kernels: the FLOP bound is the roofline."""

import config_math


def read(ctx):
    trace, math = ctx.get("trace") or {}, config_math.load(ctx)
    traced, base = ctx.get("traced_records"), ctx.get("traced_base_record")
    share = (trace.get("category_share") or {}).get("pallas")
    if math is None or not share or not trace.get("busy_s") or not traced or base is None:
        return None
    if "moe_pairs_held" not in base or not ctx.get("traced_steps"):
        return None
    per_step = math.pallas_flops_per_step(
        ctx["model"], ctx["global_batch_size"], ctx["seq_len"],
        math.held_pairs_per_step(traced, base))
    kernel_s = share * trace["busy_s"]
    return 100.0 * ctx["traced_steps"] * per_step / (kernel_s * ctx["peaks"]["bf16_flops_per_s"])
