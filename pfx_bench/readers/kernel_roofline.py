"""Share of its roofline that ONE named Pallas kernel reached over the
traced stretch: the roofline seconds of the work the program counted
between the start and the stop of the capture (``/admin/profile``'s
``counters_at_start`` / ``counters_at_stop``: the cached tokens the live
rows attended, and the (row, step) pairs), computed by the configuration's
``math`` file (``work``: the function that gives FLOPs and bytes;
``roofline_seconds``: the larger of bytes over the HBM's rate and FLOPs over
the bf16 peak, peaks.json), over the self time of every call of the kernel in
the same trace (the runner's ``kernel_self_s``: summed over the kernel's
instructions, not ``device_ops``' ten names).  None where the trace does not
name the kernel or the program has no such counters (a parent commit).  A
kernel cannot beat its roofline: a reading over 100 means the work is counted
too high or the time leaves calls out, and is refused, not clipped."""

import common
import config_math


def read(ctx, kernel, work):
    math = config_math.load(ctx)
    seconds = (ctx.get("kernel_self_s") or {}).get(kernel)
    counters = ctx.get("profile_counters")
    if math is None or not seconds or not counters:
        return None
    start, stop = counters
    if "kv_tokens" not in stop or "row_steps" not in stop:
        return None
    tokens = stop["kv_tokens"] - start["kv_tokens"]
    rows = stop["row_steps"] - start["row_steps"]
    if tokens <= 0:
        return None
    bound = math.roofline_seconds(getattr(math, work)(ctx["model"], tokens, rows), ctx["peaks"])
    share = 100.0 * bound / seconds
    if share > 100.0:
        raise common.Fail(
            f"{kernel}: {bound:.6f} s of roofline work in {seconds:.6f} s of kernel time "
            f"({share:.1f}%): the work is counted too high or the time leaves calls out")
    return share
