"""``readers/kernel_roofline.py`` with the attended tokens read under a key of
the caller's choice: the share of its roofline that ONE named Pallas kernel
reached over the traced stretch, where the work the program counted for that
kernel is not ``kv_tokens`` (a window layer's call attends each row's context
capped at the window: ``tokens_key`` ``kv_window_tokens``).  The roofline
seconds of the tokens and the (row, step) pairs between the start and the stop
of ``/admin/profile``'s capture, by the configuration's ``math`` file
(``work``), over the self time of every call of the kernel in the same trace.
None where the trace does not name the kernel or the program has no such
counter (a parent commit).  A kernel cannot beat its roofline: a reading over
100 is refused, not clipped."""

import common
import config_math


def read(ctx, kernel, work, tokens_key):
    math = config_math.load(ctx)
    seconds = (ctx.get("kernel_self_s") or {}).get(kernel)
    counters = ctx.get("profile_counters")
    if math is None or not seconds or not counters or not hasattr(math, work):
        return None
    start, stop = counters
    if tokens_key not in stop or tokens_key not in start or "row_steps" not in stop:
        return None
    tokens = stop[tokens_key] - start[tokens_key]
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
