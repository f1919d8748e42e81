"""Share of the device's busy time that ONE named Pallas kernel took over
the traced stretch: the self time of every call of the kernel in the trace
(the runner's ``kernel_self_s``: summed over the kernel's instructions, not
``device_ops``' ten names) over the trace's ``busy_s``.  None where the trace
does not name the kernel (a parent commit that has no such kernel) or the
runner gives no kernel times."""


def read(ctx, kernel):
    seconds = (ctx.get("kernel_self_s") or {}).get(kernel)
    busy = (ctx.get("trace") or {}).get("busy_s")
    if not seconds or not busy:
        return None
    return 100.0 * seconds / busy
