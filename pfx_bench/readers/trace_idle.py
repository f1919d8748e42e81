"""1 - (union of device-op intervals) / traced window."""


def read(ctx):
    idle = (ctx.get("trace") or {}).get("idle_share")
    return None if idle is None else 100.0 * idle
