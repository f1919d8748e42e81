"""Share of the window that one cumulative seconds key of the train
engine's step records grew by (``Engine.metrics_file``: ``host_gap_s``,
``log_fetch_s``, ``log_write_s``), from the last warm-up record to the
window's last.  A program whose records lack the key gives nothing."""


def read(ctx, key):
    recs, base = ctx.get("engine_records"), ctx.get("engine_base_record")
    if not recs or base is None or not ctx.get("window_s"):
        return None
    if key not in recs[-1] or key not in base:
        return None
    return 100.0 * (recs[-1][key] - base[key]) / ctx["window_s"]
