"""Growth of one cumulative key of the train engine's step records
(``Engine.metrics_file``) over the window, per step of the window, times
``scale``: from the last warm-up record to the window's last.  A program
whose records lack the key gives nothing."""


def read(ctx, key, scale=1.0):
    recs, base = ctx.get("engine_records"), ctx.get("engine_base_record")
    if not recs or base is None or key not in recs[-1] or key not in base:
        return None
    steps = recs[-1]["step"] - base["step"]
    if steps <= 0:
        return None
    return scale * (recs[-1][key] - base[key]) / steps
