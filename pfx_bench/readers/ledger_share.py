"""Share of the window one bucket of the train engine's time ledger took
(``Engine.metrics_file`` step records carry the cumulative ledger)."""


def read(ctx, bucket):
    recs, base = ctx.get("engine_records"), ctx.get("engine_base_record")
    if not recs or base is None or not ctx.get("window_s"):
        return None
    spent = recs[-1]["time_ledger"][bucket] - base["time_ledger"][bucket]
    return 100.0 * spent / ctx["window_s"]
