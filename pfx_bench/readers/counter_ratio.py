"""Ratio of sums of /metrics counter deltas over the window, in percent.
Each term: {"metric": family, "labels": {...}, "sign": +1 | -1}."""

import common


def _sum(delta, terms):
    return sum(t.get("sign", 1) * common.metric_sum(delta, t["metric"], **t.get("labels", {}))
               for t in terms)


def read(ctx, numerator, denominator):
    delta = ctx.get("scrape_delta")
    if not delta:
        return None
    den = _sum(delta, denominator)
    if den <= 0:
        return None
    return 100.0 * _sum(delta, numerator) / den
