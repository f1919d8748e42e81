"""Device-trace self time of one op category over device busy time."""


def read(ctx, category):
    shares = (ctx.get("trace") or {}).get("category_share")
    if not shares or shares.get(category) is None:
        return None
    return 100.0 * shares[category]
