"""cache_hit_share.point: requests the service answered from its epoch
cache over all requests it took in the window (/statsz counters)."""


def read(ctx):
    b, a = ctx.statsz_before, ctx.statsz_after
    n = a.get("requests", 0) - b.get("requests", 0)
    if n <= 0:
        return None
    return 100.0 * (a["cache_hits"] - b["cache_hits"]) / n
