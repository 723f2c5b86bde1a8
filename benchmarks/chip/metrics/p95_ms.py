"""p95_ms: 95th percentile latency of the window's requests, on the load
generator's clock, from each request's due time to the last byte of its
response. A request that failed or got no answer counts as missing: its
latency is the whole wait, to the end of the drain after the window."""
from chipbench.harness import percentile


def read(ctx):
    if not ctx.records:
        return None
    return percentile([((r["finish"] if r["status"] == 200
                         else ctx.wait_until) - r["due"]) * 1e3
                       for r in ctx.records], 0.95)
