"""transport_ms.point: median over answered requests of the client's
latency from the moment the request was sent (not its due time) minus the
service's own ``service_ms``: HTTP, JSON and the event loop, both ways."""
import statistics


def read(ctx):
    ok = ctx.ok()
    if not ok:
        return None
    return statistics.median((r["finish"] - r["sent"]) * 1e3
                             - r["service_ms"] for r in ok)
