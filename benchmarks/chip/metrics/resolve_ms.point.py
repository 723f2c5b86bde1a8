"""resolve_ms.point: mean lag of a request from its completion in the service
to the transport's resolving its future. A cache hit completes before
its wave executes and waits out the rest of the wave here
(``transport.resolve`` in /statsz ``trace``, over the window)."""


def read(ctx):
    d = _delta(ctx, "transport.resolve")
    return None if d is None else 1e3 * d["total_s"] / d["n"]


def _delta(ctx, name):
    """``name``'s row of /statsz ``trace`` over the window; None where the
    program keeps no such row or it did not move."""
    b, a = ctx.statsz_before.get("trace"), ctx.statsz_after.get("trace")
    if b is None or a is None or name not in a["spans"]:
        return None
    was = b["spans"].get(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
    d = {k: a["spans"][name][k] - was[k] for k in ("n", "total_s", "self_s")}
    return d if d["n"] > 0 else None
