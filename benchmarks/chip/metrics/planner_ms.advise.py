"""planner_ms.advise: the planner's own time per wave: every request's
routing, its anchor profile rows and its min/max configurations
(``planner.plan`` in /statsz ``trace``, over the window)."""


def read(ctx):
    d = _delta(ctx, "planner.plan")
    waves = _delta(ctx, "latency_service.wave")
    if d is None or waves is None:
        return None
    return 1e3 * d["self_s"] / waves["n"]


def _delta(ctx, name):
    """``name``'s row of /statsz ``trace`` over the window; None where the
    program keeps no such row or it did not move."""
    b, a = ctx.statsz_before.get("trace"), ctx.statsz_after.get("trace")
    if b is None or a is None or name not in a["spans"]:
        return None
    was = b["spans"].get(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
    d = {k: a["spans"][name][k] - was[k] for k in ("n", "total_s", "self_s")}
    return d if d["n"] > 0 else None
