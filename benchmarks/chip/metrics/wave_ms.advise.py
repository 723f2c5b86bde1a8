"""wave_ms.advise: the service's wall time per wave in the window (queue
admission, cache, planner, executor, bank, completion), from /statsz."""


def read(ctx):
    b, a = ctx.statsz_before, ctx.statsz_after
    waves = a.get("waves", 0) - b.get("waves", 0)
    if waves <= 0:
        return None
    return 1e3 * (a["wall_s"] - b["wall_s"]) / waves
