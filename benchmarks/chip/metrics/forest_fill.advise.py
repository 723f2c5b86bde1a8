"""forest_fill.advise: rows the grouped forest kernel answered over the
rows it launched, in %. Each group's rows fill whole 128-row blocks and the
block count is bucketed, so the rest of each launch routes padding
(``bank.forest_rows`` over ``bank.forest_slots`` in /statsz ``trace``, over
the window)."""


def read(ctx):
    b, a = ctx.statsz_before.get("trace"), ctx.statsz_after.get("trace")
    if b is None or a is None or "bank.forest_slots" not in a["counters"]:
        return None
    slots = (a["counters"]["bank.forest_slots"]
             - b["counters"].get("bank.forest_slots", 0))
    if slots <= 0:
        return None
    rows = (a["counters"]["bank.forest_rows"]
            - b["counters"].get("bank.forest_rows", 0))
    return 100.0 * rows / slots
