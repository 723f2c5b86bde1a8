"""h2d_mb.advise: bytes of host arrays handed to the bank's jitted launches
per wave, in MB: the rows' features and the block indices of each forest
launch, and the input block and head indices of each MLP apply; an array
already on the device, such as the padded forest stack the bank keeps
there, counts nothing (``bank.h2d_bytes`` in /statsz ``trace``, over
``latency_service.wave``, over the window)."""


def read(ctx):
    b, a = ctx.statsz_before.get("trace"), ctx.statsz_after.get("trace")
    if b is None or a is None or "bank.h2d_bytes" not in a["counters"]:
        return None
    waves = (a["spans"].get("latency_service.wave", {"n": 0})["n"]
             - b["spans"].get("latency_service.wave", {"n": 0})["n"])
    if waves <= 0:
        return None
    moved = (a["counters"]["bank.h2d_bytes"]
             - b["counters"].get("bank.h2d_bytes", 0))
    return moved / waves / 1e6
