"""idle_share.train: share of the traced window of training steps in which
no operation ran on the device (1 - busy / window, busy the union of the
device's operation intervals)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
