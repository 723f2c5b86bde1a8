"""step_ms: the window's wall time, to the last step's loss, over the
training steps it ran (all the work and all the time of the window)."""


def read(ctx):
    if not getattr(ctx, "steps", None):
        return None
    return 1e3 * (ctx.w1 - ctx.w0) / ctx.steps
