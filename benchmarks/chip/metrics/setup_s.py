"""setup_s: from the process's start to the first request of the window
(JAX start, fit or artifact load, bank build, warm-up, the warm phase)."""


def read(ctx):
    return ctx.setup_s
