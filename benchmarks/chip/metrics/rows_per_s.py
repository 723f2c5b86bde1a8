"""rows_per_s: /advise rows answered inside the window, over the window's
seconds."""


def read(ctx):
    rows = sum(len(r["body"]) for r in ctx.ok()
               if ctx.w0 <= r["finish"] <= ctx.w1)
    return rows / (ctx.w1 - ctx.w0)
