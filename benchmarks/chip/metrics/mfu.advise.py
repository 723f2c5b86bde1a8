"""mfu.advise: model FLOPs of the rows the bank answered in the traced
window, over the window's seconds times the chip's peak bf16 rate. Per
row: the DNN's 2 * sum(d_in * d_out) at its widths, the linear member's
2 * (d + 1), and the forest's one compare per tree per level of the row's
group depth."""
from chipbench import roofline


def read(ctx):
    if ctx.trace is None or not ctx.bank_waves:
        return None
    d, members = ctx.n_features, ctx.cfg["members"]
    per_row = ((roofline.dnn_flops(d, ctx.cfg["dnn_layers"])
                if "dnn" in members else 0)
               + (roofline.linear_flops(d) if "linear" in members else 0))
    flops = sum(rows * per_row + steps
                for _, _, rows, steps in ctx.bank_waves)
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops"])
