"""mfu.train: model FLOPs of the steps in the traced window over the
window's seconds times the chip's peak bf16 rate. Per step the
``step_flops`` of the module the configuration names (the dense decoder's:
6 per weight per token plus the causal attention's products),
recomputation not counted."""


def read(ctx):
    if ctx.trace is None or not getattr(ctx, "steps", None):
        return None
    return 100.0 * ctx.flops_per_step * ctx.steps / (
        ctx.trace["window_s"] * ctx.peaks["bf16_flops"])
