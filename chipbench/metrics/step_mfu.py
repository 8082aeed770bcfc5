"""The whole step's share of the chip's peak: the operations of every
token the window processed (prompt tokens prefilled and tokens decoded,
padding left out) over the window's length and the peak FLOP/s."""
from chipbench.counts import model


def read(ctx):
    if ctx.peaks is None or not ctx.steps:
        return None
    flops = sum(model.step_flops(ctx.ref, ctx.model, s) for s in ctx.steps)
    return 100.0 * flops / (ctx.window_s * ctx.peaks["flops_per_s"])
