"""Kernels: the least time the traced steps' ``decode_attention`` calls
could take on this chip, counting only the cache rows each slot has
written, over the time their events took."""
from chipbench import trace
from chipbench.counts import model


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peaks is None or not t.steps:
        return None
    if ctx.ref.attention(ctx.model) is None:
        return None
    spent = trace.kernel_ns(t.ops, {"decode_attention"}, t.lo, t.hi) / 1e9
    if spent <= 0:
        return None
    least = sum(model.attention_least_s(ctx.ref, ctx.config, s, ctx.peaks)
                for s in t.steps)
    return 100.0 * least / spent
