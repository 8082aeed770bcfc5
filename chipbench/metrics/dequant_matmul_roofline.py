"""Kernels: the least time the traced steps' ``dequant_matmul`` and
``dequant_matmul_t`` calls could take on this chip (the larger of
operations over peak FLOP/s and bytes over HBM bandwidth, per call, from
the shapes of each step variant) over the time their events took."""
from chipbench import trace
from chipbench.counts import model

KERNELS = {"dequant_matmul", "dequant_matmul_t"}


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peaks is None or not t.steps:
        return None
    spent = trace.kernel_ns(t.ops, KERNELS, t.lo, t.hi) / 1e9
    if spent <= 0:
        return None
    least = sum(model.matmul_least_s(ctx.ref, ctx.config, s, ctx.peaks)
                for s in t.steps)
    return 100.0 * least / spent
