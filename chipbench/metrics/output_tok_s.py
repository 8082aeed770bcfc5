"""Every token emitted in the window, finished requests or not, over the
window's length."""


def read(ctx):
    return sum(r["n_tokens"] for r in ctx.requests) / ctx.window_s
