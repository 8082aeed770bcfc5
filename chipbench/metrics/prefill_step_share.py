"""Engine: the share of the window's steps that ran a prefill chunk (T =
chunk); decode slots emit one token on such a step, as on any other."""


def read(ctx):
    if not ctx.steps:
        return None
    return 100.0 * sum(s["T"] > 1 for s in ctx.steps) / len(ctx.steps)
