"""Engine: megabytes of logits the window's steps copied to the host for
each token they emitted: the engine's counters ``logits_host_bytes`` and
``tokens_emitted``, as the last traced ``serve.step`` carries them."""
from chipbench import program_trace


def read(ctx):
    c = program_trace.counters(ctx)
    if c is None or c["tokens_emitted"] <= 0:
        return None
    return c["logits_host_bytes"] / 1e6 / c["tokens_emitted"]
