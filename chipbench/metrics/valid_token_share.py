"""Engine: the share of the token rows the window's steps computed (slots
x T) that were tokens to process (a prompt chunk's tokens, a decoding
slot's one token): the engine's counters ``tokens_valid`` and
``tokens_computed``, as the last traced ``serve.step`` carries them."""
from chipbench import program_trace


def read(ctx):
    c = program_trace.counters(ctx)
    if c is None or c["tokens_computed"] <= 0:
        return None
    return 100.0 * c["tokens_valid"] / c["tokens_computed"]
