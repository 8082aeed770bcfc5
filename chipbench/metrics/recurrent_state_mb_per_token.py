"""Engine: megabytes of recurrent decode state (the state outside the KV
cache, such as Mamba's SSM and conv state) that the window's steps read
and wrote for each token they emitted: the engine's counters
``recurrent_state_bytes`` and ``tokens_emitted``, as the last traced
``serve.step`` carries them. A program without the counter reports
nothing."""
from chipbench import program_trace

KEYS = ("steps_total", "tokens_emitted", "recurrent_state_bytes")


def read(ctx):
    spans = program_trace.of_run(ctx)
    if spans is None:
        return None
    steps = [a for n, _, _, a in spans
             if n == "serve.step" and all(k in a for k in KEYS)]
    if not steps:
        return None
    last = max(steps, key=lambda a: int(a["steps_total"]))
    if int(last["tokens_emitted"]) <= 0:
        return None
    return int(last["recurrent_state_bytes"]) / 1e6 / int(
        last["tokens_emitted"])
