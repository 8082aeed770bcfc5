"""Engine on the device: the share of the traced window in which no
operation ran on the device while the host was in ``serve.sample``, the
per-slot finiteness check, argmax, quarantine and deadline check. The
five ``host_idle_pct`` groups sum to ``device_idle_pct``."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_pct(ctx, "sample")
