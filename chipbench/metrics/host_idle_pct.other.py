"""Engine on the device: the share of the traced window in which no
operation ran on the device while the host was in no phase of the
engine: ``serve.device_wait``, the step between its phases, the
harness's spans, or no span at all. The five ``host_idle_pct`` groups
sum to ``device_idle_pct``."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_pct(ctx, "other")
