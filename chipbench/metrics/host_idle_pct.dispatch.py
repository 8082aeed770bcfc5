"""Engine on the device: the share of the traced window in which no
operation ran on the device while the host was in ``serve.assemble`` or
``serve.dispatch``: the batch, its copy to the device and the enqueue of
the step. The five ``host_idle_pct`` groups sum to ``device_idle_pct``."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_pct(ctx, "dispatch")
