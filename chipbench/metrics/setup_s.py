"""Set-up: process start to the opening of the measured window (loading,
weight making, quantising, packing, compiling every step variant)."""


def read(ctx):
    return ctx.setup_s
