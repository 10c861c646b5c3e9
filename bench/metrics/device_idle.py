"""Share of the traced window in which no operation ran on the device."""

from bench.readers import device_idle


def read(ctx):
    return device_idle(ctx)
