"""Model FLOP/s utilization of the traced window: the operations the
model needs for the real prompt and generated tokens served, over the
window at the bf16 peak of the configuration's chips."""

from bench.readers import mfu


def read(ctx):
    return mfu(ctx)
