"""Model FLOP/s utilization of the traced window: the operations the
model needs for the real prompt and generated tokens served, over the
window at the chip's bf16 peak."""

from bench.readers import mfu


def read(ctx):
    return mfu(ctx)
