"""Set-up time: process start to window start (weights, compilation
or the compile cache, profiling, warm-up)."""


def read(ctx):
    return ctx.setup_s
