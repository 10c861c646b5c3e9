"""Roofline share of the int8 matmul kernel: per call, the larger of its
operations at the int8 peak and its bytes at the HBM bandwidth, for
every projection matmul the reference's counts name, times the layers
that have it, of the int8 candidates' calls, summed, over the kernel's
device time in the trace."""

from bench import flops
from bench.readers import kernel_roofline, window_calls


def least_time(ctx):
    pk = ctx.peaks
    total = 0.0
    for c in window_calls(ctx):
        if ctx.cfg["models"][c.model]["precision"] != "int8":
            continue
        a = ctx.arch(c.model)
        m = c.rows * c.tokens
        for k, n, layers in a.int8_matmuls():
            ops, nbytes = flops.int8_matmul_cost(m, k, n)
            t, _ = flops.roofline_time(ops, nbytes, pk["int8_ops"],
                                       pk["hbm_bytes_per_s"])
            total += layers * t
    return total


def read(ctx):
    return kernel_roofline(ctx, "int8_matmul", least_time)
