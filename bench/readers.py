"""Arithmetic shared by the metric readers in bench/metrics/. Times are
on the harness's wall clock (time.perf_counter) unless they come from
the device trace."""

from __future__ import annotations

import math

import numpy as np


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank; inf counts as beyond all."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return None
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def finished(r) -> bool:
    return len(r.tokens) == r.max_new_tokens


def e2e_ms(ctx, r) -> float:
    """Last-token stamp minus due time; inf for an unfinished request."""
    if not finished(r):
        return math.inf
    return (r.tokens.stamps[-1] - ctx.due(r)) * 1e3


def window_calls(ctx, kind=None, model=None):
    return [c for c in ctx.served.calls
            if (kind is None or c.kind == kind)
            and (model is None or c.model == model)]


def model_flops(ctx) -> float:
    """Operations the model needs for the real prompt and generated
    tokens of every request (padding excluded): the prompt's forward
    pass with logits at its last position, then one token per decode
    step at its own context length. The counts are the configuration's
    reference's (`Context.arch`)."""
    total = 0.0
    for r in ctx.served.requests:
        if not r.tokens:
            continue
        arch = ctx.arch(r.model)
        L = min(len(r.prompt), ctx.cfg["prompt_len"])
        total += arch.prompt_ops(L)
        for j in range(1, len(r.tokens)):
            total += arch.token_ops(L + j)
    return total


def mfu(ctx):
    """Model operations over the traced window at the bf16 peak of all
    the chips the configuration runs on."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    return 100.0 * model_flops(ctx) / (ctx.chips * ctx.trace["window_s"]
                                       * ctx.peaks["bf16_flops"])


def device_idle(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def kernel_roofline(ctx, kernel: str, least_time_of_calls):
    """Sum of each call's least time over the kernel's device time."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    spent = ctx.trace["kernel_s"].get(kernel, 0.0)
    least = least_time_of_calls(ctx)
    if spent <= 0.0 or least <= 0.0:
        return None
    return 100.0 * least / spent
