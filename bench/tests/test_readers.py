"""The per-layer metric readers of a traced run, on a window built by
hand: each number against its count from the definitions."""

import dataclasses
import os
from types import SimpleNamespace

import pytest
from repro.serving.engine import EngineStats

from bench import flops, harness, readers
from bench.tests import rehearse

PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e9}


def request(model, prompt_len, stamps, t0):
    r = SimpleNamespace(model=model, prompt=list(range(prompt_len)),
                        arrival=0.0, max_new_tokens=len(stamps),
                        tokens=harness.Stamped())
    for s in stamps:
        r.tokens.append(0)
    r.tokens.stamps = [t0 + s for s in stamps]
    return r


@pytest.fixture(scope="module")
def ctx():
    cfg, _ = rehearse.tiny()
    t0 = 100.0
    served = harness.Served(requests=[
        request("tiny_bf16", 8, [0.1, 0.2, 0.3], t0),
        request("tiny_int8", 40, [0.5], t0)], t0=t0, seconds=2.0)
    served.calls = [
        harness.Call("prefill", "tiny_int8", 0, 0, 4, 32),
        harness.Call("decode", "tiny_bf16", 0, 0, 4, 1),
        harness.Call("decode", "tiny_int8", 0, 0, 4, 1),
    ]
    stats = {"tiny_bf16": EngineStats(prefill_calls=1, prefill_time_s=0.03),
             "tiny_int8": EngineStats(prefill_calls=1, prefill_time_s=0.05)}
    trace = {"window_s": 2.0, "busy_s": 1.5,
             "kernel_s": {"int8_matmul": 1e-3}}
    return harness.Context(workload="tiny", cfg=cfg, mix=None, served=served,
                           stats=stats, setup_s=1.0, trace=trace, peaks=PEAKS)


@pytest.fixture(scope="module")
def got(ctx):
    entries = harness.metric_entries(rehearse.CELL, True)
    return {k: v["value"] for k, v in harness.read_metrics(entries,
                                                           ctx).items()}


def test_host_and_engine_layers(got):
    # due at t0; last tokens at 0.3 s and 0.5 s: the p95 of two is the later
    assert got["request_p95_ms"] == pytest.approx(500.0)
    assert got["prefill_ms"] == pytest.approx(40.0)
    assert got["device_idle"] == pytest.approx(25.0)


def test_mfu_counts_real_tokens_only(ctx, got):
    a = ctx.arch("tiny_bf16")
    # the int8 request's prompt is cut to the cell's prompt_len of 32
    want = (flops.prompt_flops(a, 8) + flops.token_flops(a, 9, logits=True)
            + flops.token_flops(a, 10, logits=True)
            + flops.prompt_flops(a, 32))
    assert got["mfu"] == pytest.approx(100 * want / (2.0 * 1e12))


def test_kernel_rooflines(ctx, got):
    """The int8 candidate's calls only: its prefill of 4 x 32 tokens and
    its decode step of 4 rows, every projection of both layers."""
    a = ctx.arch("tiny_int8")
    least = sum(2 * max(o / 2e12, b / 1e9) for m in (4 * 32, 4)
                for o, b in (flops.int8_matmul_cost(m, k, n)
                             for k, n in a.projections()))
    assert got["int8_matmul_roofline"] == pytest.approx(100 * least / 1e-3)


def test_counts_are_the_dense_formulas_to_the_last_digit(ctx, got):
    """The readers take their counts from the reference's `counts`; on
    this window they read what the dense formulas, called directly, gave
    before."""
    int8 = harness.load_module(
        os.path.join(harness.BENCH, "metrics", "int8_matmul_roofline.py"),
        "bench_metric_int8_matmul_roofline")
    assert readers.model_flops(ctx) == 6753792.0
    assert readers.mfu(ctx) == 0.0003376896
    assert got["mfu"] == 0.0003376896
    assert int8.least_time(ctx) == 0.0006963200000000002
    assert got["int8_matmul_roofline"] == 69.632


def test_mfu_divides_by_the_chips_of_the_configuration(ctx):
    """Four chips have four times one chip's peak; the int8 kernel's
    roofline sums its time over the chips and is left as it is."""
    four = dataclasses.replace(ctx, chips=4)
    assert ctx.chips == 1
    assert readers.mfu(four) == readers.mfu(ctx) / 4
    assert readers.mfu(ctx) == 100.0 * readers.model_flops(ctx) / (
        ctx.trace["window_s"] * ctx.peaks["bf16_flops"])
    got4 = harness.read_metrics(harness.metric_entries(rehearse.CELL, True),
                                four)
    got1 = harness.read_metrics(harness.metric_entries(rehearse.CELL, True),
                                ctx)
    assert got4["int8_matmul_roofline"] == got1["int8_matmul_roofline"]


def test_engine_time_per_request_spans_every_call(ctx):
    timed = harness.Served(requests=ctx.served.requests, t0=100.0,
                           seconds=2.0)
    timed.calls = [harness.Call("prefill", "tiny_int8", 1.0, 1.25, 4, 32),
                   harness.Call("decode", "tiny_bf16", 2.0, 2.05, 4, 1)]
    c = harness.Context(workload="tiny", cfg=ctx.cfg, mix=None, served=timed,
                        stats={}, setup_s=1.0)
    got = harness.read_metrics(harness.metric_entries(rehearse.CELL, False), c)
    # 0.3 s of calls over the two finished requests
    assert got["engine_ms_per_request"]["value"] == pytest.approx(150.0)


def test_a_reader_with_nothing_to_read_is_left_out(ctx):
    bare = harness.Context(workload="tiny", cfg=ctx.cfg, mix=None,
                           served=harness.Served([], 0.0, 2.0), stats={},
                           setup_s=1.0)
    entries = harness.metric_entries(rehearse.CELL, True)
    assert harness.read_metrics(entries, bare) == {}
