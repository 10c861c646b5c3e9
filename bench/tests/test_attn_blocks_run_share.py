"""The reader of the flash prefill kernel's gate counters
(`EngineStats.attn_blocks_run` over `attn_blocks`): the exact share,
candidates pooled, where the program keeps them, and nothing where it
does not (a program before the counters) or where no block was
counted."""

from types import SimpleNamespace

import pytest
from repro.serving.engine import EngineStats

from bench import harness
from bench.tests import rehearse


def share(stats):
    entries = [m for m in harness.metric_entries(rehearse.CELL, True)
               if m["name"] == "attn_blocks_run_share"]
    assert len(entries) == 1
    served = harness.Served(requests=[], t0=0.0, seconds=2.0)
    ctx = harness.Context(workload="tiny", cfg=None, mix=None,
                          served=served, stats=stats, setup_s=1.0)
    got = harness.read_metrics(entries, ctx)
    return got["attn_blocks_run_share"]["value"] if got else None


def test_attn_blocks_run_share_reads_the_engine_counters():
    assert share({"tiny_bf16": EngineStats(attn_blocks_run=30,
                                           attn_blocks=96),
                  "tiny_int8": EngineStats(attn_blocks_run=6,
                                           attn_blocks=32)}) \
        == pytest.approx(36 / 128)
    old = SimpleNamespace(prefill_calls=1, decode_calls=0, backfill_calls=0,
                          prefill_time_s=0.1, fetch_time_s=0.0)
    assert share({"tiny_bf16": old}) is None
    assert share({"tiny_bf16": EngineStats(prefill_calls=1)}) is None
